"""The program's own spans and scopes in a device trace.

Since the serving program names its work itself, a traced run can say
where the time goes without wrapping the engine from outside:

* every engine phase is a host span ``serve.<phase>`` on the profiler's
  clock (``serve.step`` around each round; admit, prefix_match,
  group_setup, build_batch, the dispatches, device_sync, sample_host and
  record inside it);
* each dispatch span (``serve.*_dispatch``, ``serve.spec_verify``)
  carries its launch's counts: ``decode_rows``, ``chunk_rows``,
  ``chunk_tokens`` and ``kv_tokens``;
* each op of a step program carries the model's name scope in its
  metadata: ``gemm``, ``attention``, ``kv_commit``, ``norm`` or
  ``embed``; the paged attention kernel, whose body the program traces
  under no scope, is ``attention`` by its pinned name. An op with none
  of them is the layer scan's own slicing and stacking of weights and
  arena, or a copy the compiler inserted.

:func:`extract` keeps all of :func:`bench.harness.trace.extract`'s form,
bit for bit, and adds to it::

  "host":     the serve.* spans too, [name, start_ns, dur_ns]
  "dispatch": [[name, start_ns, dur_ns, {count: value}], ...]
  "op_scope": [[module, op, scope path], ...]   # once per program op

:class:`Spans` is :class:`bench.harness.trace.Reduced` with readings over
those keys; on a form without them (a trace of a program that names
nothing) each reading is ``None``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from bench.harness import trace as tr
from bench.harness.steps import PAGED_KERNEL

PHASE_PREFIX = "serve."
STEP_SPAN = "serve.step"
COUNTS = ("decode_rows", "chunk_rows", "chunk_tokens", "kv_tokens")
# innermost of these names on an op's scope path says what the op serves
MODEL_SCOPES = ("gemm", "attention", "kv_commit", "norm", "embed")
# the harness's own extraction, as it stands when this module loads (the
# tool that patches ``tr.extract`` needs it; both go once bench/run.py
# calls this module itself)
_harness_extract = tr.extract


def _fields(buf):
    """The fields of one protobuf message: (number, value), a value being
    an int (varint, fixed) or a memoryview (length-delimited)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Scope path of every op of every program on the device planes, by
    program id and op name, from the event metadata of an ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``: a space's planes are field
    1; a plane's name 2, event metadata 4 and stat metadata 5, both maps;
    an event metadata's name 2, display name 4 and stats 5; a stat's
    metadata id 1, integer 3 or 4, string 5 or string reference 7).
    ``jax.profiler.ProfileData`` does not show event metadata. The path is
    the op's ``tf_op`` stat, the framework op name the compiler keeps
    from the program's ``jax.named_scope``s; an op the profiler folded
    into another of the same program (``deduplicated_name``) takes that
    op's."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                md = dict(_fields(entry)).get(2)
                if md is not None:
                    md = dict(_fields(md))
                    stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        ops = []
        for k, entry in fields:
            if k != 4:
                continue
            md = dict(_fields(entry)).get(2)
            if md is None:
                continue
            op = {"name": "", "stats": {}}
            for f, v in _fields(md):
                if f == 2 and not op["name"]:
                    op["name"] = tr.op_name(bytes(v).decode())
                elif f == 4 and len(v):
                    op["name"] = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if 5 in stat:  # a string
                        value = bytes(stat[5]).decode()
                    elif 7 in stat:  # a string kept once, by reference
                        value = stat_names.get(stat[7], "")
                    else:
                        value = stat.get(3, stat.get(4))
                    op["stats"][stat_names.get(stat.get(1))] = value
            ops.append(op)
        for op in ops:
            st = op["stats"]
            if "program_id" in st and op["name"]:
                out.setdefault(str(st["program_id"]), {})[op["name"]] = \
                    str(st.get("tf_op", "")).rstrip(":")
        for op in ops:  # the folded ops take the scope of their twin
            st = op["stats"]
            prog = out.get(str(st.get("program_id")))
            if prog is not None and not prog.get(op["name"]) and \
                    st.get("deduplicated_name") in prog:
                prog[op["name"]] = prog[st["deduplicated_name"]]
    return out


def model_scope(path: str) -> Optional[str]:
    """The innermost model scope on ``path``, or None."""
    for part in reversed(path.split("/")):
        if part in MODEL_SCOPES:
            return part
    return None


_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_PAGED = re.compile(PAGED_KERNEL)


def program_id(module: str) -> str:
    """The program id a device trace names a module by:
    ``jit_f(1234)`` -> ``1234``."""
    m = _PROGRAM_ID.search(module)
    return m.group(1) if m else ""


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    form = _harness_extract(xplane_path)
    host, dispatch = [], []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PHASE_PREFIX):
                    continue
                span = [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                host.append(span)
                stats = dict(ev.stats)
                if all(k in stats for k in COUNTS):
                    dispatch.append(span + [{k: int(stats[k])
                                             for k in COUNTS}])
    with open(xplane_path, "rb") as f:
        by_program = op_scopes(f.read())
    # each op event of the form, with the program it ran in
    mods = sorted((s, s + d, n) for line, n, s, d in form["device"]
                  if line == tr.MODULES_LINE)
    starts = [s for s, _, _ in mods]
    scopes = {}
    for line, op, s, _ in form["device"]:
        if line != tr.OPS_LINE:
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= mods[k][1]:
            continue
        path = by_program.get(program_id(mods[k][2]), {}).get(op)
        if path is not None:
            scopes[(mods[k][2], op)] = path
    form["host"] = form["host"] + host
    form["dispatch"] = dispatch
    form["op_scope"] = [[m, op, p] for (m, op), p in sorted(scopes.items())]
    return form


class Spans(tr.Reduced):
    """A traced window reduced with the program's spans and scopes."""

    def __init__(self, form: dict):
        super().__init__(form)
        self.dispatch = None
        self.scopes = None
        if "dispatch" in form:
            self.dispatch = [(n, s, s + d, meta)
                             for n, s, d, meta in form["dispatch"]
                             if self.lo <= s < self.hi]
        if "op_scope" in form:
            self.scopes = {(m, op): p for m, op, p in form["op_scope"]}

    def steps(self) -> List[Tuple[int, int]]:
        """The engine steps (``serve.step`` spans) that start inside the
        window."""
        return sorted((s, e) for n, s, e in self.host
                      if n == STEP_SPAN and self.lo <= s < self.hi)

    def host_time_per_step(self) -> Optional[float]:
        """Mean seconds per engine step in which no device op runs."""
        steps = self.steps()
        if not steps or not self.ops:
            return None
        idle = tr.length(steps) - tr.length(tr.intersect(steps, self.busy))
        return idle / 1e9 / len(steps)

    def _op_modules(self) -> List[Optional[str]]:
        """The program each op of the window ran in, by the module event
        that holds its start."""
        mods = sorted((s, e, n) for n, s, e in self.modules)
        starts = [s for s, _, _ in mods]
        out = []
        for _, s, _ in self.ops:
            k = bisect.bisect_right(starts, s) - 1
            out.append(mods[k][2] if k >= 0 and s < mods[k][1] else None)
        return out

    def scope_time(self, programs: str) -> Optional[Dict[Optional[str],
                                                          float]]:
        """Device seconds of the ops of the programs named by
        ``programs`` (a regular expression on the module name), by model
        scope (None: no model scope), leaving out the loops and calls that
        hold other ops."""
        if self.scopes is None:
            return None
        rx = re.compile(programs)
        out: Dict[Optional[str], float] = {}
        named = False
        for (name, s, e), mod in zip(self.ops, self._op_modules()):
            if mod is None or not rx.search(mod) or tr.CONTAINER.match(name):
                continue
            scope = model_scope(self.scopes.get((mod, name), ""))
            named = named or scope is not None
            if scope is None and _PAGED.search(name):
                scope = "attention"
            out[scope] = out.get(scope, 0.0) + (e - s) / 1e9
        # a program whose ops carry no model scope names nothing
        return out if named else None

    def dispatch_counts(self, key: str) -> Optional[List[int]]:
        """One count of every dispatch span that starts in the window."""
        if self.dispatch is None:
            return None
        return [meta[key] for _, _, _, meta in self.dispatch]
