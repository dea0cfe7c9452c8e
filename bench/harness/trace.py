"""Device trace of a traced run, and its reduction to numbers.

``capture`` records a ``jax.profiler`` trace (no Python tracer: only the
harness's ``bench.*`` host spans and the device's own events). ``extract``
turns the ``.xplane.pb`` into a small neutral form::

  {"device": [[line, name, start_ns, dur_ns], ...],   # device planes
   "host":   [[name, start_ns, dur_ns], ...]}          # bench.* spans

and the functions below reduce that form; tests feed them a form recorded
on the chip (``bench/tests/data``).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_LINES = (OPS_LINE, MODULES_LINE)


def capture_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    device.append([line.name, op_name(ev.name),
                                   int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"device": device, "host": host}


def op_name(text: str) -> str:
    """An op event on a TPU is named by its HLO instruction's text,
    ``%name.12 = f32[...] custom-call(...)``: keep ``name.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


# -- intervals ---------------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> List[Tuple[int, int]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


# -- reduction ---------------------------------------------------------------

CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?$")


class Reduced:
    """A traced window, reduced. Times in nanoseconds on the trace's
    clock; the window is the ``bench.window`` host span."""

    def __init__(self, form: dict):
        host = form["host"]
        win = [(s, s + d) for n, s, d in host if n == "bench.window"]
        if not win:
            raise ValueError("trace has no bench.window span")
        self.lo, self.hi = win[0]
        ops = [(n, s, s + d) for line, n, s, d in form["device"]
               if line == OPS_LINE]
        mods = [(n, s, s + d) for line, n, s, d in form["device"]
                if line == MODULES_LINE]
        self.ops = [(n, max(s, self.lo), min(e, self.hi))
                    for n, s, e in ops if e > self.lo and s < self.hi]
        self.modules = [(n, max(s, self.lo), min(e, self.hi))
                        for n, s, e in mods if e > self.lo and s < self.hi]
        self.busy = union((s, e) for _, s, e in self.ops)
        self.host = [(n, s, s + d) for n, s, d in host
                     if n != "bench.window"]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return length(self.busy) / 1e9

    def spans(self, name: str) -> List[Tuple[int, int]]:
        return union((s, e) for n, s, e in self.host if n == name)

    def program_time(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and launches of the programs whose name matches
        ``pattern`` (a regular expression on the module name)."""
        rx = re.compile(pattern)
        hits = [(s, e) for n, s, e in self.modules if rx.search(n)]
        return length(hits) / 1e9, len(hits)

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and count of the ops whose name matches."""
        rx = re.compile(pattern)
        hits = [(s, e) for n, s, e in self.ops if rx.search(n)]
        return sum(e - s for s, e in hits) / 1e9, len(hits)

    def idle_share_within(self, name: str) -> Optional[float]:
        """Share of the union of host spans ``name`` in which no device op
        runs; nothing where the trace holds no device op at all."""
        if not self.ops:
            return None
        spans = self.spans(name)
        total = length(spans)
        if not total:
            return None
        return 1.0 - length(intersect(spans, self.busy)) / total

    def top_ops(self, n: int = 10) -> List[list]:
        """The ops that took most device time, leaving out the loops and
        calls that contain other ops."""
        tot: Dict[str, int] = collections.defaultdict(int)
        for name, s, e in self.ops:
            if CONTAINER.match(name):
                continue
            tot[name] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time on the device, by what the host was doing: each gap
        between busy intervals goes to the innermost ``bench.*`` span
        (the latest to start) that covers its midpoint."""
        gaps, prev = [], self.lo
        for s, e in self.busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if prev < self.hi:
            gaps.append((prev, self.hi))
        by: Dict[str, int] = collections.defaultdict(int)
        names = {}
        for name in {h[0] for h in self.host}:
            spans = sorted((hs, he) for n_, hs, he in self.host if n_ == name)
            names[name] = ([a for a, _ in spans], spans)
        for s, e in gaps:
            mid = (s + e) // 2
            label, latest = "none", None
            for name, (starts, spans) in names.items():
                # spans of one name follow each other without overlap
                k = bisect.bisect_right(starts, mid) - 1
                if k >= 0 and spans[k][1] >= mid and (
                        latest is None or spans[k][0] > latest):
                    label, latest = name, spans[k][0]
            by[label] += e - s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]
