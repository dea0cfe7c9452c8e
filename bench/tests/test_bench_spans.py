"""The reduction of the program's own spans and scopes
(``bench/harness/spans.py``) and the readers over it: exact on a
hand-made trace, unchanged from ``trace.Reduced`` on the recorded trace
that predates them, and on the CPU the dispatch counts agree with the
launches the harness records from outside."""
import gzip
import io
import json
import os
import sys
from contextlib import redirect_stderr, redirect_stdout
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import counts, spec, spans  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.steps import PAGED_KERNEL, STEP_PROGRAMS  # noqa: E402
from bench.tests.smoke_cells import make_root  # noqa: E402
from bench.tools import trace_program  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = ("step_device_ms", "step_roofline", "paged_attention_roofline",
       "step_mfu", "device_idle_share")
NEW = ("step_host_ms", "gemm_device_ms", "layer_scan_ms",
       "decode_rows_per_step")
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _read(name, run):
    return spec.reader(name)(run)


def _form():
    """Window [0, 100): two engine steps; a chunk step's program at
    [10, 30) (a GEMM, then a copy of the scan's), a decode step's at
    [50, 60) (the attention kernel inside the scan's loop), and another
    program at [90, 95) whose GEMM is no step program's."""
    return {
        "host": [["bench.window", 0, 100], ["bench.step", 4, 82],
                 ["serve.step", 5, 40], ["serve.build_batch", 6, 2],
                 ["serve.mixed_dispatch", 8, 2],
                 ["serve.sample_host", 30, 12], ["serve.step", 45, 40],
                 ["serve.decode_dispatch", 48, 2],
                 ["serve.record", 70, 10]],
        "device": [["XLA Modules", "jit__unknown(1)", 10, 20],
                   ["XLA Ops", "fusion.1", 10, 15],
                   ["XLA Ops", "copy.2", 25, 5],
                   ["XLA Modules", "jit__unknown(2)", 50, 10],
                   ["XLA Ops", "while.4", 50, 10],
                   ["XLA Ops", "_paged_attention_pallas.3", 51, 8],
                   ["XLA Modules", "jit_other(3)", 90, 5],
                   ["XLA Ops", "fusion.1", 90, 5]],
        "dispatch": [
            ["serve.mixed_dispatch", 8, 2, {"decode_rows": 1,
                                            "chunk_rows": 1,
                                            "chunk_tokens": 16,
                                            "kv_tokens": 40}],
            ["serve.decode_dispatch", 48, 2, {"decode_rows": 2,
                                              "chunk_rows": 0,
                                              "chunk_tokens": 0,
                                              "kv_tokens": 50}]],
        "op_scope": [
            ["jit__unknown(1)", "fusion.1",
             "jit(_unknown)/while/body/checkpoint/gemm/dot_general"],
            ["jit__unknown(1)", "copy.2", "jit(_unknown)/while/body/"
             "dynamic_slice"],
            ["jit__unknown(2)", "while.4", "jit(_unknown)/while"],
            # the kernel's body is traced under no model scope: its
            # pinned name says it is attention
            ["jit__unknown(2)", "_paged_attention_pallas.3",
             "jit(_unknown)/while/body/jit(_paged_attention_pallas)/"
             "_paged_attention_pallas/pallas_call"],
            ["jit_other(3)", "fusion.1", "jit(other)/gemm/dot_general"]],
    }


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(num, value):
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited value."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(ops):
    """An XSpace with one TPU plane whose event metadata are ``ops``:
    (display name, {stat: value}), a value an int or a string; strings
    under ``ref:`` are kept once and referred to."""
    stats = ["program_id", "tf_op", "deduplicated_name"]
    refs = sorted({v[4:] for _, st in ops for v in st.values()
                   if isinstance(v, str) and v.startswith("ref:")})
    names = stats + refs
    plane = _field(2, "/device:TPU:0")
    for i, name in enumerate(names, 1):
        plane += _field(5, _field(1, i) + _field(2, _field(1, i)
                                                 + _field(2, name)))
    for j, (op, st) in enumerate(ops, 1):
        md = _field(1, j) + _field(2, f"%{op} = f32[] fusion()") + \
            _field(4, op)
        for key, value in st.items():
            stat = _field(1, names.index(key) + 1)
            if isinstance(value, int):
                stat += _field(3, value)
            elif value.startswith("ref:"):
                stat += _field(7, names.index(value[4:]) + 1)
            else:
                stat += _field(5, value)
            md += _field(5, stat)
        plane += _field(4, _field(1, j) + _field(2, md))
    host = _field(2, "/host:CPU")
    return _field(1, host) + _field(1, plane)


def test_op_scopes_are_read_from_the_event_metadata():
    big = 2 ** 64 - 5  # program ids are unsigned 64-bit
    space = _xspace([
        ("fusion.1", {"program_id": big,
                      "tf_op": "jit(f)/while/body/attention/gemm/dot:"}),
        ("copy.2", {"program_id": big,
                    "tf_op": "ref:jit(f)/while/body/dynamic_slice:"}),
        ("copy.3", {"program_id": big, "deduplicated_name": "copy.2"}),
        ("fusion.1", {"program_id": 7, "tf_op": "jit(g)/norm/rsqrt:"}),
        ("copy.4", {"program_id": 7})])
    assert spans.op_scopes(space) == {
        str(big): {"fusion.1": "jit(f)/while/body/attention/gemm/dot",
                   "copy.2": "jit(f)/while/body/dynamic_slice",
                   "copy.3": "jit(f)/while/body/dynamic_slice"},
        "7": {"fusion.1": "jit(g)/norm/rsqrt", "copy.4": ""}}
    assert spans.program_id(f"jit__unknown({big})") == str(big)


def test_model_scope_is_the_innermost():
    assert spans.model_scope("jit(f)/attention/gemm/dot_general") == "gemm"
    assert spans.model_scope("jit(f)/attention/kv_commit/scatter") == \
        "kv_commit"
    assert spans.model_scope("jit(f)/while/body/dynamic_slice") is None
    assert spans.model_scope("") is None


def test_spans_read_the_hand_made_trace():
    red = spans.Spans(_form())
    run = SimpleNamespace(trace=red)
    assert red.steps() == [(5, 45), (45, 85)]
    # busy [10, 30) and [50, 60): steps idle 20 and 30 of their 40 ns
    assert red.host_time_per_step() == pytest.approx(25e-9)
    assert _read("step_host_ms", run) == pytest.approx(25e-6)
    by = red.scope_time(STEP_PROGRAMS)
    # the loop holding the kernel is left out; the other program's GEMM
    # is no step program's
    assert by == {"gemm": pytest.approx(15e-9),
                  None: pytest.approx(5e-9),
                  "attention": pytest.approx(8e-9)}
    assert _read("gemm_device_ms", run) == pytest.approx(1e3 * 7.5e-9)
    assert _read("layer_scan_ms", run) == pytest.approx(1e3 * 2.5e-9)
    assert red.dispatch_counts("kv_tokens") == [40, 50]
    assert _read("decode_rows_per_step", run) == 1.5
    # each idle gap goes to the innermost program phase at its midpoint:
    # [0, 10) to the step, [30, 50) to sampling, [60, 90) to recording
    assert dict(red.idle_gaps()) == {
        "serve.step": pytest.approx(10e-9),
        "serve.sample_host": pytest.approx(20e-9),
        "serve.record": pytest.approx(30e-9), "none": pytest.approx(5e-9)}


def test_a_program_that_names_nothing_reads_none():
    form = _form()
    form["op_scope"] = [[m, op, "jit(f)/while/body/fusion"]
                        for m, op, _ in form["op_scope"]]
    assert spans.Spans(form).scope_time(STEP_PROGRAMS) is None
    for key in ("dispatch", "op_scope"):
        del form[key]
    run = SimpleNamespace(trace=spans.Spans(form))
    assert _read("gemm_device_ms", run) is None
    assert _read("decode_rows_per_step", run) is None
    # the harness's own reduction offers none of the readings
    run = SimpleNamespace(trace=tr.Reduced(_form()))
    for name in NEW:
        assert _read(name, run) is None


def _recorded(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def _run_data(red, form):
    """What a reader gets, with the launches' rows and the steps' host
    times made up (the recorded trace keeps neither)."""
    steps = [(s / 1e9, (s + d) / 1e9) for n, s, d in form["host"]
             if n == "bench.step"]
    rows = [("mixed", [(0, 128), (512, 128), (700, 1), (900, 1)])] * len(
        [n for n, _, _ in red.host if n.startswith("bench.dispatch")])
    with open(os.path.join(os.path.dirname(DATA), os.pardir, "configs",
                           "phi3-mini-3.8b-bf16.json")) as f:
        cfg = json.load(f)
    return SimpleNamespace(
        win=SimpleNamespace(step_spans=steps), rows=rows, trace=red,
        shapes=spec.family(cfg).shapes(cfg),
        peak=counts.peaks("TPU v5 lite"),
        traced=(steps[0][0], steps[-1][1]), events=[], cell=None)


# the five readers on the recorded trace with the made-up rows, as the
# harness read them before the program named its own work: a reduction
# of the program's spans must not move them
OLD_VALUES = {
    "step_device_ms": 300.45531274999996,
    "step_roofline": 3.4461798307070834,
    "paged_attention_roofline": 0.6378683090562945,
    "step_mfu": 3.1131159702995728,
    "device_idle_share": 3.190897572708673,
}


def test_old_trace_reads_as_before():
    form = _recorded("trace_v5e.json.gz")
    old, new = tr.Reduced(form), spans.Spans(form)
    assert new.busy_s == old.busy_s and new.window_s == old.window_s
    assert new.host == old.host and new.ops == old.ops
    assert new.spans("bench.step") == old.spans("bench.step")
    for pattern in (STEP_PROGRAMS, PAGED_KERNEL):
        assert new.program_time(pattern) == old.program_time(pattern)
        assert new.op_time(pattern) == old.op_time(pattern)
    assert new.idle_share_within("bench.step") == \
        old.idle_share_within("bench.step")
    assert new.top_ops() == old.top_ops()
    assert new.idle_gaps() == old.idle_gaps()
    got = {name: _read(name, _run_data(new, form)) for name in OLD}
    assert got == {name: _read(name, _run_data(old, form)) for name in OLD}
    assert got == OLD_VALUES
    # it names nothing of the program's: the new readings are absent
    for name in NEW:
        assert _read(name, _run_data(new, form)) is None


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def test_new_readers_on_a_recorded_program_trace():
    """Three engine steps of phi3-bf16.longdoc traced on a TPU v5e with
    the program's spans and scopes (bench/tools/trace_program.py): each
    reader agrees with a plain recount of the same events."""
    form = _recorded("trace_v5e_spans.json.gz")
    red = spans.Spans(form)
    run = SimpleNamespace(trace=red)
    steps = [(s, s + d) for n, s, d in form["host"] if n == "serve.step"]
    assert len(steps) == len(red.steps()) == 3
    ops = [(s, s + d) for line, _, s, d in form["device"]
           if line == tr.OPS_LINE]
    busy = _merged(ops)
    idle = sum(e - s - sum(max(0, min(e, b) - max(s, a)) for a, b in busy)
               for s, e in steps)
    assert _read("step_host_ms", run) == pytest.approx(idle / 3 / 1e6)
    # device time by scope, recounted from each op's program and path
    mods = sorted((s, s + d, n) for line, n, s, d in form["device"]
                  if line == tr.MODULES_LINE and n.startswith("jit__unknown"))
    paths = {(m, op): p for m, op, p in form["op_scope"]}
    by = {}
    for line, name, s, d in form["device"]:
        mod = next((n for a, b, n in mods if a <= s < b), None)
        if line != tr.OPS_LINE or mod is None or tr.CONTAINER.match(name):
            continue
        scope = spans.model_scope(paths.get((mod, name), ""))
        if scope is None and name.startswith("_paged_attention_pallas"):
            scope = "attention"
        by[scope] = by.get(scope, 0) + d
    gemm = _read("gemm_device_ms", run)
    scan = _read("layer_scan_ms", run)
    assert gemm == pytest.approx(by["gemm"] / 3 / 1e6)
    assert scan == pytest.approx(by[None] / 3 / 1e6)
    # between the GEMM weights' least time (7.44 GB at 819 GB/s) and the
    # step programs' device time; the scopes share that time out whole
    secs, _ = red.program_time(STEP_PROGRAMS)
    assert 9.0 < gemm < 1e3 * secs / 3
    assert sum(by.values()) / 1e9 == pytest.approx(secs, rel=1e-3)
    assert _read("decode_rows_per_step", run) == 0.0  # chunk steps only
    assert red.dispatch_counts("chunk_tokens") == [128, 128, 128]
    # the arena copies, the scan's slices and its stacking of the new
    # arena are the scan's; the MLP's three fusions are GEMMs
    for op, scope in (("copy.148", None), ("copy.179", None),
                      ("constant_dynamic-slice_fusion.12", None),
                      ("constant_dynamic-update-slice_fusion.4", None),
                      ("fusion.163", "gemm"), ("fusion.165", "gemm")):
        got = {spans.model_scope(p) for (_, o), p in paths.items()
               if o == op}
        assert got == {scope}, op
    # the program's phases take all the idle time inside the steps: a
    # gap goes to a phase, or to a harness span nested in one (the
    # harness times sampling inside ``serve.sample_host``), never to a
    # whole step
    phases = [(s, s + d) for n, s, d in form["host"]
              if n.startswith("serve.") and n != "serve.step"]
    labels = {k for k, _ in red.idle_gaps()}
    assert labels and not labels & {"serve.step", "bench.step", "none"}
    for name in labels - {n for n, _, _ in form["host"]
                          if n.startswith("serve.")}:
        for _, s, d in (h for h in form["host"] if h[0] == name):
            assert any(a <= s and s + d <= b for a, b in phases), name


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def test_tool_reads_dispatch_counts_on_the_cpu(root, tmp_path):
    """The tool's traced smoke run: the dispatch spans' counts are in the
    trace (no device plane on the CPU, so nothing read from device ops),
    the fixture holds a chunk and a decode step, and the step wall times
    are reported."""
    fixture = str(tmp_path / "form.json.gz")
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = trace_program.main(
            ["--workload", "smoke.doc", "--seed", str(2 ** 31 + 5),
             "--seconds", "2", "--trace", "1", "--fixture", fixture],
            root=root, device=DEVICE)
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True
    assert 0 < result["metrics"]["decode_rows_per_step"]["value"] <= 4
    for name in ("step_host_ms", "gemm_device_ms", "layer_scan_ms"):
        assert name not in result["metrics"]
    walls = [json.loads(line) for line in err.getvalue().splitlines()
             if line.startswith('{"steps_in_first_s"')]
    assert walls and walls[0]["n"] > 0 and walls[0]["mean_ms"] > 0
    with gzip.open(fixture, "rt") as f:
        form = json.load(f)
    kinds = {n for n, *_ in form["dispatch"]}
    assert {"serve.mixed_dispatch", "serve.decode_dispatch"} <= kinds
    assert [h[0] for h in form["host"]].count("serve.step") == 3
    # the harness's own extraction is back in place
    assert tr.Reduced is not spans.Spans


def test_dispatch_counts_match_the_recorded_launches(tmp_path):
    """The counts the program attaches to its dispatch spans agree with
    the row layouts the harness records by wrapping the step programs."""
    import jax
    import numpy as np

    from bench.harness import program
    from bench.tests.smoke_cells import SMOKE

    engine, rec = program.build(SMOKE, SMOKE["deployment"], 3)
    # on the CPU a device array may share the host block tables' memory,
    # which the engine edits after the launch: give each launch its own
    tables = engine.cache.block_tables
    engine.cache.tables_device = lambda: jax.numpy.array(tables, copy=True)
    rng = np.random.default_rng(0)
    for n in (40, 23, 9):
        program.submit(engine, rng.integers(0, 500, n, np.int32), 6)
    engine.step()  # the second and third wait for the first's chunk
    program.submit(engine, rng.integers(0, 500, 30, np.int32), 4)
    rec.on = True
    jax.profiler.start_trace(str(tmp_path))
    engine.drain()
    jax.profiler.stop_trace()
    form = spans.extract(tr.find_xplane(str(tmp_path)))
    launches = rec.rows()
    assert len(form["dispatch"]) == len(launches) > 3
    for (name, _, _, got), (kind, rows) in zip(form["dispatch"], launches):
        assert name == f"serve.{kind}_dispatch"
        assert got["decode_rows"] + got["chunk_rows"] == len(rows)
        assert got["decode_rows"] + got["chunk_tokens"] == \
            sum(n for _, n in rows)
        assert got["kv_tokens"] == sum(s + n for s, n in rows)
        if kind == "decode":
            assert got["chunk_rows"] == 0
    assert {k for k, _ in launches} == {"mixed", "decode"}
