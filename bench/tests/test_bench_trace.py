"""The reduction from a device trace to numbers: interval arithmetic on
hand-made traces, and the whole reduction on a trace recorded on a
TPU v5e (bench/tests/data)."""
import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import trace as tr  # noqa: E402
from bench.harness.steps import PAGED_KERNEL, STEP_PROGRAMS  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_intersect():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tr.length([(0, 3), (5, 8)]) == 6


def _form():
    # window [0, 100); two step spans; device ops at [10, 30) and
    # [50, 60) (overlapping ops count once); an idle gap during sampling
    return {"host": [["bench.window", 0, 100],
                     ["bench.step", 5, 40], ["bench.sample", 32, 6],
                     ["bench.step", 45, 40], ["bench.idle", 88, 10]],
            "device": [["XLA Ops", "fusion.1", 10, 15],
                       ["XLA Ops", "fusion.2", 20, 10],
                       ["XLA Ops", "custom-call.7", 50, 10],
                       ["XLA Modules", "jit_step(1)", 10, 20],
                       ["XLA Modules", "jit_step(1)", 50, 10],
                       ["XLA Modules", "jit_other(2)", 95, 10]]}


def test_busy_idle_and_programs():
    red = tr.Reduced(_form())
    assert red.window_s == 100e-9
    assert red.busy_s == 30e-9
    # steps cover [5, 45) and [45, 85): 80 ns, 30 of them busy
    assert red.idle_share_within("bench.step") == pytest.approx(50 / 80)
    assert red.program_time(r"jit_step") == (30e-9, 2)
    # clipped to the window
    assert red.program_time(r"jit_other") == (5e-9, 1)
    assert red.op_time(r"custom-call") == (10e-9, 1)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    red = tr.Reduced(_form())
    gaps = dict(red.idle_gaps())
    # [0,10): mid 5 in step 1; [30,50): mid 40 in step 1 (sample ended at
    # 38); [60,100): mid 80 in step 2
    assert gaps == {"bench.step": pytest.approx(70e-9)}
    form = _form()
    form["host"].append(["bench.sample", 35, 10])
    assert dict(tr.Reduced(form).idle_gaps())["bench.sample"] == \
        pytest.approx(20e-9)


def test_top_ops():
    red = tr.Reduced(_form())
    assert red.top_ops(2) == [["fusion.1", 15e-9], ["fusion.2", 10e-9]]


def test_op_names_and_containers():
    assert tr.op_name("%_paged_attention_pallas.11 = f32[17,32] custom-call("
                      "%copy-done.1)") == "_paged_attention_pallas.11"
    form = _form()
    form["device"].append(["XLA Ops", "while.3", 0, 90])
    assert tr.Reduced(form).top_ops(1) == [["fusion.1", 15e-9]]


def _recorded():
    with gzip.open(os.path.join(DATA, "trace_v5e.json.gz"), "rt") as f:
        return json.load(f)


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def test_recorded_trace_reduces_consistently():
    """Four engine steps of phi3-bf16.chat traced on a TPU v5e: the
    reduction agrees with a plain recount of the same events."""
    form = _recorded()
    red = tr.Reduced(form)
    lo, hi = red.lo, red.hi
    ops = [(s, s + d) for line, _, s, d in form["device"]
           if line == tr.OPS_LINE]
    busy = sum(e - s for s, e in _merge(ops))
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert 0 < red.busy_s <= red.window_s == pytest.approx((hi - lo) / 1e9)
    steps = [(s, s + d) for n, s, d in form["host"] if n == "bench.step"]
    assert len(steps) == 4
    launches = [n for n, _, _ in red.host if n.startswith("bench.dispatch")]
    secs, n_prog = red.program_time(STEP_PROGRAMS)
    assert n_prog == len(launches) == 4
    assert 0 < secs <= red.busy_s
    k_secs, n_kernel = red.op_time(PAGED_KERNEL)
    # one kernel launch per layer per step
    assert n_kernel == 4 * 32 and 0 < k_secs < secs
    share = red.idle_share_within("bench.step")
    assert 0 <= share < 1
    labels = {k for k, _ in red.idle_gaps()}
    assert labels <= {n for n, _, _ in red.host} | {"none"}
    assert all(not tr.CONTAINER.match(k) for k, _ in red.top_ops())
