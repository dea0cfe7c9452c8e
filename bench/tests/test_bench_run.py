"""The harness end to end on the CPU, with a stand-in for the chip's
description: it finds a cell's pieces by name, prints the contract's last
line, reads correct on a sound run, and reads not correct when the timed
path is broken underneath. Without a TPU the real entry point exits
non-zero and prints no result."""
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.tests.smoke_cells import make_root  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def _run(root, cell, trace=0, fault=None, seconds=2):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", cell, "--seed", str(SEED),
                             "--seconds", str(seconds), "--trace",
                             str(trace)], root=root, device=DEVICE,
                            fault=fault)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_cell_pieces_are_found_by_name(root):
    cell = spec.load_cell("smoke.doc", root)
    assert cell.config["name"] == "smoke" and cell.mix["loop"] == "closed"
    # a metric with no workloads key follows its end-to-end metric
    assert "queue_wait_p50_ms" not in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}
    chat = spec.load_cell("smoke.chat", root)
    assert "queue_wait_p50_ms" in {m["name"] for m in chat.per_layer}
    # a new metric is a file of its own plus an entry
    with open(os.path.join(root, "bench", "metrics", "answer.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    assert spec.reader("answer", root)(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root)


@pytest.mark.parametrize("cell", ["smoke.chat", "smoke-swis.chat",
                                  "smoke.doc"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"] is True, out["check"]
    assert list(out)[-1] == "check"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in spec.load_cell(cell, root).end_to_end}
    assert set(out["metrics"]) == want
    assert out["device"]["kind"] == "TPU v5 lite"
    for name in ("widest_gap", "logit_err", "failed_requests",
                 "tokens_compared"):
        assert set(out["check"][name]) == {"value", "limit"}


def _alter_tokens(engine):
    """A token altered where it is produced: every fifth decode step
    records the next id instead of the sampled one."""
    record = engine.scheduler.record_decode
    calls = {"n": 0}

    def altered(toks):
        calls["n"] += 1
        toks = np.asarray(toks).copy()
        if calls["n"] % 5 == 0:
            toks = (toks + 1) % 500
        return record(toks)
    engine.scheduler.record_decode = altered


def _rebind(engine):
    # the scheduler is replaced by reset(); patch the live one per step
    step = engine.step

    def stepped():
        if not getattr(engine.scheduler, "_altered", False):
            _alter_tokens(engine)
            engine.scheduler._altered = True
        return step()
    engine.step = stepped


@pytest.mark.parametrize("cell", ["smoke.chat", "smoke-swis.chat"])
def test_altered_token_is_not_correct(root, cell):
    out = _run(root, cell, fault=_rebind)
    assert out["correct"] is False
    assert out["check"]["widest_gap"]["value"] > \
        out["check"]["widest_gap"]["limit"]


def _scale_logits(engine):
    """Logits scaled where they are produced, by a larger unembedding:
    every served token is still the argmax, so only the comparison of
    logits can see it."""
    embed = engine.params["embed"]
    embed["unembed"] = embed["unembed"] * 1.25


@pytest.mark.parametrize("cell", ["smoke.chat", "smoke.doc"])
def test_scaled_logits_are_not_correct(root, cell):
    out = _run(root, cell, fault=_scale_logits)
    assert out["correct"] is False
    check = out["check"]
    assert check["widest_gap"]["value"] <= check["widest_gap"]["limit"]
    assert check["logit_err"]["value"] > check["logit_err"]["limit"]


def test_traced_run_reports_per_layer_metrics_only(root):
    out = _run(root, "smoke.chat", trace=1)
    names = set(out["metrics"])
    layer = {m["name"] for m in spec.load_cell("smoke.chat", root).per_layer}
    assert names <= layer and "queue_wait_p50_ms" in names
    # no device trace on the CPU: nothing read from it is reported
    assert "step_device_ms" not in names and "device_idle_share" not in names
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "phi3-bf16.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
