"""Model families, found by a configuration's ``model_type``
(``spec.family``): the phi3 family gives the values that the harness
computed before families existed, pinned below; and a new architecture
joins the benchmark as new files only, served, checked and counted by
``bench/run.py`` on the CPU with a stand-in for the chip's description."""
import dataclasses
import hashlib
import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402
from bench.tests.smoke_cells import SMOKE, make_root  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 31 + 1515
CONFIGS = os.path.join(ROOT, "bench", "configs")
BF16, SWIS4 = "phi3-mini-3.8b-bf16", "phi3-mini-3.8b-swis4"


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


# -- phi3's readings, computed by the harness before the family lookup --

# ``dataclasses.asdict`` of the program's config, with ``d_head`` given as
# the width it resolves to (``ArchConfig.head_dim``): the parent left it
# 0, which the program reads as d_model // n_heads = 96
ARCH_BF16 = {
    "name": BF16, "family": "dense", "n_layers": 32, "d_model": 3072,
    "n_heads": 32, "n_kv_heads": 32, "head_dim": 96, "d_ff": 8192,
    "vocab": 32064, "act": "silu", "glu": True, "norm": "rms",
    "rope_theta": 10000.0, "tie_embeddings": False,
    "vocab_pad_multiple": 128, "causal": True, "moe": None,
    "griffin": None, "mamba2": None, "vlm": None,
    "quant": {"cfg": {"method": "swis", "n_shifts": 4, "group_size": 4,
                      "alpha": 1.0, "bits": 8, "per_channel": False,
                      "double_shift": False, "schedule": True,
                      "round_trunc": False},
              "mode": "off", "quantize_embeddings": False,
              "act_shifts": 0, "keep_slices": None},
    "parallel": {"remat": "full", "scan_layers": True, "grad_accum": 1,
                 "sp": True, "fsdp_params": False, "fsdp_opt": True,
                 "grad_compress": False},
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "attn_chunk": 1024, "sub_quadratic": False, "has_decoder": True,
}
ARCH = {BF16: ARCH_BF16, SWIS4: dict(ARCH_BF16, name=SWIS4)}

# a chunk alone; a fused step's two chunks and two decode rows; eight
# decode rows at capacity; a chunk, an idle row and a decode row
ROWS = [[(0, 128)], [(0, 128), (512, 128), (700, 1), (900, 1)],
        [(1023, 1)] * 8, [(300, 37), (0, 0), (5, 1)]]
LAUNCH_FLOPS = [931156328448, 1903601909760, 62779293696, 280452268032]
LAUNCH_TOKENS = [(128, 1), (258, 4), (8, 8), (38, 2)]
KERNEL = [(3246391296, 125829120), (32892518400, 1084096512),
          (3221225472, 3225944064), (4643487744, 157286400)]
WEIGHT_BYTES = {BF16: 7445157888.0, SWIS4: 3821279232.0}
LAUNCH_BYTES = {
    BF16: [7546607616.0, 8480114688.0, 10669578240.0, 7595206656.0],
    SWIS4: [3922728960.0, 4856236032.0, 7045699584.0, 3971328000.0],
}

# the reference at a small cut of each file, seed 2**33 + 5, on the CPU:
# float32 logits of two rows of 24 tokens (summed in float64), the first
# four of the first position and the last four of the last
SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "vocab_size": 500}
LOGITS = {
    BF16: {"sum": 121.28687847387027, "abs": 3045.1489592238822,
           "first": [0.09166964143514633, 0.02570473589003086,
                     -0.17096957564353943, 0.2399822473526001],
           "last": [0.1155083179473877, -0.11401259899139404,
                    0.13773061335086823, -0.11466792970895767]},
    SWIS4: {"sum": -44.7385940778513, "abs": 2991.1163643684595,
            "first": [0.06682413816452026, 0.15124821662902832,
                      0.04929717630147934, -0.1695851981639862],
            "last": [-0.0016389030497521162, -0.21342049539089203,
                     0.1061682403087616, -0.14374840259552002]},
}


@pytest.mark.parametrize("name", [BF16, SWIS4])
def test_phi3_arch_config_is_pinned(name):
    cfg = _config(name)
    arch = spec.family(cfg).arch_config(cfg)
    got = dataclasses.asdict(arch)
    got.pop("d_head")
    got["head_dim"] = arch.head_dim
    assert got == ARCH[name]


@pytest.mark.parametrize("name", [BF16, SWIS4])
def test_phi3_counts_are_pinned(name):
    cfg = _config(name)
    shapes = spec.family(cfg).shapes(cfg)
    assert shapes.weight_bytes == WEIGHT_BYTES[name]
    for rows, flops, (tokens, n), nbytes, kernel in zip(
            ROWS, LAUNCH_FLOPS, LAUNCH_TOKENS, LAUNCH_BYTES[name], KERNEL):
        assert shapes.launch(rows) == {"flops": flops, "bytes": nbytes,
                                       "tokens": tokens, "rows": n}
        assert shapes.attention_kernel(rows) == {"flops": kernel[0],
                                                 "bytes": kernel[1]}


@pytest.mark.parametrize("name", [BF16, SWIS4])
def test_phi3_reference_logits_are_pinned(name):
    cfg = dict(_config(name), **SMALL)
    ref = spec.family(cfg).Reference(cfg, 2 ** 33 + 5)
    toks = np.random.default_rng(0).integers(0, 500, (2, 24)).astype(
        np.int32)
    logits = np.asarray(ref.logits(ref.final_hidden(toks)), np.float64)
    want = LOGITS[name]
    np.testing.assert_allclose(logits.sum(), want["sum"], rtol=1e-5)
    np.testing.assert_allclose(np.abs(logits).sum(), want["abs"], rtol=1e-5)
    np.testing.assert_allclose(logits[0, 0, :4], want["first"], rtol=1e-5)
    np.testing.assert_allclose(logits[1, -1, -4:], want["last"], rtol=1e-5)


@pytest.mark.parametrize("model_type", [None, "no-such-family"])
def test_unknown_model_type_names_the_families(tmp_path, model_type):
    root = make_root(tmp_path)
    cfg = dict(SMOKE)
    cfg.pop("model_type")
    if model_type:
        cfg["model_type"] = model_type
    with pytest.raises(KeyError, match=r"known: \['phi3'\]"):
        spec.family(cfg, root)


# -- a new family is new files only ------------------------------------

# hidden 64 over 4 heads is 16; the file says 32
TOY = dict(SMOKE, name="toy", model_type="toy", head_dim=32,
           num_key_value_heads=2)
TOY_PY = """from bench.families.phi3 import Reference, arch_config, shapes
"""
# a reference that takes its heads' width from hidden / heads
TOY_PY_NARROW = """from bench.families import phi3
from bench.families.phi3 import arch_config, shapes


class Reference(phi3.Reference):
    def __init__(self, cfg, seed):
        super().__init__(cfg, seed)
        self.dh = self.d // self.h
"""
# what the harness counted: attention FLOPs per (query, key) pair,
# 4 x layers x heads x head_dim
READER_PY = """def read(run):
    keys = sum(n * s + n * (n + 1) // 2 for _, rows in run.rows
               for s, n in rows)
    flops = sum(run.shapes.attention_kernel(rows)["flops"]
                for _, rows in run.rows)
    return flops / keys if keys else None
"""
READER = {"name": "attention_flops_per_key", "unit": "flops",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "itl_p95_ms", "workloads": ["toy.doc"]}


def _hashes(root):
    out = {}
    for folder, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _add_toy(root, family_py):
    """The three new files and the entries: a configuration, its family
    module and a cell, and a reader of what the harness counted."""
    files = {"bench/configs/toy.json": json.dumps(TOY),
             "bench/families/toy.py": family_py,
             "bench/metrics/attention_flops_per_key.py": READER_PY}
    for rel, text in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.doc", "config": "toy",
                               "traffic": "doc", "chips": 1, "why": "test"})
    bench["per_layer"].append(READER)
    with open(path, "w") as f:
        json.dump(bench, f)


def _run(root, trace):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", "toy.doc", "--seed", str(SEED),
                             "--seconds", "2", "--trace", str(trace)],
                            root=root, device=DEVICE)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("toy"))
    before = _hashes(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    _add_toy(root, TOY_PY)
    return root, before, bench


@pytest.mark.parametrize("trace", [0, 1])
def test_new_family_is_served_checked_and_counted(toy, trace):
    root, before, bench = toy
    out = _run(root, trace)
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0
    if trace:
        # counted at the file's head_dim: 4 x 2 layers x 4 heads x 32
        assert out["metrics"]["attention_flops_per_key"]["value"] == 1024
        assert "step_mfu" in out["metrics"]
    else:
        assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(
            out["metrics"])
    # nothing that was there changed, but for the entries appended
    after = _hashes(root)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        now = json.load(f)
    for key, old in bench.items():
        if isinstance(old, list):
            assert now[key][:len(old)] == old
        else:
            assert now[key] == old
    assert set(now) == set(bench)


def test_new_family_whose_reference_ignores_head_dim_is_not_correct(
        tmp_path):
    root = make_root(tmp_path)
    _add_toy(root, TOY_PY_NARROW)
    out = _run(root, 0)
    assert out["correct"] is False
    assert out["check"]["widest_gap"]["value"] > \
        out["check"]["widest_gap"]["limit"]
