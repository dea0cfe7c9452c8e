"""The float32 reference and its seeded weights, on the CPU at smoke size:
the engine's served tokens and logits agree with it for dense and packed
weights, the fp8 control, an altered token and logits off by a scale or
an offset do not, and a seed draws the same weights in any process."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import weights as W  # noqa: E402
from bench.harness.reference import Reference, swis_decode, widest_gap  # noqa: E402
from bench.tests.smoke_cells import SMOKE, SWIS  # noqa: E402

SEED = 2 ** 33 + 5  # wider than 32 bits


def _serve(cfg, n=4, seed=SEED):
    """Served (prompt, tokens) pairs and the logits rows each token was
    sampled from, as the harness records them."""
    from bench.harness import program

    engine, rec = program.build(cfg, cfg["deployment"], seed)
    rec.keep_logits = True
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], int(k), np.int32)
               for k in (7, 40, 90, 23)[:n]]
    rids = [program.submit(engine, p, m) for p, m in zip(prompts,
                                                         (20, 9, 30, 14))]
    out = engine.drain()
    seqs = [(p, out[r][len(p):]) for p, r in zip(prompts, rids)]
    logits = [rec.served_logits(r, len(s)) for r, (_, s) in zip(rids, seqs)]
    return seqs, logits


@pytest.fixture(scope="module", params=["dense", "swis"])
def served(request):
    cfg = SMOKE if request.param == "dense" else SWIS
    seqs, logits = _serve(cfg)
    return cfg, seqs, logits


def test_engine_agrees_with_reference(served):
    cfg, seqs, logits = served
    got = widest_gap(Reference(cfg, SEED), seqs, pad_len=128, pad_seqs=4,
                     logits=logits)
    assert got["tokens"] == 20 + 9 + 30 + 14
    assert got["widest_gap"] <= cfg["limits"]["widest_gap"]
    assert got["logit_err"] <= cfg["limits"]["logit_err"]


def test_fp8_control_fails_the_limit(served):
    cfg, seqs, _ = served
    got = widest_gap(Reference(cfg, SEED), seqs, pad_len=128, pad_seqs=4,
                     control=True)
    assert got["widest_gap"] > cfg["limits"]["widest_gap"]
    assert got["logit_err"] > cfg["limits"]["logit_err"]


@pytest.mark.parametrize("fault", ["scaled", "offset", "lost_row"])
def test_logits_off_by_a_scale_or_offset_fail_the_limit(served, fault):
    """Faults that leave every argmax where it was: only the comparison
    of logits sees them."""
    cfg, seqs, logits = served
    if fault == "scaled":
        bad = [x * 1.25 for x in logits]
    elif fault == "offset":
        bad = [x + 0.5 * np.std(x) for x in logits]
    else:
        bad = logits[:1] + [None] + logits[2:]
    got = widest_gap(Reference(cfg, SEED), seqs, pad_len=128, pad_seqs=4,
                     logits=bad)
    assert got["widest_gap"] <= cfg["limits"]["widest_gap"]
    assert got["logit_err"] > cfg["limits"]["logit_err"]


def test_an_altered_token_fails_the_limit(served):
    cfg, seqs, _ = served
    p, s = seqs[2]
    bad = s.copy()
    bad[len(s) // 2] = (bad[len(s) // 2] + 1) % cfg["vocab_size"]
    got = widest_gap(Reference(cfg, SEED), seqs[:2] + [(p, bad)] + seqs[3:],
                     pad_len=128, pad_seqs=4)
    assert got["widest_gap"] > cfg["limits"]["widest_gap"]


def test_wrong_seed_fails_the_limit(served):
    cfg, seqs, _ = served
    got = widest_gap(Reference(cfg, SEED + 1), seqs, pad_len=128,
                     pad_seqs=4)
    assert got["widest_gap"] > cfg["limits"]["widest_gap"]


def _checksum(seed):
    """Sums of every leaf of a small drawn tree and of the reference's
    layer 1."""
    from bench.harness.program import arch_config, draw_params

    out = {}
    for cfg in (SMOKE, SWIS):
        tree = draw_params(cfg, arch_config(cfg), seed)
        leaves = jax.tree_util.tree_leaves_with_path(tree)
        out[cfg["name"]] = {jax.tree_util.keystr(k): float(
            jnp.sum(v.astype(jnp.float32))) for k, v in leaves}
        ref = Reference(cfg, seed).layer_weights(1)
        out[cfg["name"] + "/ref1"] = {k: float(jnp.sum(v))
                                      for k, v in ref.items()}
    return out


def test_same_seed_same_weights_in_two_processes():
    code = ("import json, sys; sys.path.insert(0, %r); "
            "from bench.tests.test_bench_reference import _checksum; "
            "print(json.dumps(_checksum(%d)))" % (ROOT, SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONHASHSEED="123")  # a different salt from this process
    src = os.path.join(ROOT, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=600)
    other = json.loads(out.stdout.strip().splitlines()[-1])
    assert other == json.loads(json.dumps(_checksum(SEED)))


def test_reference_layer_draw_equals_the_stacked_tree():
    from bench.harness.program import arch_config, draw_params

    for cfg in (SMOKE, SWIS):
        tree = draw_params(cfg, arch_config(cfg), SEED)
        blk = tree["blocks"]["sub0_attn"]
        ref = Reference(cfg, SEED)
        for layer in range(cfg["num_hidden_layers"]):
            w = ref.layer_weights(layer)
            leaf = blk["mlp"]["wo"]["w"]
            if cfg.get("swis"):
                got = swis_decode(jax.tree.map(lambda a: a[layer], leaf), 4)
            else:
                got = leaf[layer].astype(jnp.float32)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(w["mlp/wo/w"]))
            np.testing.assert_array_equal(
                np.asarray(blk["ln1"]["scale"][layer], np.float32),
                np.asarray(w["ln1/scale"]))


def test_swis_decode_matches_the_format():
    """Decode one drawn leaf by hand from its documented bit layout, and
    check the program reads the same weights from it."""
    from repro.serve.quantized import dequant_leaf

    k, c, n, m = 64, 8, 4, 4
    planes = W.swis_planes(jax.random.key(3), k, c, n, m)
    sign = np.asarray(planes["sign_plane"])
    masks = np.asarray(planes["mask_planes"])
    shifts = np.asarray(planes["shifts"])
    scale = np.asarray(planes["scale"])
    want = np.zeros((k, c))
    for r in range(k):
        for col in range(c):
            mag = 0.0
            for j in range(n):
                s = (shifts[r // m, col, j // 2] >> (4 * (j % 2))) & 0xF
                mag += float((masks[j, r // 32, col] >> (r % 32)) & 1) * 2.0 ** s
            neg = int((sign[r // 32, col] >> (r % 32)) & 1)
            want[r, col] = (-mag if neg else mag) * scale[0, col]
    pos = np.stack([(shifts[..., j // 2] >> (4 * (j % 2))) & 0xF
                    for j in range(n)], -1)
    assert (np.diff(pos, axis=-1) > 0).all()  # distinct, ascending
    np.testing.assert_allclose(np.asarray(swis_decode(planes, m)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dequant_leaf(planes)), want,
                               rtol=1e-6)
