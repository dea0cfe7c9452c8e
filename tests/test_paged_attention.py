"""Paged-attention decode parity: the fused kernel (Pallas and the XLA
scan fallback) must match the einsum-over-gather reference bit-for-token —
op level against ``full_attention`` over the materialized gather, and
engine level (``use_paged_kernel=True``) against the default gather engine
on shared-prefix and chunked-prefill workloads, greedy and seeded
temperature. Also pins the dtype-aware mask value (finite in fp16) that
replaced the old ``-1e30`` constant."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from conftest import interpret_modes
from repro.kernels.paged_attention import mask_value, paged_attention_decode
from repro.models import params as pp
from repro.models.attention import full_attention
from repro.models.model import Model
from repro.serve import (ContinuousBatchingEngine, EngineConfig,
                         SamplingParams)

MAX_LEN = 48
BS = 8  # arena block size


# ---------------------------------------------------------------------------
# mask value (satellite bugfix: -1e30 overflows to -inf in fp16)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_mask_value_finite_and_annihilating(dtype):
    m = mask_value(dtype)
    # finite in the target dtype (the old -1e30 became -inf in fp16, and
    # -inf - -inf = NaN poisons the softmax the moment a row is all-masked)
    assert np.isfinite(np.asarray(m, dtype))
    assert m < 0
    # still annihilates under softmax: exp(m - finite_max) == 0
    assert float(jnp.exp(jnp.asarray(m, jnp.float32))) == 0.0


def test_all_masked_row_is_nan_free():
    # a slot whose table is entirely trash blocks (freshly cleared slot)
    # produces an all-masked score row; the output must be finite
    q = jnp.ones((1, 1, 2, 8), jnp.float32)
    k = jnp.ones((3, BS, 2, 8), jnp.float32)
    pos = jnp.full((3, BS), -1, jnp.int32)
    tables = jnp.zeros((1, 2), jnp.int32)  # all trash
    out = paged_attention_decode(q, k, k, pos, tables, jnp.array([5]),
                                 impl="xla")
    assert np.all(np.isfinite(np.asarray(out)))


# ---------------------------------------------------------------------------
# op-level parity vs the materialized gather reference
# ---------------------------------------------------------------------------


def _make_arena(rng, *, b=3, nb=4, n_blocks=9, hkv=2, g=2, dh=16):
    """Random arena with the serve engine's invariants: block 0 is trash
    (garbage pos plane!), tables have trash-padded tails, the last live
    block of each row is partially filled."""
    h = hkv * g
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (n_blocks, BS, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (n_blocks, BS, hkv, dh)), jnp.float32)
    pos = np.full((n_blocks, BS), -1, np.int32)
    # block 0 holds garbage positions from free-slot dummy decode writes;
    # the kernel must mask table entries == 0 wholesale, not trust pos
    pos[0] = rng.integers(0, 8, (BS,))
    tables = np.zeros((b, nb), np.int32)
    q_pos = np.zeros((b,), np.int32)
    free = list(range(1, n_blocks))
    for r in range(b):
        n_live = int(rng.integers(1, nb + 1))
        n_tok = (n_live - 1) * BS + int(rng.integers(1, BS + 1))
        for j in range(n_live):
            blk = free.pop()
            tables[r, j] = blk
            filled = min(BS, n_tok - j * BS)
            pos[blk, :filled] = np.arange(j * BS, j * BS + filled)
        q_pos[r] = n_tok - 1
    return q, k, v, jnp.asarray(pos), jnp.asarray(tables), \
        jnp.asarray(q_pos)


def _gather_reference(q, k, v, pos, tables, q_pos, *, causal, window):
    """The reference path from models/attention.py, verbatim semantics."""
    b, nb = tables.shape
    gk = k[tables].reshape((b, nb * BS) + k.shape[2:])
    gv = v[tables].reshape((b, nb * BS) + v.shape[2:])
    gp = jnp.where((tables == 0)[:, :, None], -1,
                   pos[tables]).reshape(b, nb * BS)
    return full_attention(q, gk, gv, q_pos=q_pos[:, None], kv_pos=gp,
                          causal=causal, window=window)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 12),
                                           (False, None)])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_op_parity_vs_gather(rng, impl, causal, window):
    q, k, v, pos, tables, q_pos = _make_arena(rng)
    want = np.asarray(_gather_reference(q, k, v, pos, tables, q_pos,
                                        causal=causal, window=window))
    got = np.asarray(paged_attention_decode(
        q, k, v, pos, tables, q_pos, causal=causal, window=window,
        impl=impl))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("interpret", interpret_modes())
def test_pallas_modes_match_xla(rng, interpret):
    """Kernel parity in both interpret modes (compiled runs on TPU/GPU
    runners, interpret everywhere): the Pallas kernel and the scan
    fallback share one accumulation contract."""
    q, k, v, pos, tables, q_pos = _make_arena(rng, b=2, nb=3, n_blocks=7)
    want = np.asarray(paged_attention_decode(
        q, k, v, pos, tables, q_pos, impl="xla"))
    impl = "pallas_interpret" if interpret else "pallas"
    got = np.asarray(paged_attention_decode(
        q, k, v, pos, tables, q_pos, impl=impl))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fp16_cache_stays_finite(rng):
    q, k, v, pos, tables, q_pos = _make_arena(rng, b=2, nb=3, n_blocks=7)
    out = paged_attention_decode(
        q.astype(jnp.float16), k.astype(jnp.float16), v.astype(jnp.float16),
        pos, tables, q_pos, impl="xla")
    assert out.dtype == jnp.float16
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_unknown_impl_rejected(rng):
    q, k, v, pos, tables, q_pos = _make_arena(rng, b=1, nb=2, n_blocks=5)
    with pytest.raises(ValueError, match="impl"):
        paged_attention_decode(q, k, v, pos, tables, q_pos, impl="cuda")


# ---------------------------------------------------------------------------
# the kernel's iteration space: live extent, many pages per tile, query
# sub-tiles — each case against the materialized gather reference
# ---------------------------------------------------------------------------

# (bs, nb, hkv, g, dh, sq, causal, window). With 64-token blocks a K/V tile
# is 4 blocks and a row's table 2 tiles; with 8-token blocks the table is
# one tile of 6 blocks.
EXTENT_CASES = {
    "g1-dh96": (64, 6, 2, 1, 96, 4, True, None),
    "g3-dh64": (64, 6, 2, 3, 64, 4, True, None),
    "g3-dh96-decode": (64, 6, 2, 3, 96, 1, True, None),
    "g1-dh64-one-tile": (8, 6, 2, 1, 64, 4, True, None),
    "window": (64, 6, 2, 1, 64, 4, True, 40),
    "noncausal": (64, 6, 2, 3, 64, 4, False, None),
}


def _mixed_arena(rng, bs, nb, hkv, g, dh, sq):
    """Five rows of a fused step: an idle row (no queries, all-trash
    table), decode rows (one query) and chunk rows (sq - 1 and sq
    queries), whose extents end mid-block and, at 64-token blocks, across
    a tile boundary. Every row's K/V is written up to its last query, then
    two stale entries past it (rejected drafts); block 0 holds garbage
    positions; the tables' tails are trash."""
    cap = nb * bs
    rows = [(0, 0), (int(0.34 * cap), 1), (int(0.52 * cap), sq - 1),
            (int(0.66 * cap), sq), (int(0.78 * cap), 1)]
    n_blocks = 1 + len(rows) * nb
    pos = np.full((n_blocks, bs), -1, np.int32)
    pos[0] = rng.integers(0, cap, (bs,))
    tables = np.zeros((len(rows), nb), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    for r, (qp, ql) in enumerate(rows):
        if ql == 0:
            continue
        n_tok = min(qp + ql + 2, cap)
        for j in range(-(-n_tok // bs)):
            blk = tables[r, j] = free.pop()
            filled = min(bs, n_tok - j * bs)
            pos[blk, :filled] = np.arange(j * bs, j * bs + filled)
    q = rng.normal(0, 1, (len(rows), sq, hkv * g, dh))
    k = rng.normal(0, 1, (n_blocks, bs, hkv, dh))
    v = rng.normal(0, 1, (n_blocks, bs, hkv, dh))
    q_pos, q_lens = np.array(rows, np.int32).T
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
            jnp.asarray(v, jnp.float32), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(q_pos), jnp.asarray(q_lens))


@pytest.mark.parametrize("case", list(EXTENT_CASES))
@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_live_extent_parity_vs_gather(rng, impl, case):
    bs, nb, hkv, g, dh, sq, causal, window = EXTENT_CASES[case]
    q, k, v, pos, tables, q_pos, q_lens = _mixed_arena(
        rng, bs, nb, hkv, g, dh, sq)
    b = tables.shape[0]
    gk = k[tables].reshape((b, nb * bs, hkv, dh))
    gv = v[tables].reshape((b, nb * bs, hkv, dh))
    gp = jnp.where((tables == 0)[:, :, None], -1,
                   pos[tables]).reshape(b, nb * bs)
    want = np.asarray(full_attention(
        q, gk, gv, q_pos=q_pos[:, None] + jnp.arange(sq)[None, :],
        kv_pos=gp, causal=causal, window=window))
    got = np.asarray(paged_attention_decode(
        q, k, v, pos, tables, q_pos, q_lens=q_lens, causal=causal,
        window=window, impl=impl))
    real = np.arange(sq)[None, :] < np.asarray(q_lens)[:, None]
    np.testing.assert_allclose(got[real], want[real], rtol=1e-5, atol=1e-5)
    if impl == "pallas_interpret":
        # the kernel finalizes padding queries and idle rows to zeros
        assert not np.any(got[~real])


# ---------------------------------------------------------------------------
# engine-level parity: fused decode vs the gather engine, token-exact
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _setup():
    cfg = C.get_smoke("smollm-135m").replace(compute_dtype="float32")
    params = pp.init_params(Model(cfg).build(), jax.random.key(0))
    return cfg, params


def _shared_prefix_prompts(rng, n, sys_len=2 * BS + 1):
    cfg, _ = _setup()
    sys_p = rng.integers(0, cfg.vocab, (sys_len,)).astype(np.int32)
    return [np.concatenate([sys_p,
                            rng.integers(0, cfg.vocab,
                                         (3 + i % 5,)).astype(np.int32)])
            for i in range(n)]


def _run(prompts, n_tok, temperature, *, paged, **kw):
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params,
                                   config=EngineConfig(max_len=MAX_LEN,
                                                       n_slots=3,
            block_size=BS, use_paged_kernel=paged is not None,
            paged_impl=paged, **kw))
    rids = [eng.submit(p, SamplingParams(max_tokens=n_tok,
                                         temperature=temperature, seed=i))
            for i, p in enumerate(prompts)]
    out = eng.drain()
    return [out[r] for r in rids]


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_engine_shared_prefix_token_exact(rng, temperature):
    prompts = _shared_prefix_prompts(rng, 6)
    want = _run(prompts, 8, temperature, paged=None)
    got = _run(prompts, 8, temperature, paged="xla")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_engine_chunked_prefill_token_exact(rng, temperature):
    prompts = _shared_prefix_prompts(rng, 5, sys_len=3 * BS + 2)
    want = _run(prompts, 6, temperature, paged=None, prefill_chunk=BS)
    got = _run(prompts, 6, temperature, paged="xla", prefill_chunk=BS)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_engine_pallas_interpret_token_exact(rng):
    # one small run through the actual kernel body (interpreted): the
    # engine wiring for impl="pallas" differs from "xla" only in dispatch
    prompts = _shared_prefix_prompts(rng, 2)[:2]
    want = _run(prompts, 3, 0.0, paged=None)
    got = _run(prompts, 3, 0.0, paged="pallas_interpret")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_paged_requires_block_mode(rng):
    cfg, params = _setup()
    with pytest.raises(ValueError, match="block-mode"):
        ContinuousBatchingEngine(cfg, params,
                                 config=EngineConfig(max_len=MAX_LEN,
                                                     n_slots=2,
                prefix_cache=False, use_paged_kernel=True))
