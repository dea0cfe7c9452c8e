"""Pallas TPU kernel: paged GQA attention over a block arena.

Decode (and mixed chunk+decode) attention against the serve engine's
physical-block KV arena (``repro.serve.kv_cache.SlotKVCache`` block mode)
*without* materializing the gathered K/V. The gather path
(``models/attention.py``) re-builds an O(B * n_logical_blocks * block_size
* Hkv * Dh) contiguous view of every slot's cache each step — exactly the
copy paged attention exists to avoid. Here the grid iterates (slot,
logical block); each program reads ``block_tables[slot, j]`` from SMEM
(scalar prefetch, so the index is known before the body runs) and DMAs
only that physical K/V block, all kv-heads at once, into VMEM. The
softmax is accumulated online across the block axis (flash-decoding
style): running max / denominator / weighted-V scratch persists across
the innermost grid dimension and the output block is finalized on the
last logical block.

Rows may carry more than one query token (the fused mixed step batches
one-token decode rows together with an S-token prefill chunk row): row
``b``'s queries sit at absolute positions ``q_pos[b] + [0, S)`` and only
the first ``q_lens[b]`` of them are real — ``q_lens`` rides in as a
scalar-prefetch operand so decode rows (``q_lens == 1``) and chunk rows
(``q_lens == n_valid``) coexist in one grid, and queries past a row's
count are masked wholesale (their output rows finalize to zero).

Masking contract (identical to the gather path):
  * entries with ``pos < 0`` are invalid (unwritten / scrubbed / padding);
  * logical blocks mapped to the reserved trash block 0 are invalid
    wholesale, whatever garbage block 0's pos plane holds;
  * causal: ``pos <= q_pos[slot] + i`` per query ``i``; window:
    ``pos > q_pos[slot] + i - window``;
  * queries ``i >= q_lens[slot]`` are invalid (mixed-batch padding).

Two implementations behind one wrapper, both bit-identical in masking and
accumulation order:

  * ``impl="pallas"`` — the kernel above, compiled for the TPU
    (``impl="pallas_interpret"`` runs the same body in Python, for CPU
    validation only);
  * ``impl="xla"`` — a ``lax.scan`` over logical blocks gathering one
    (B, block_size) K/V slab per step. Working set is O(B * block_size),
    never O(B * n_blocks * block_size); this is the serving path on
    backends without Pallas compile support.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mask_value(dtype) -> float:
    """Additive-mask fill for invalid attention scores: large-magnitude
    negative but comfortably inside ``dtype``'s range, so downcasting the
    scores (fp16/bf16/fp8 caches) never overflows to ``-inf`` (whose
    ``exp`` is a well-defined 0 but whose arithmetic breeds NaNs the
    moment two masked scores are subtracted)."""
    return float(jnp.finfo(jnp.dtype(dtype)).min) / 2


def _paged_decode_kernel(
    tables_ref,  # (B, nb) int32, SMEM scalar prefetch
    qpos_ref,  # (B,) int32, SMEM scalar prefetch: row's first query pos
    qlens_ref,  # (B,) int32, SMEM scalar prefetch: valid queries per row
    q_ref,  # (1, Hkv, Sq*G, Dh) this slot's queries, Sq-major per kv-head
    k_ref,  # (1, bs, Hkv, Dh) the *physical* block tables[b, j] points at
    v_ref,  # (1, bs, Hkv, Dh)
    pos_ref,  # (1, 1, bs) int32 position plane of that physical block
    o_ref,  # (1, Hkv, Sq*G, Dh) output, revisited across the block axis
    m_ref,  # (Hkv, Sq*G, 1) f32 scratch: running max
    l_ref,  # (Hkv, Sq*G, 1) f32 scratch: running denominator
    acc_ref,  # (Hkv, Sq*G, Dh) f32 scratch: running weighted V
    *,
    nb: int,
    sq: int,
    causal: bool,
    window: Optional[int],
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    neg = mask_value(jnp.float32)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hkv, sg, dh = q_ref.shape[1:]
    g = sg // sq
    bs = k_ref.shape[1]
    pos = pos_ref[0]  # (1, bs)
    qp = qpos_ref[b]
    ql = qlens_ref[b]
    # per-score query index: score row i*G+g' belongs to query i, whose
    # absolute position is qp + i (TPU needs >= 2-D iota)
    qi = jax.lax.broadcasted_iota(jnp.int32, (sq, g, bs), 0).reshape(sg, bs)
    valid = jnp.broadcast_to(pos >= 0, (sg, bs))
    # logical blocks parked on the trash block are invalid by definition
    valid &= tables_ref[b, j] != 0
    # queries past the row's count are padding: mask them wholesale so
    # their output rows finalize to exact zeros
    valid &= qi < ql
    if causal:
        valid &= pos <= qp + qi
    if window is not None:
        valid &= pos > qp + qi - window

    # one DMA brings every kv-head of the block; the heads share the mask
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32) * (dh ** -0.5)  # (Sq*G, Dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)  # (bs, Dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(  # (Sq*G, bs)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, neg)
        m_prev = m_ref[h]  # (Sq*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (Sq*G, bs)
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sq", "causal", "window", "interpret"))
def _paged_attention_pallas(q4, k_arena, v_arena, pos_arena, block_tables,
                            q_pos, q_lens, *, sq, causal, window, interpret):
    """q4: (B, Hkv, Sq*G, Dh) -> (B, Hkv, Sq*G, Dh) float32.

    The grid is (slot, logical block). Every block spec covers its array's
    last two dims whole (the TPU block rule): a program takes all kv-heads
    of one physical block, and the pos plane is viewed as (n_blocks, 1, bs).
    """
    b, hkv, sg, dh = q4.shape
    n_blocks, bs = pos_arena.shape
    nb = block_tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, hkv, sg, dh),
                         lambda bi, j, t, qp, ql: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, dh),
                         lambda bi, j, t, qp, ql: (t[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, dh),
                         lambda bi, j, t, qp, ql: (t[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs),
                         lambda bi, j, t, qp, ql: (t[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, sg, dh),
                               lambda bi, j, t, qp, ql: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, sg, 1), jnp.float32),
            pltpu.VMEM((hkv, sg, 1), jnp.float32),
            pltpu.VMEM((hkv, sg, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, nb=nb, sq=sq,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="_paged_attention_pallas",
        out_shape=jax.ShapeDtypeStruct((b, hkv, sg, dh), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(q_pos, jnp.int32),
      jnp.asarray(q_lens, jnp.int32), q4, k_arena, v_arena,
      pos_arena.reshape(n_blocks, 1, bs))


def _paged_attention_xla(q4, k_arena, v_arena, pos_arena, block_tables,
                         q_pos, q_lens, *, sq, causal, window):
    """lax.scan over logical blocks: same masking and online-softmax
    accumulation as the kernel, one (B, block_size) gathered slab per step
    — the full gathered K/V is never materialized."""
    b, hkv, sg, dh = q4.shape
    g = sg // sq
    neg = mask_value(jnp.float32)
    qh = q4.astype(jnp.float32) * (dh ** -0.5)  # (B, Hkv, Sq*G, Dh)
    qi = jnp.arange(sq, dtype=jnp.int32)  # query index within the row
    # per-query absolute positions / validity, Sq-major like the q layout
    qpos = (q_pos[:, None] + qi[None, :])  # (B, Sq)
    qvalid = qi[None, :] < q_lens[:, None]  # (B, Sq)
    qpos_sg = jnp.repeat(qpos, g, axis=1)  # (B, Sq*G)
    qvalid_sg = jnp.repeat(qvalid, g, axis=1)

    def step(carry, tcol):  # tcol: (B,) physical ids of logical block j
        m, denom, acc = carry
        kj = k_arena[tcol].astype(jnp.float32)  # (B, bs, Hkv, Dh)
        vj = v_arena[tcol].astype(jnp.float32)
        pj = jnp.where((tcol == 0)[:, None], -1, pos_arena[tcol])  # (B, bs)
        s = jnp.einsum("bhgd,bkhd->bhgk", qh, kj,
                       preferred_element_type=jnp.float32)
        valid = pj[:, None, None, :] >= 0
        valid &= qvalid_sg[:, None, :, None]
        if causal:
            valid &= pj[:, None, None, :] <= qpos_sg[:, None, :, None]
        if window is not None:
            valid &= pj[:, None, None, :] > (qpos_sg[:, None, :, None]
                                             - window)
        s = jnp.where(valid, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        denom = denom * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p, vj, preferred_element_type=jnp.float32)
        return (m_new, denom, acc), None

    m0 = jnp.full((b, hkv, sg), neg, jnp.float32)
    l0 = jnp.zeros((b, hkv, sg), jnp.float32)
    a0 = jnp.zeros((b, hkv, sg, dh), jnp.float32)
    (_, denom, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.asarray(block_tables, jnp.int32).T)
    return acc / jnp.maximum(denom[..., None], 1e-30)


VALID_PAGED_IMPLS = ("pallas", "pallas_interpret", "xla")


def paged_attention_decode(
    q: jnp.ndarray,  # (B, S, H, Dh) — decode: S == 1
    k_arena: jnp.ndarray,  # (n_blocks, block_size, Hkv, Dh)
    v_arena: jnp.ndarray,
    pos_arena: jnp.ndarray,  # (n_blocks, block_size) int32, -1 invalid
    block_tables: jnp.ndarray,  # (B, nb) int32 physical ids, 0 = trash
    q_pos: jnp.ndarray,  # (B,) int32 first-query absolute positions
    *,
    q_lens: Optional[jnp.ndarray] = None,  # (B,) valid queries; None => S
    causal: bool = True,
    window: Optional[int] = None,
    impl: str = "xla",
) -> jnp.ndarray:
    """Paged GQA attention over the arena: returns (B, S, H, Dh) in
    ``q.dtype``. ``S == 1`` is plain decode; ``S > 1`` is the fused mixed
    step, where row ``b`` carries ``q_lens[b]`` real queries at absolute
    positions ``q_pos[b] + [0, q_lens[b])`` (decode rows 1, chunk rows up
    to S) and the padding queries' outputs are exact zeros.

    ``impl``: ``"pallas"`` (compiled kernel, TPU), ``"pallas_interpret"``
    (kernel body interpreted on CPU — validation only), or ``"xla"`` (the
    scan fallback, the fused serving path on non-TPU backends). All three
    share the masking contract and online-softmax math; parity against the
    gather path is pinned by ``tests/test_paged_attention.py``.
    """
    b, s, h, dh = q.shape
    hkv = k_arena.shape[2]
    g = h // hkv
    if q_lens is None:
        q_lens = jnp.full((b,), s, jnp.int32)
    # head index = hkv_idx * g + g_idx: the same (hkv, g) split the gather
    # path's full_attention uses, so outputs line up head-for-head. The
    # query axis folds in Sq-major ((q0 heads..., q1 heads...)) so the
    # kernel's score row i*G+g' maps back to query i of head group g'.
    # the layouts around the kernel are attention work; the Pallas kernel
    # is traced outside the name scope (a trace finds it by its name)
    with jax.named_scope("attention"):
        q4 = (q.reshape(b, s, hkv, g, dh).transpose(0, 2, 1, 3, 4)
              .reshape(b, hkv, s * g, dh))
    if impl in ("pallas", "pallas_interpret"):
        out = _paged_attention_pallas(
            q4, k_arena, v_arena, pos_arena, block_tables, q_pos, q_lens,
            sq=s, causal=causal, window=window,
            interpret=(impl == "pallas_interpret"))
    elif impl == "xla":
        with jax.named_scope("attention"):
            out = _paged_attention_xla(
                q4, k_arena, v_arena, pos_arena, block_tables, q_pos,
                q_lens, sq=s, causal=causal, window=window)
    else:
        raise ValueError(
            f"unknown paged attention impl {impl!r}; valid impls: "
            f"{', '.join(VALID_PAGED_IMPLS)}")
    with jax.named_scope("attention"):
        return (out.reshape(b, hkv, s, g, dh).transpose(0, 2, 1, 3, 4)
                .reshape(b, s, h, dh).astype(q.dtype))
