"""Pallas TPU kernel: paged GQA attention over a block arena.

Decode (and mixed chunk+decode) attention against the serve engine's
physical-block KV arena (``repro.serve.kv_cache.SlotKVCache`` block mode)
*without* materializing the gathered K/V. The gather path
(``models/attention.py``) re-builds an O(B * n_logical_blocks * block_size
* Hkv * Dh) contiguous view of every slot's cache each step — exactly the
copy paged attention exists to avoid. Here the grid iterates (row, K/V
tile); a tile is ``pages`` logical blocks of the row, each its own block
operand whose index map reads the physical block, looked up in
``block_tables`` before the call, from SMEM (scalar prefetch, so the
index is known before the body runs), so the pipeline DMAs exactly those
physical blocks, all kv-heads at once, into VMEM while the previous tile
computes. The softmax is accumulated online
across the tile axis (flash-decoding style): running max / denominator /
weighted-V scratch persists across the innermost grid dimension and the
output block is finalized on the last tile.

Work follows each row's live extent, not the cache's capacity: only the
tiles ``[lo, hi)`` that can hold a key some real query of the row sees
are copied and computed (``live_tiles``); outside them the index maps
repeat the nearest live tile's blocks, so nothing is copied, and the body
is skipped. Rows may carry more than one query token (the fused mixed
step batches one-token decode rows together with an S-token prefill chunk
row): row ``b``'s queries sit at absolute positions ``q_pos[b] + [0, S)``
and only the first ``q_lens[b]`` of them are real — ``q_lens`` rides in
as a scalar-prefetch operand so decode rows (``q_lens == 1``) and chunk
rows (``q_lens == n_valid``) coexist in one grid. A row computes only the
query sub-tiles its real queries fill; queries past its count are masked
wholesale and their output rows finalize to zero.

Masking contract (identical to the gather path):
  * entries with ``pos < 0`` are invalid (unwritten / scrubbed / padding);
  * logical blocks mapped to the reserved trash block 0 are invalid
    wholesale, whatever garbage block 0's pos plane holds;
  * causal: ``pos <= q_pos[slot] + i`` per query ``i``; window:
    ``pos > q_pos[slot] + i - window``;
  * queries ``i >= q_lens[slot]`` are invalid (mixed-batch padding).

Two implementations behind one wrapper, identical in masking and in the
online-softmax math (the kernel's tiles are wider, so sums round in
another order):

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
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mask_value(dtype) -> float:
    """Additive-mask fill for invalid attention scores: large-magnitude
    negative but comfortably inside ``dtype``'s range, so downcasting the
    scores (fp16/bf16/fp8 caches) never overflows to ``-inf`` (whose
    ``exp`` is a well-defined 0 but whose arithmetic breeds NaNs the
    moment two masked scores are subtracted)."""
    return float(jnp.finfo(jnp.dtype(dtype)).min) / 2


# The kernel's VMEM plan. A K/V tile is ``pages`` whole arena blocks of one
# row: at most _TILE_TOKENS keys (a lane-dense score tile of two vregs) and
# at most _KV_VMEM_BUDGET for its four buffers (K and V, double-buffered).
# Queries go in sub-tiles of _Q_TILE rows (the MXU's height).
_TILE_TOKENS = 256
_KV_VMEM_BUDGET = 8 << 20
_Q_TILE = 128


def _padded_bytes(shape, dtype) -> int:
    """VMEM bytes of ``shape``: the last two dims pad to whole
    (sublanes, 128) tiles, a sublane being 32 bits wide."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = 8 * 4 // itemsize
    *lead, r, c = shape
    n = -(-r // rows) * rows * (-(-c // 128) * 128) * itemsize
    for d in lead:
        n *= d
    return n


def tile_pages(nb: int, bs: int, hkv: int, dh: int, dtype) -> int:
    """Arena blocks per K/V tile: a power of two (so that a tile of 8-token
    blocks spans whole 128-lane vregs) within both caps, and no more than
    a table row holds."""
    page = _padded_bytes((bs, hkv, dh), dtype)
    t = max(1, min(_TILE_TOKENS // bs, _KV_VMEM_BUDGET // (4 * page)))
    return min(1 << (t.bit_length() - 1), nb)


def live_tiles(q_pos, q_lens, *, tk: int, n_tiles: int, causal: bool,
               window: Optional[int]):
    """The K/V tiles ``[lo, hi)`` that rows with first query position
    ``q_pos`` and ``q_lens`` queries can see: keys of logical block j sit
    at positions [j * bs, (j + 1) * bs), so a causal row sees none past
    ``q_pos + q_lens - 1`` and a windowed one none at or before
    ``q_pos - window``. A row with no queries has no extent. Numpy arrays
    on the host, jax arrays or scalars on the device."""
    xp = np if isinstance(q_pos, np.ndarray) else jnp
    hi = (xp.minimum(-(-(q_pos + q_lens) // tk), n_tiles) if causal
          else xp.full_like(q_pos, n_tiles))
    hi = xp.where(q_lens > 0, hi, 0)
    lo = xp.zeros_like(hi)
    if window is not None:
        lo = xp.minimum(xp.maximum(q_pos - window + 1, 0) // tk, hi)
    return lo, hi


def _head_group(hkv: int, scores: int) -> int:
    """Heads per score tile: the most that divide ``hkv`` and keep their
    f32 scores within 1 MiB."""
    n = max(1, min(hkv, (1 << 20) // (4 * scores)))
    while hkv % n:
        n -= 1
    return n


def _paged_decode_kernel(
    blocks_ref,  # (B, n_tiles * pages) int32, SMEM scalar prefetch: the
    # physical blocks each grid step copies (read by the index maps only)
    qpos_ref,  # (B,) int32, SMEM scalar prefetch: row's first query pos
    qlens_ref,  # (B,) int32, SMEM scalar prefetch: valid queries per row
    q_ref,  # (1, Hkv, Sq*G, Dh) this row's queries, Sq-major per kv-head
    kpos_ref,  # (1, 1, tk) int32 positions of this step's keys
    *refs,  # `pages` K blocks, `pages` V blocks (1, bs, Hkv, Dh) each;
    # o_ref (1, Hkv, Sq*G, Dh); m, l (Hkv, Sq*G, 1) and acc (Hkv, Sq*G, Dh)
    # f32 scratch: running max, denominator and weighted V
    sq: int,
    pages: int,
    n_tiles: int,
    q_small: int,
    causal: bool,
    window: Optional[int],
):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b = pl.program_id(0)
    t = pl.program_id(1)
    neg = mask_value(jnp.float32)
    hkv, sg, dh = q_ref.shape[1:]
    g = sg // sq
    tk = kpos_ref.shape[2]
    qp = qpos_ref[b]
    ql = qlens_ref[b]
    lo, hi = live_tiles(qp, ql, tk=tk, n_tiles=n_tiles, causal=causal,
                        window=window)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def heads(refs_, hs):
        """The heads ``hs`` of this tile, head-major: (heads, tk, Dh)."""
        parts = [r[0, :, hs, :] for r in refs_]
        x = parts[0] if pages == 1 else jnp.concatenate(parts, axis=0)
        return jnp.transpose(x, (1, 0, 2))

    def attend(qt, n_sub):
        """Online softmax of this tile for query rows [0, n_sub * qt), in
        sub-tiles of qt rows, heads in groups of at most 1 MiB of scores."""
        kpos = kpos_ref[0]  # (1, tk)
        hg = _head_group(hkv, qt * tk)

        def sub_step(s, carry):
            r0 = s * qt if isinstance(s, int) else pl.multiple_of(s * qt, qt)
            rows = pl.ds(r0, qt)
            # score row r belongs to query (r0 + r) // G, at qp + that
            qi = lax.div(r0 + lax.broadcasted_iota(jnp.int32, (qt, tk), 0), g)
            valid = (kpos >= 0) & (qi < ql)
            if causal:
                valid &= kpos <= qp + qi
            if window is not None:
                valid &= kpos > qp + qi - window

            def group_step(gi, carry):
                h0 = gi * hg if isinstance(gi, int) else pl.multiple_of(
                    gi * hg, hg)
                hs = pl.ds(h0, hg)
                q = q_ref[0, hs, rows, :]  # (hg, qt, Dh)
                k = heads(k_refs, hs)  # (hg, tk, Dh)
                if k.dtype != q.dtype:
                    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
                s_ = lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * (dh ** -0.5)
                s_ = jnp.where(valid[None], s_, neg)  # (hg, qt, tk)
                m_prev = m_ref[hs, rows]  # (hg, qt, 1)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s_, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s_ - m_new)
                m_ref[hs, rows] = m_new
                l_ref[hs, rows] = l_ref[hs, rows] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                v = heads(v_refs, hs).astype(jnp.float32)
                acc_ref[hs, rows] = acc_ref[hs, rows] * alpha + (
                    lax.dot_general(
                        p, v, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32))
                return carry

            # the tile's blocks carry every kv-head; the heads share the mask
            if hg == hkv:
                group_step(0, 0)
            else:
                lax.fori_loop(0, hkv // hg, group_step, 0)
            return carry

        if isinstance(n_sub, int):
            for s in range(n_sub):
                sub_step(s, 0)
        else:
            lax.fori_loop(0, n_sub, sub_step, 0)

    live = (t >= lo) & (t < hi)
    if q_small < sg:
        # a row with few real queries (a decode row of a fused step) pays
        # for one small sub-tile; a chunk row for as many full ones as its
        # queries fill
        few = ql * g <= q_small

        @pl.when(live & few)
        def _few():
            attend(q_small, 1)

        @pl.when(live & jnp.logical_not(few))
        def _many():
            qt = _Q_TILE if sg % _Q_TILE == 0 else sg
            attend(qt, 1 if qt == sg else lax.div(ql * g + qt - 1, qt))
    else:
        pl.when(live)(lambda: attend(sg, 1))

    @pl.when(t == n_tiles - 1)
    def _finish():
        # queries past the row's count are padding: their rows finalize to
        # exact zeros, as does every row of a row with no queries
        real = lax.div(lax.broadcasted_iota(jnp.int32, (sg, dh), 0), g) < ql
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.where(real[None], out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sq", "causal", "window", "interpret"))
def _paged_attention_pallas(q4, k_arena, v_arena, pos_arena, block_tables,
                            q_pos, q_lens, *, sq, causal, window, interpret):
    """q4: (B, Hkv, Sq*G, Dh) -> (B, Hkv, Sq*G, Dh) in q4's dtype.

    The grid is (row, K/V tile). A tile is ``pages`` arena blocks, each
    its own block operand whose index map reads the physical block from
    SMEM, so the pipeline copies the next tile's blocks under this tile's
    compute. Outside the row's live extent a step repeats the nearest live
    tile's blocks (a row with none, the trash block), so the pipeline
    copies nothing, and the body is skipped. The tile's key positions come
    in lane-dense, one (1, tk) slab per step.
    """
    b, hkv, sg, dh = q4.shape
    bs = pos_arena.shape[1]
    nb = block_tables.shape[1]
    pages = tile_pages(nb, bs, hkv, dh, k_arena.dtype)
    tk = pages * bs
    n_tiles = pl.cdiv(nb, pages)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    with jax.named_scope("attention"):
        # what each (row, grid step) copies: the blocks of its nearest live
        # tile, so that the pipeline copies nothing new outside the extent
        # (a row with none, the trash block), and their keys' positions,
        # -1 on the trash block and past the table's end. Computed here and
        # not in the index maps: 64 of those tracing the extent each cost
        # every step program half a second of set-up.
        lo, hi = live_tiles(q_pos, q_lens, tk=tk, n_tiles=n_tiles,
                            causal=causal, window=window)
        tile = jnp.clip(jnp.arange(n_tiles)[None, :], lo[:, None],
                        jnp.maximum(hi - 1, lo)[:, None])  # (B, n_tiles)
        j = (tile[:, :, None] * pages + jnp.arange(pages)).reshape(b, -1)
        blocks = jnp.take_along_axis(jnp.asarray(block_tables, jnp.int32),
                                     jnp.minimum(j, nb - 1), axis=1)
        blocks = jnp.where((j < nb) & (hi > lo)[:, None], blocks, 0)
        kpos = jnp.where(blocks[:, :, None] == 0, -1, pos_arena[blocks])
        kpos = kpos.reshape(b * n_tiles, 1, tk)

    def block_map(i):
        return lambda bi, t, blk, *_: (blk[bi, t * pages + i], 0, 0, 0)

    row = lambda bi, t, *_: (bi, 0, 0, 0)  # noqa: E731
    q_small = 8 * 4 // q4.dtype.itemsize  # one sublane tile of queries
    # VMEM: double-buffered q, output, key-position and K/V blocks, then
    # m, l and acc. Phi-3's fused step holds about 18 MiB, above the
    # 16 MiB default scoped limit: the limit is what the plan holds plus
    # 8 MiB for the score tiles and their temporaries.
    vmem = (4 * _padded_bytes((hkv, sg, dh), q4.dtype)
            + 2 * _padded_bytes((1, tk), jnp.int32)
            + 4 * pages * _padded_bytes((bs, hkv, dh), k_arena.dtype)
            + 2 * _padded_bytes((hkv, sg, 1), jnp.float32)
            + _padded_bytes((hkv, sg, dh), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_tiles),
        in_specs=[pl.BlockSpec((1, hkv, sg, dh), row),
                  pl.BlockSpec((1, 1, tk),
                               lambda bi, t, *_: (bi * n_tiles + t, 0, 0))]
        + [pl.BlockSpec((1, bs, hkv, dh), block_map(i))
           for i in range(pages)] * 2,
        out_specs=pl.BlockSpec((1, hkv, sg, dh), row),
        scratch_shapes=[
            pltpu.VMEM((hkv, sg, 1), jnp.float32),
            pltpu.VMEM((hkv, sg, 1), jnp.float32),
            pltpu.VMEM((hkv, sg, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, sq=sq, pages=pages,
                               n_tiles=n_tiles, q_small=q_small,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="_paged_attention_pallas",
        out_shape=jax.ShapeDtypeStruct((b, hkv, sg, dh), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 16 << 20)),
        interpret=interpret,
    )(blocks, q_pos, q_lens, q4, kpos, *([k_arena] * pages),
      *([v_arena] * pages))


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
    to S). Padding queries' outputs are not attention: the Pallas kernel
    writes zeros there, the scan a mean over the row's masked keys, and
    callers discard them.

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
