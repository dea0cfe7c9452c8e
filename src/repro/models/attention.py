"""Attention: GQA/MHA/MQA, local (sliding-window), cross-attention, KV cache.

Training/prefill uses a KV-chunked online-softmax (flash-style) scan so the
S x S score matrix is never materialized — memory O(S * chunk). Decode uses a
single einsum over the (sharded) cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention import mask_value, paged_attention_decode
from repro.models.layers import build_linear, dense, rope
from repro.models.params import P


def build_attention(cfg: ArchConfig, kind: str = "self") -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_in = cfg.vlm.vision_dim if (kind == "cross" and cfg.vlm) else d
    return {
        "wq": build_linear(d, h * dh, ("embed", "q_proj")),
        "wk": build_linear(kv_in, hkv * dh, ("embed", "kv_proj")),
        "wv": build_linear(kv_in, hkv * dh, ("embed", "kv_proj")),
        "wo": build_linear(h * dh, d, ("q_proj", "embed")),
    }


def build_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    """K/V planes. The position plane is added by ``build_block_cache`` —
    shared (cache_len,) for the static engine, per-slot (batch, cache_len)
    for continuous batching."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": P((batch, max_len, hkv, dh), ("batch", "kv_seq", "kv_heads", "head_dim"),
               init="zeros", dtype=dtype),
        "v": P((batch, max_len, hkv, dh), ("batch", "kv_seq", "kv_heads", "head_dim"),
               init="zeros", dtype=dtype),
    }


def _split_heads(x, n_heads, d_head):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, d_head)


def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dh)
    k: jnp.ndarray,  # (B, Skv, Hkv, Dh)
    v: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (Sq,) int32, or (B, Sq) per-slot
    kv_pos: jnp.ndarray,  # (Skv,) int32, or (B, Skv) per-slot; neg => padding
    causal: bool,
    window: Optional[int],
    chunk: int,
) -> jnp.ndarray:
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    chunk = min(chunk, skv)
    # 2-D positions carry a per-batch (slot) row, e.g. a cached-prefix
    # suffix prefill attending over a per-slot cache.
    kp = kv_pos if kv_pos.ndim == 2 else kv_pos[None, :]  # (1 | B, Skv)
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]  # (1 | B, Sq)
    if skv % chunk:
        pad = (-skv) % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, pad)), constant_values=-1)
        skv += pad
    n_chunks = skv // chunk

    qh = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * (dh ** -0.5)
    kc = k.reshape(b, n_chunks, chunk, hkv, dh)
    vc = v.reshape(b, n_chunks, chunk, hkv, dh)
    pc = kp.reshape(kp.shape[0], n_chunks, chunk)

    def step(carry, xs):
        m, denom, acc = carry
        k_c, v_c, p_c = xs  # p_c: (1 | B, chunk)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            qh,
            k_c.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        valid = p_c[:, None, :] >= 0
        if causal:
            valid = valid & (p_c[:, None, :] <= qp[:, :, None])
        if window is not None:
            valid = valid & (p_c[:, None, :] > qp[:, :, None] - window)
        s = jnp.where(valid[:, None, None], s, mask_value(s.dtype))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        denom_new = denom * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd",
            p,
            v_c.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, denom_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), mask_value(jnp.float32), jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dh), jnp.float32)
    (m, denom, acc), _ = jax.lax.scan(
        step,
        (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
         pc.transpose(1, 0, 2)),
    )
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh).astype(q.dtype)


def full_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dh) — decode: Sq == 1
    k: jnp.ndarray,  # (B, Skv, Hkv, Dh)
    v: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,  # (Sq,) shared, or (B, Sq) per-slot
    kv_pos: jnp.ndarray,  # (Skv,) shared, or (B, Skv) per-slot
    causal: bool,
    window: Optional[int],
) -> jnp.ndarray:
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qh = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * (dh ** -0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    # 2-D positions carry a per-batch (slot) row: each sequence in the batch
    # masks against its own cache occupancy (continuous batching decode).
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]  # (1 | B, Sq)
    kp = kv_pos if kv_pos.ndim == 2 else kv_pos[None, :]  # (1 | B, Skv)
    valid = kp[:, None, :] >= 0
    if causal:
        valid &= kp[:, None, :] <= qp[:, :, None]
    if window is not None:
        valid &= kp[:, None, :] > qp[:, :, None] - window
    s = jnp.where(valid[:, None, None], s, mask_value(s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh).astype(q.dtype)


def attention_apply(
    p: dict,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ArchConfig,
    *,
    positions: jnp.ndarray,  # (S,) int32 absolute positions of x
    causal: bool = True,
    window: Optional[int] = None,
    use_rope: bool = True,
    ctx: Optional[jnp.ndarray] = None,  # cross-attn context (B, P, Dv)
    cache: Optional[dict] = None,
    cache_index: Optional[jnp.ndarray] = None,  # scalar int32 write offset
    block_tables: Optional[jnp.ndarray] = None,  # (B, n_blocks) physical ids
    attend_cache: bool = False,  # prefill: attend over the (prefix) cache
    paged: Optional[str] = None,  # fused paged decode kernel impl
    q_lens: Optional[jnp.ndarray] = None,  # (B,) valid tokens per row (mixed)
):
    """Returns (out (B,S,D), new_cache_or_None).

    ``block_tables`` switches the decode path to block-table indirection:
    the cache leaves are a physical-block arena ((n_blocks, block_size,
    ...)) and row r's K/V is gathered through ``block_tables[r]`` — two
    rows pointing at the same physical block share that KV (prefix
    caching). ``paged`` selects the fused paged-attention decode instead of
    materializing that gather (``"pallas"`` / ``"pallas_interpret"`` /
    ``"xla"``, see :mod:`repro.kernels.paged_attention`); ``None`` keeps
    the einsum-over-gather reference path. ``attend_cache`` makes a
    multi-token prefill attend over the *updated cache* instead of just its
    own K/V, which is what lets a prefill chunk see everything committed
    before it — a cached prompt prefix, previously prefilled chunks, or
    both; the kv_pos >= 0 masking contract is unchanged in all modes.

    ``q_lens`` (with ``block_tables``) selects the fused mixed-step path:
    row ``r`` carries ``q_lens[r]`` real tokens starting at its own
    ``cache_index[r]`` (decode rows 1, chunk rows up to S, idle rows 0),
    every row's valid K/V is scatter-committed into the arena through its
    block table inside this same launch, and attention reads the arena
    through the tables — one dispatch covers the decode batch and a
    prefill chunk with zero host-side commit work afterwards.

    Name scopes in the step programs' metadata: the projections are
    ``gemm`` (``dense``), the arena writes ``kv_commit``, and the rest
    ``attention``, but for the Pallas paged kernel's own call, which a
    device trace finds by the kernel's pinned name: its body is traced
    under no model scope, which keeps set-up fast on the chip.
    """
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, s, _ = x.shape

    q = _split_heads(dense(p["wq"], x, cfg), h, dh)
    kv_src = ctx if ctx is not None else x
    k = _split_heads(dense(p["wk"], kv_src, cfg), hkv, dh)
    v = _split_heads(dense(p["wv"], kv_src, cfg), hkv, dh)

    if use_rope and ctx is None:
        with jax.named_scope("attention"):
            pos_b = positions if positions.ndim == 2 else positions[None, :]
            q = rope(q, pos_b, cfg.rope_theta)
            k = rope(k, pos_b, cfg.rope_theta)

    new_cache = None
    if ctx is not None:
        with jax.named_scope("attention"):
            kv_pos = jnp.arange(ctx.shape[1], dtype=jnp.int32)
            out = chunked_attention(
                q, k, v, q_pos=positions, kv_pos=kv_pos, causal=False,
                window=None, chunk=cfg.attn_chunk,
            )
    elif cache is not None:
        # Ring-buffer cache {'k','v','pos'} of length cache_len (== window
        # for local attention). The position plane is either shared across
        # the batch (1-D, static engine) or per-slot (2-D (B, cache_len),
        # continuous batching), and ``cache_index`` is either a scalar
        # (lockstep batch) or a (B,) vector (per-slot decode positions).
        # Statically-distinguished write modes: full-sequence prefill, tail
        # prefill (S >= cache_len), lockstep single-token decode, and
        # per-slot single-token decode (each row wraps at its own slot).
        idx = cache_index
        cache_len = cache["k"].shape[1]
        per_slot = cache["pos"].ndim == 2
        kd = k.astype(cache["k"].dtype)
        vd = v.astype(cache["v"].dtype)
        new_pos = positions.astype(jnp.int32)
        if q_lens is not None and block_tables is not None:
            # fused mixed step: decode rows (1 valid token) and a prefill
            # chunk's rows (up to S valid tokens) share one launch. Every
            # row's valid tokens are written straight into the arena
            # through its block table — invalid tokens (mixed-batch
            # padding, idle decode rows) are routed to the trash block 0,
            # so the commit needs no host-side scatter afterwards — and
            # attention reads each row's K/V through its table, paged or
            # gathered. Valid writes cannot collide: a request's write
            # region lies in blocks it exclusively owns, and each request
            # contributes valid tokens from exactly one row.
            #
            # Speculative decode rides this same path for BOTH of its
            # launches (never the plain block-table decode branch below,
            # whose bi is unclipped): draft steps are S=1 rows with
            # q_lens in {0, 1} (rows past their per-row draft budget mask
            # to the trash block), and the verify launch feeds S = k+1
            # tokens per speculating row. Rejected drafts need no explicit
            # rollback: their stale arena entries sit at positions strictly
            # beyond every later query position until the next feed window
            # overwrites them (write-before-attend in this same block), so
            # causal masking (kv pos <= q pos) keeps them unread.
            assert jnp.ndim(idx) == 1 and per_slot, (jnp.ndim(idx), per_slot)
            nb = block_tables.shape[1]
            pos2 = positions  # (B, S): row r writes at idx[r] + [0, S)
            with jax.named_scope("kv_commit"):
                tok_valid = (jnp.arange(s, dtype=jnp.int32)[None, :]
                             < q_lens[:, None])  # (B, S)
                bi = jnp.clip(pos2 // cache_len, 0, nb - 1)
                phys = jnp.where(
                    tok_valid, jnp.take_along_axis(block_tables, bi, axis=1),
                    0)  # (B, S); invalid tokens -> trash block
                off = jnp.mod(pos2, cache_len)
                fp, fo = phys.reshape(-1), off.reshape(-1)
                ck = cache["k"].at[fp, fo].set(
                    kd.reshape((b * s,) + kd.shape[2:]))
                cv = cache["v"].at[fp, fo].set(
                    vd.reshape((b * s,) + vd.shape[2:]))
                cp = cache["pos"].at[fp, fo].set(
                    jnp.where(tok_valid, pos2, -1).reshape(-1))
            if paged is not None:
                out = paged_attention_decode(
                    q, ck, cv, cp, block_tables, pos2[:, 0],
                    q_lens=q_lens, causal=causal, window=window, impl=paged)
            else:
                with jax.named_scope("attention"):
                    gk = ck[block_tables].reshape(
                        (b, nb * cache_len) + ck.shape[2:])
                    gv = cv[block_tables].reshape(
                        (b, nb * cache_len) + cv.shape[2:])
                    gp = jnp.where((block_tables == 0)[:, :, None], -1,
                                   cp[block_tables]).reshape(
                                       b, nb * cache_len)
                    out = chunked_attention(
                        q, gk, gv, q_pos=pos2, kv_pos=gp, causal=causal,
                        window=window, chunk=cfg.attn_chunk)
            y = dense(p["wo"], out.reshape(b, s, h * dh), cfg)
            return y, {"k": ck, "v": cv, "pos": cp}
        if jnp.ndim(idx) == 1 and block_tables is not None:
            # block-table decode: the cache is a physical-block arena; row
            # r's token lands in block idx[r] // bs at offset idx[r] % bs
            # of whatever physical block its table maps it to. Attention
            # then gathers the row's K/V *through the table*, so physical
            # blocks shared between rows (cached prefixes) are read in
            # place — zero copies, zero recompute.
            assert s == 1 and per_slot, (s, per_slot)
            nb = block_tables.shape[1]
            with jax.named_scope("kv_commit"):
                bi = idx // cache_len  # logical block of each row's write
                off = jnp.mod(idx, cache_len)
                phys = jnp.take_along_axis(block_tables, bi[:, None],
                                           axis=1)[:, 0]  # (B,)
                ck = cache["k"].at[phys, off].set(kd[:, 0])
                cv = cache["v"].at[phys, off].set(vd[:, 0])
                cp = cache["pos"].at[phys, off].set(new_pos[:, 0])
            if paged is not None:
                # fused path: the kernel indexes the arena through the
                # table in place — the gathered K/V below never exists
                out = paged_attention_decode(
                    q, ck, cv, cp, block_tables, positions[:, 0],
                    causal=causal, window=window, impl=paged)
            else:
                with jax.named_scope("attention"):
                    gk = ck[block_tables].reshape(
                        (b, nb * cache_len) + ck.shape[2:])
                    gv = cv[block_tables].reshape(
                        (b, nb * cache_len) + cv.shape[2:])
                    # logical blocks mapped to the trash block (id 0:
                    # unallocated table tails, free slots) are invalid by
                    # definition — their positions must never enter the
                    # mask, whatever garbage the free-slot dummy writes left
                    # in block 0's pos plane
                    gp = jnp.where((block_tables == 0)[:, :, None], -1,
                                   cp[block_tables]).reshape(
                                       b, nb * cache_len)
                    out = full_attention(q, gk, gv, q_pos=positions,
                                         kv_pos=gp, causal=causal,
                                         window=window)
            y = dense(p["wo"], out.reshape(b, s, h * dh), cfg)
            return y, {"k": ck, "v": cv, "pos": cp}
        with jax.named_scope("kv_commit"):
            if jnp.ndim(idx) == 1:
                # per-slot decode: row r writes its token at position idx[r]
                assert s == 1 and per_slot, (s, per_slot)
                slot = jnp.mod(idx, cache_len)  # (B,)
                ck = jax.vmap(lambda c, u, sl: jax.lax.dynamic_update_slice(
                    c, u, (sl, 0, 0)))(cache["k"], kd, slot)
                cv = jax.vmap(lambda c, u, sl: jax.lax.dynamic_update_slice(
                    c, u, (sl, 0, 0)))(cache["v"], vd, slot)
                cp = jax.vmap(
                    lambda c, u, sl: jax.lax.dynamic_update_slice(c, u, (sl,))
                )(cache["pos"], new_pos, slot)
            elif s >= cache_len:
                # Keep the ring invariant slot == pos % cache_len so later
                # single-token writes overwrite the *oldest* entry.
                shift = jnp.mod(new_pos[-cache_len], cache_len)
                ck = jnp.roll(kd[:, -cache_len:], shift, axis=1)
                cv = jnp.roll(vd[:, -cache_len:], shift, axis=1)
                cp = jnp.roll(new_pos[-cache_len:], shift)
                if per_slot:
                    cp = jnp.broadcast_to(cp[None, :], (b, cache_len))
            elif s == 1:
                slot = jnp.mod(idx, cache_len)
                ck = jax.lax.dynamic_update_slice(cache["k"], kd,
                                                  (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], vd,
                                                  (0, slot, 0, 0))
                if per_slot:
                    cp = jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(new_pos[None, :], (b, 1)), (0, slot))
                else:
                    cp = jax.lax.dynamic_update_slice(cache["pos"], new_pos,
                                                      (slot,))
            else:  # chunked prefill within capacity (no wrap by design)
                ck = jax.lax.dynamic_update_slice(cache["k"], kd,
                                                  (0, idx, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], vd,
                                                  (0, idx, 0, 0))
                if per_slot:
                    cp = jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(new_pos[None, :], (b, s)), (0, idx))
                else:
                    cp = jax.lax.dynamic_update_slice(cache["pos"], new_pos,
                                                      (idx,))
        new_cache = {"k": ck, "v": cv, "pos": cp}
        with jax.named_scope("attention"):
            if s == 1:
                # decode: attend over the (ring) cache
                out = full_attention(q, ck, cv, q_pos=positions, kv_pos=cp,
                                     causal=causal, window=window)
            elif attend_cache and s < cache_len:
                # chunk / suffix prefill past a committed position: the
                # cache rows [0, cache_index) hold valid K/V (cached prefix
                # and/or earlier chunks) and this chunk was just written at
                # [cache_index, cache_index + s), so the chunk's queries
                # attend over the whole updated cache (invalid entries are
                # pos == -1 and masked as always).
                out = chunked_attention(
                    q, ck, cv, q_pos=positions, kv_pos=cp, causal=causal,
                    window=window, chunk=cfg.attn_chunk)
            else:
                # whole-prompt prefill: the ring cache only retains the
                # last `cache_len` KVs, so early queries must attend over
                # the full current K/V (cache is write-only here; decode
                # reads it).
                out = chunked_attention(
                    q, k, v, q_pos=positions, kv_pos=positions,
                    causal=causal, window=window, chunk=cfg.attn_chunk)
    else:
        with jax.named_scope("attention"):
            out = chunked_attention(
                q, k, v, q_pos=positions, kv_pos=positions, causal=causal,
                window=window, chunk=cfg.attn_chunk,
            )

    y = dense(p["wo"], out.reshape(b, s, h * dh), cfg)
    return y, new_cache
