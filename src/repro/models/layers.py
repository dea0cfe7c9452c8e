"""Basic model layers (pure JAX, placeholder-tree params).

Every GEMM goes through :func:`dense`, which applies the SWIS quantization
policy (QAT fake-quant / PTQ / off) — the paper's technique is a first-class
feature of every architecture.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.qat import maybe_quant
from repro.models.params import P


# ---------------------------------------------------------------------------
# Builders (placeholder trees)
# ---------------------------------------------------------------------------


def build_norm(d: int) -> dict:
    return {"scale": P((d,), ("embed",), init="ones")}


def build_linear(d_in: int, d_out: int, axes=("embed", "mlp"), scale=None) -> dict:
    return {"w": P((d_in, d_out), axes, scale=scale)}


def build_mlp(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "wo": build_linear(f, d, ("mlp", "embed")),
        "wi": build_linear(d, f, ("embed", "mlp")),
    }
    if cfg.glu:
        p["wg"] = build_linear(d, f, ("embed", "mlp"))
    return p


def build_embed(cfg: ArchConfig) -> dict:
    v, d = cfg.padded_vocab, cfg.d_model
    p = {"tok": P((v, d), ("vocab", "embed"), init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = P((d, v), ("embed", "vocab"), scale=0.02)
    return p


# ---------------------------------------------------------------------------
# Appliers
# ---------------------------------------------------------------------------


def norm_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig, eps: float = 1e-6):
    with jax.named_scope("norm"):
        dt = x.dtype
        x32 = x.astype(jnp.float32)
        if cfg.norm == "rms":
            var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            y = x32 * jax.lax.rsqrt(var + eps)
        else:  # LayerNorm without bias
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(dt)


def dense(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Linear layer with the SWIS quantization policy applied to the weight.

    Packed-serving path: when the leaf is a packed SWIS dict (see
    repro.serve.quantized), the matmul consumes the compressed bit-planes —
    the Pallas kernel dequantizes in VMEM on TPU; the jnp reference path
    does the same math on CPU/dry-run with identical packed HBM operands.
    Every op of it, bf16 or packed, carries the ``gemm`` name scope in the
    step programs' metadata (docs/serving.md "Observability").
    """
    with jax.named_scope("gemm"):
        return _dense(p, x, cfg)


def _dense(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    w = p["w"]
    if isinstance(w, dict) and "mask_planes" in w:
        from repro.kernels import ops
        from repro.core.packing import PackedWeight

        k = w["sign_plane"].shape[0] * 32
        method = ("swis_c" if cfg.quant.cfg.method == "swis_c" else "swis")
        pw = PackedWeight(
            sign_plane=w["sign_plane"], mask_planes=w["mask_planes"],
            shifts=w["shifts"], scale=w["scale"],
            group_size=k // w["shifts"].shape[0],
            n_shifts=int(w["mask_planes"].shape[0]), k=k,
            c=w["sign_plane"].shape[1], method=method)
        return ops.swis_matmul(
            x, pw, use_pallas=False,
            keep_slices=cfg.quant.keep_slices).astype(x.dtype)
    if cfg.quant.act_shifts:
        from repro.core.swis import act_truncate

        x = act_truncate(x, cfg.quant.act_shifts)
    w = maybe_quant(w, cfg.quant.cfg, cfg.quant.mode)
    return x @ w.astype(x.dtype)


def _act(h: jnp.ndarray, kind: str) -> jnp.ndarray:
    return jax.nn.silu(h) if kind == "silu" else jax.nn.gelu(h)


def mlp_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    h = _act(dense(p["wi"], x, cfg), cfg.act)
    if cfg.glu:
        h = h * dense(p["wg"], x, cfg)
    return dense(p["wo"], h, cfg)


def embed_apply(p: dict, tokens: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    with jax.named_scope("embed"):
        e = p["tok"]
        if cfg.quant.quantize_embeddings:
            e = maybe_quant(e, cfg.quant.cfg, cfg.quant.mode)
        dt = jnp.dtype(cfg.compute_dtype)
        return jnp.take(e, tokens, axis=0).astype(dt)


def unembed_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    with jax.named_scope("gemm"):
        if cfg.tie_embeddings:
            w = p["tok"].T
        else:
            w = p["unembed"]
        # logits in fp32 for a stable softmax-CE
        return (x @ w.astype(x.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: (..., S, n_heads, d_head); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
