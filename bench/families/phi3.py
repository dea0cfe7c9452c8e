"""The family of ``"model_type": "phi3"``: a dense pre-norm decoder
(RMSNorm, RoPE over half-split pairs, causal softmax attention with
grouped K/V heads, SwiGLU MLP, untied unembedding). It is also the dense
base that other families import and adjust.

A family module (``bench/families/<model_type>.py``, found by
``spec.family``) gives the harness three things for a configuration file
(``cfg``: its dict):

* ``arch_config(cfg)``: the program's config of the model;
* ``Reference(cfg, seed)``: the plain float32 reference, with ``d``,
  ``vocab``, ``final_hidden(tokens, precision)`` and
  ``logits(hidden, precision)``, which ``reference.widest_gap`` compares;
* ``shapes(cfg)``: the required work, with ``launch(rows)`` and
  ``attention_kernel(rows)``, which the roofline readers count.

Attention heads are ``head_dim`` wide where the file gives it, else
``hidden_size // num_attention_heads``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W
from bench.harness.reference import _mm, swis_decode


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    # the program's types, imported here so that the reference and the
    # counts load without the program
    from bench.harness.program import ArchConfig
    from repro.configs.base import QuantPolicy
    from repro.core.swis import QuantConfig

    arch = ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype="bfloat16", compute_dtype="bfloat16")
    if cfg.get("swis"):
        sw = cfg["swis"]
        arch = arch.replace(quant=QuantPolicy(cfg=QuantConfig(
            method=sw["method"], n_shifts=sw["n_shifts"],
            group_size=sw["group_size"]), mode="off"))
    return arch


class Reference:
    """The reference for one configuration file, written from the
    published architecture with ``jax.numpy`` alone: no kernel, cache or
    batching of the program, and nothing the program made. Weights are
    drawn again from the seed, one layer at a time
    (``weights.layer_leaf``), so the reference fits beside nothing else on
    the chip; SWIS layers are decoded from their bit planes
    (``reference.swis_decode``)."""

    # the program's path of the stacked layer leaves, and the leaves
    LAYER_PREFIX = "blocks/sub0_attn"
    GEMMS = ("attn/wq/w", "attn/wk/w", "attn/wv/w", "attn/wo/w",
             "mlp/wi/w", "mlp/wg/w", "mlp/wo/w")
    NORMS = ("ln1/scale", "ln2/scale")

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.hkv = cfg["num_key_value_heads"]
        self.dh = head_dim(cfg)
        self.f = cfg["intermediate_size"]
        self.vocab = cfg["vocab_size"]
        self.n_layers = cfg["num_hidden_layers"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.swis = cfg.get("swis")
        self.root = W.root_key(seed)

    # -- weights --------------------------------------------------------

    def _gemm_shape(self, name):
        d, f, q, kv = self.d, self.f, self.h * self.dh, self.hkv * self.dh
        return {"attn/wq/w": (d, q), "attn/wk/w": (d, kv),
                "attn/wv/w": (d, kv), "attn/wo/w": (q, d),
                "mlp/wi/w": (d, f), "mlp/wg/w": (d, f),
                "mlp/wo/w": (f, d)}[name]

    @functools.partial(jax.jit, static_argnums=0)
    def layer_weights(self, layer):
        out = {}
        for name in self.GEMMS:
            path = f"{self.LAYER_PREFIX}/{name}"
            k, c = self._gemm_shape(name)
            if self.swis:
                sw = self.swis
                planes = W.layer_leaf(self.root, path, layer, lambda key: (
                    W.swis_planes(key, k, c, sw["n_shifts"],
                                  sw["group_size"])))
                out[name] = swis_decode(planes, sw["group_size"])
            else:
                out[name] = W.layer_leaf(
                    self.root, path, layer,
                    lambda key: W.dense_leaf(key, "w", (k, c), self.vocab)
                ).astype(jnp.float32)
        for name in self.NORMS:
            out[name] = W.layer_leaf(
                self.root, f"{self.LAYER_PREFIX}/{name}", layer,
                lambda key: W.dense_leaf(key, "scale", (self.d,),
                                         self.vocab)).astype(jnp.float32)
        return out

    def _global(self, path, shape):
        name = path.rsplit("/", 1)[-1]
        return W.dense_leaf(W.leaf_key(self.root, path), name, shape,
                            self.vocab).astype(jnp.float32)

    # -- forward --------------------------------------------------------

    def _rms(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale

    def _rope(self, x, pos):  # x (S, H, Dh), pos (S,)
        half = self.dh // 2
        freqs = 1.0 / (self.theta ** (jnp.arange(half, dtype=jnp.float32)
                                      / half))
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _layer(self, x, w, precision):
        """One decoder layer over a batch of sequences (B, S, d)."""
        s = x.shape[1]
        pos = jnp.arange(s)
        causal = pos[None, :] <= pos[:, None]
        g = self.h // self.hkv

        def one(xr):  # (S, d)
            h = self._rms(xr, w["ln1/scale"])
            q = _mm("sd,de->se", h, w["attn/wq/w"], precision)
            k = _mm("sd,de->se", h, w["attn/wk/w"], precision)
            v = _mm("sd,de->se", h, w["attn/wv/w"], precision)
            q = self._rope(q.reshape(s, self.h, self.dh), pos)
            k = self._rope(k.reshape(s, self.hkv, self.dh), pos)
            v = v.reshape(s, self.hkv, self.dh)
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
            sc = _mm("qhd,khd->hqk", q, k, precision) / np.sqrt(self.dh)
            sc = jnp.where(causal[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = _mm("hqk,khd->qhd", p, v, precision).reshape(s, -1)
            xr = xr + _mm("sd,de->se", o, w["attn/wo/w"], precision)
            h = self._rms(xr, w["ln2/scale"])
            a = jax.nn.silu(_mm("sd,df->sf", h, w["mlp/wi/w"], precision))
            b = _mm("sd,df->sf", h, w["mlp/wg/w"], precision)
            return xr + _mm("sf,fd->sd", a * b, w["mlp/wo/w"], precision)

        return jax.lax.map(one, x)

    def final_hidden(self, tokens: np.ndarray, precision: str = "f32"):
        """tokens (B, S) int -> normalized final hidden states (B, S, d)
        float32. Padding after a sequence's end does not reach its
        positions (causal)."""
        emb = self._global("embed/tok", (self.vocab, self.d))
        x = jnp.take(emb, jnp.asarray(tokens), axis=0)
        del emb
        for layer in range(self.n_layers):
            x = self._layer(x, self.layer_weights(layer), precision)
        return self._rms(x, self._global("final_norm/scale", (self.d,)))

    def logits(self, hidden, precision: str = "f32"):
        """(..., d) -> (..., vocab) float32, in the given precision."""
        un = self._global("embed/unembed", (self.d, self.vocab))
        return _mm("...d,dv->...v", hidden, un, precision)


@dataclass(frozen=True)
class Shapes:
    """Operations and bytes that the model's work requires, from the
    configuration's shapes alone. They count the same work whatever
    implements it, and never less than the format requires:

    * weights are read once per launch, bf16 at 2 bytes, SWIS layer
      weights at the paper's section 3.3 size, ``(M * (1 + N) + 3 * N) /
      M`` bits per weight (8 for N = 4 shifts in groups of M = 4; the
      program stores 9);
    * attention reads each row's K/V at the row's actual length, not at
      the cache's capacity, and writes the K/V of each new token;
    * logits are required once per row that asks for them.
    """
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    weight_bits: float  # per layer-GEMM weight, as the format requires
    kv_itemsize: int = 2
    embed_itemsize: int = 2

    @classmethod
    def from_config(cls, cfg: dict) -> "Shapes":
        swis = cfg.get("swis")
        if swis:
            m, n = swis["group_size"], swis["n_shifts"]
            bits = (m * (1 + n) + 3 * n) / m
        else:
            bits = 16.0
        return cls(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=head_dim(cfg), layers=cfg["num_hidden_layers"],
                   vocab=cfg["vocab_size"], weight_bits=bits)

    # -- per token ------------------------------------------------------

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per = self.d * q * 2 + self.d * kv * 2 + 3 * self.d * self.f
        return self.layers * per

    @property
    def unembed_params(self) -> int:
        return self.d * self.vocab

    @property
    def matmul_flops_per_token(self) -> int:
        """2 x every matmul parameter, the unembedding included."""
        return 2 * (self.layer_matmul_params + self.unembed_params)

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.kv_itemsize)

    @property
    def weight_bytes(self) -> float:
        """Weights one launch has to read: layer GEMMs at the format's
        size, the bf16 unembedding and the norm scales."""
        norms = (2 * self.layers + 1) * self.d * 2
        return (self.layer_matmul_params * self.weight_bits / 8
                + self.unembed_params * self.embed_itemsize + norms)

    # -- attention at actual lengths ------------------------------------

    def attention_flops(self, rows: Iterable[Tuple[int, int]]) -> int:
        """QK^T and PV over every layer; ``rows``: (start, n_new): the row's
        queries sit at positions start .. start + n_new - 1 and each sees
        itself and everything before it."""
        keys = sum(n * start + n * (n + 1) // 2 for start, n in rows)
        return 4 * self.layers * self.heads * self.head_dim * keys

    def kv_read_bytes(self, rows: Iterable[Tuple[int, int]]) -> int:
        return sum((start + n) for start, n in rows) * self.kv_bytes_per_token

    def kv_write_bytes(self, rows: Iterable[Tuple[int, int]]) -> int:
        return sum(n for _, n in rows) * self.kv_bytes_per_token

    # -- one launch ------------------------------------------------------

    def launch(self, rows) -> dict:
        """Required work of one step program over live ``rows``; each row
        asks for one row of logits."""
        rows = [(int(s), int(n)) for s, n in rows if n > 0]
        tokens = sum(n for _, n in rows)
        flops = (2 * self.layer_matmul_params * tokens
                 + 2 * self.unembed_params * len(rows)
                 + self.attention_flops(rows))
        act = tokens * self.d * self.embed_itemsize  # embedding rows read
        nbytes = (self.weight_bytes + self.kv_read_bytes(rows)
                  + self.kv_write_bytes(rows) + act)
        return {"flops": flops, "bytes": nbytes, "tokens": tokens,
                "rows": len(rows)}

    def attention_kernel(self, rows) -> dict:
        """Required work of the paged attention kernel over live ``rows``:
        its FLOPs, K/V read at actual length, queries read (bf16) and the
        float32 output written."""
        rows = [(int(s), int(n)) for s, n in rows if n > 0]
        tokens = sum(n for _, n in rows)
        io = tokens * self.layers * self.heads * self.head_dim * (2 + 4)
        return {"flops": self.attention_flops(rows),
                "bytes": self.kv_read_bytes(rows) + io}


def shapes(cfg: dict) -> Shapes:
    return Shapes.from_config(cfg)
