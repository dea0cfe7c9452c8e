"""Plain float32 reference of the served model, and its control.

A pre-norm decoder (RMSNorm, RoPE over half-split pairs, causal softmax
attention, SwiGLU MLP, untied unembedding), written from the published
architecture with ``jax.numpy`` alone: no kernel, cache or batching of the
program, and nothing the program made. Weights are drawn again from the
seed, one layer at a time (``weights.layer_leaf``), so the reference fits
beside nothing else on the chip. SWIS layers are decoded from their bit
planes by :func:`swis_decode`, written here from the storage format.

``precision="f32"`` runs every matmul in float32 at HIGHEST precision.
``precision="fp8"`` is the control: every matmul operand is rounded to
float8 (e4m3, one scale per tensor) first, the step below bf16 that a
later change might take. :func:`widest_gap` compares both the served
tokens and the logits they were sampled from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_PREFIX = "blocks/sub0_attn"
GEMMS = ("attn/wq/w", "attn/wk/w", "attn/wv/w", "attn/wo/w",
         "mlp/wi/w", "mlp/wg/w", "mlp/wo/w")
NORMS = ("ln1/scale", "ln2/scale")


def _fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def swis_decode(planes, group: int):
    """Dense float32 (K, C) weights of one layer's packed leaf."""
    sign_w, mask_w = planes["sign_plane"], planes["mask_planes"]
    kw, c = sign_w.shape
    bit = jnp.arange(32, dtype=jnp.uint32)[None, :, None]

    def unpack(words):  # (K/32, C) -> (K, C): bit i of word w is row 32w+i
        return ((words[:, None, :] >> bit) & 1).reshape(kw * 32, c)

    n = mask_w.shape[0]
    packed = planes["shifts"].astype(jnp.int32)  # (K/M, C, ceil(N/2))
    mag = jnp.zeros((kw * 32, c), jnp.float32)
    for j in range(n):
        shift = (packed[..., j // 2] >> (4 * (j % 2))) & 0xF  # (K/M, C)
        shift = jnp.repeat(shift, group, axis=0)
        mag = mag + unpack(mask_w[j]).astype(jnp.float32) * jnp.exp2(
            shift.astype(jnp.float32))
    sign = 1.0 - 2.0 * unpack(sign_w).astype(jnp.float32)
    return sign * mag * planes["scale"].astype(jnp.float32)


class Reference:
    """The reference for one configuration file (``cfg``: its dict)."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.hkv = cfg["num_key_value_heads"]
        self.dh = self.d // self.h
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
        for name in GEMMS:
            path = f"{LAYER_PREFIX}/{name}"
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
        for name in NORMS:
            out[name] = W.layer_leaf(
                self.root, f"{LAYER_PREFIX}/{name}", layer,
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


@jax.jit
def _gap_rows(logits, tokens):
    """Reference's best logit minus its logit of ``tokens`` (per row)."""
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - at


@jax.jit
def _rel_err_rows(got, logits):
    """Per row, the norm of ``got - logits`` over the norm of ``logits``."""
    got = got.astype(jnp.float32)
    return (jnp.linalg.norm(got - logits, axis=-1)
            / jnp.linalg.norm(logits, axis=-1))


def widest_gap(ref: Reference, seqs, *, pad_len: int, pad_seqs: int,
               control: bool = False, logits=None,
               rows_per_block: int = 512) -> dict:
    """Compare served tokens, and the logits they were sampled from, with
    the reference.

    ``seqs``: list of ``(prompt, served)`` int arrays. Position ``p`` of
    ``prompt + served[:-1]`` predicts ``served[p - len(prompt) + 1]``. For
    each served token, the gap is the reference's best logit there minus
    its logit of the served token; the widest gap over all of them is
    returned. ``logits``: for each sequence the program's logits rows
    ``(len(served), >= vocab)`` (padded columns are ignored), or None
    where the program lost one; the logit error of a row is the norm of
    its difference from the reference's row over the norm of the
    reference's row, and the largest is returned. ``control=True``
    replaces the served tokens with the ones the fp8 reference puts first
    at the same positions, and the program's logits with the fp8
    reference's. Batches are padded to ``pad_seqs`` sequences of
    ``pad_len`` tokens and rows to whole blocks, so every run compiles the
    same few programs.
    """
    length = pad_len
    if len(seqs) > pad_seqs or max(len(p) + len(s) - 1
                                   for p, s in seqs) > length:
        raise ValueError("sample larger than the reference's padding")
    toks = np.zeros((pad_seqs, length), np.int32)
    rows, want = [], []
    for i, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s[:-1]]).astype(np.int32)
        toks[i, :len(full)] = full
        for j, t in enumerate(s):
            rows.append(i * length + len(p) - 1 + j)
            want.append(int(t))
    n_rows = len(rows)
    pad = -n_rows % rows_per_block
    rows = np.asarray(rows + [0] * pad)
    want = np.asarray(want + [0] * pad, np.int32)
    got = None
    if logits is not None and all(x is not None for x in logits):
        got = np.concatenate([np.asarray(x)[:, :ref.vocab] for x in logits])
        got = np.concatenate([got, np.zeros((pad, ref.vocab), got.dtype)])
    hidden = ref.final_hidden(toks).reshape(-1, ref.d)
    low = ref.final_hidden(toks, "fp8").reshape(-1, ref.d) if control \
        else None
    gaps, errs = [], []
    for lo in range(0, len(rows), rows_per_block):
        blk = jnp.asarray(rows[lo:lo + rows_per_block])
        exact = ref.logits(hidden[blk])
        if control:
            other = ref.logits(low[blk], "fp8")
            chosen = jnp.argmax(other, axis=-1).astype(jnp.int32)
            errs.append(np.asarray(_rel_err_rows(other, exact)))
        else:
            chosen = jnp.asarray(want[lo:lo + rows_per_block])
            if got is not None:
                errs.append(np.asarray(_rel_err_rows(
                    jnp.asarray(got[lo:lo + rows_per_block]), exact)))
        gaps.append(np.asarray(_gap_rows(exact, chosen)))
    del low
    gaps = np.concatenate(gaps)[:n_rows]
    out = {"widest_gap": float(gaps.max()), "tokens": int(len(gaps)),
           "median_gap": float(np.median(gaps))}
    if errs:
        errs = np.concatenate(errs)[:n_rows]
        out["logit_err"] = float(errs.max())
        out["median_logit_err"] = float(np.median(errs))
    elif logits is not None:
        out["logit_err"] = float("inf")
        out["median_logit_err"] = None
    return out
