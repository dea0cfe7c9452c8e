"""The parts of the float32 reference that every model family shares,
and the comparison that decides ``correct``.

A family's ``Reference`` (``bench/families/<model_type>.py``) writes its
model from the published architecture with ``jax.numpy`` alone, with the
matmul :func:`_mm` in either precision and SWIS layers decoded from their
bit planes by :func:`swis_decode`, written here from the storage format.

``precision="f32"`` runs every matmul in float32 at HIGHEST precision.
``precision="fp8"`` is the control: every matmul operand is rounded to
float8 (e4m3, one scale per tensor) first, the step below bf16 that a
later change might take. :func:`widest_gap` compares both the served
tokens and the logits they were sampled from.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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


def widest_gap(ref, seqs, *, pad_len: int, pad_seqs: int,
               control: bool = False, logits=None,
               rows_per_block: int = 512) -> dict:
    """Compare served tokens, and the logits they were sampled from, with
    the reference ``ref``, a family's ``Reference``.

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
