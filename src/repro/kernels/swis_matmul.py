"""Pallas TPU kernel: SWIS dequant-in-kernel matmul.

TPU-native realization of the paper's bit-serial PE (DESIGN.md §2): the
compressed SWIS representation (sign bit-plane + N mask bit-planes packed in
uint32 lanes + per-group 3-bit shifts) streams HBM->VMEM, the kernel
reconstructs integer weights *in VMEM* (the analogue of the shift-accumulate
loop, Eq. 7) and feeds the MXU with them:

    w[k, n]   = sign[k, n] * sum_j  mask_j[k, n] << shifts[k // M, n, j]
    out[i, n] = scale[n] * sum_k x[i, k] * w[k, n]

The HBM weight traffic is the *packed* bytes — (M(1+N)+3N)/(8M) of the int8
baseline — which is where SWIS's win lands on TPU (memory roofline term).

Bit-major evaluation. Bit ``b`` of packed word ``w`` holds weight row
``k = 32 w + b``. The kernel loops over the 32 bit positions: for bit ``b``
it extracts one (words, bn) slab from every plane with a single shift and
mask, rebuilds that slab's integer weights, and multiplies it with the
activation columns ``x[:, 32 w + b]``. Every value in the loop is a 2-D
(sublane = packed word, lane = output column) tile, so the body needs no
in-kernel reshape or relayout and compiles in about a second at any width.
The wrapper lays out the two small operands to match: activations as
``x3[tile, b] = x[:, 32 w + b]`` and the per-group shifts as
``s2[byte, b // M, w, n] = shifts[(32 w + b) // M, n, byte]`` (a transpose,
same bytes; ``M`` must divide 32).

Tiling: grid (M_rows/bm, N_cols/bn, K/bk); the fp32 accumulator lives in the
output VMEM block across the K loop (output-stationary, like the paper's OS
systolic dataflow). TPU block rule: the last two dims of every block are
multiples of (8, 128) or the full array dims, so bm is a multiple of 8 or
all rows, bn a multiple of 128 or all columns, and bk a multiple of 4096
(128 packed words on the lanes of the activation block) or all of K.
:func:`pick_tiles` chooses legal tiles; interpret mode takes any tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BITS = 32  # weights per packed uint32 word
# bk // 32 packed words sit on the lane axis of the activation block
_BK_ALIGN = 128 * _BITS


def _swis_matmul_kernel(
    x_ref,  # (1, 32, bm, bk // 32) activations, bit-major
    sign_ref,  # (bk // 32, bn) uint32
    mask_ref,  # (n_shifts, bk // 32, bn) uint32
    shift_ref,  # (n_bytes, 32 // group, bk // 32, bn) uint8
    scale_ref,  # (1, bn) float32
    o_ref,  # (bm, bn) float32 accumulator
    *,
    n_shifts: int,
    group: int,
    k_steps: int,
    consecutive: bool,
    keep_slices=None,
):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # keep_slices truncates execution to the top-k most significant planes
    # (shift combos are ascending, so plane n_shifts-1 carries the largest
    # shift): the bit-serial PE simply stops k cycles early, which is the
    # truncated-precision draft execution speculative decode runs on.
    first = 0 if keep_slices is None else n_shifts - keep_slices

    def one_bit(b, acc):
        ub = b.astype(jnp.uint32)
        c = b // group  # which of the word's 32/M groups bit b belongs to
        negative = ((sign_ref[...] >> ub) & 1) == 1
        w_mag = jnp.zeros(sign_ref.shape, jnp.int32)
        # Shift-accumulate (Eq. 7): one mask plane per shift index, the
        # plane loop unrolled (n_shifts is static).
        for j in range(first, n_shifts):
            bit = ((mask_ref[j] >> ub) & 1).astype(jnp.int32)
            if consecutive:  # SWIS-C: shift j = per-group offset + j
                s = shift_ref[0, c].astype(jnp.int32) + j
            else:
                byte = shift_ref[j // 2, c].astype(jnp.int32)
                s = (byte >> (4 * (j % 2))) & 0xF
            w_mag = w_mag + (bit << s)
        w = jnp.where(negative, -w_mag, w_mag).astype(x_ref.dtype)
        return acc + jax.lax.dot_general(
            x_ref[0, b], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    o_ref[...] += jax.lax.fori_loop(
        0, _BITS, one_bit, jnp.zeros(o_ref.shape, jnp.float32))

    @pl.when(k_idx == k_steps - 1)
    def _finish():
        o_ref[...] *= scale_ref[...]


def pick_tiles(m: int, k: int, n: int):
    """Largest legal TPU tiles ``(bm, bn, bk)`` for an ``(m, k) x (k, n)``
    packed matmul: the biggest candidate that divides the dimension, else
    the whole dimension (always legal)."""
    def largest(dim, cands):
        for c in cands:
            if dim % c == 0:
                return c
        return dim

    bm = largest(m, (128, 64, 32, 16, 8))
    bn = largest(n, (512, 256, 128))
    bk = largest(k, (_BK_ALIGN,))
    return bm, bn, bk


@functools.partial(
    jax.jit,
    static_argnames=("n_shifts", "group", "bm", "bn", "bk", "interpret",
                     "consecutive", "keep_slices"),
)
def swis_matmul_packed(
    x: jnp.ndarray,
    sign_plane: jnp.ndarray,
    mask_planes: jnp.ndarray,
    shifts: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    n_shifts: int,
    group: int,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: bool = False,
    consecutive: bool = False,
    keep_slices=None,
):
    """``x (M, K) @ dequant(packed (K, N)) -> (M, N) float32``.

    See module docstring for the packed layout and the tile rule; tiles
    left as None come from :func:`pick_tiles`. ``interpret=True`` executes
    the kernel body in Python (CPU validation only).
    ``keep_slices=k`` evaluates only the k most significant bit-planes —
    the truncated-precision execution that a bit-serial PE gets by ending
    its shift-accumulate loop early (speculative-draft path).
    """
    if keep_slices is not None and not 1 <= keep_slices <= n_shifts:
        raise ValueError(
            f"keep_slices must be in [1, {n_shifts}], got {keep_slices}")
    m, k = x.shape
    kw, n = sign_plane.shape
    if kw * _BITS != k:
        raise ValueError(f"x has K={k} but the planes pack {kw * _BITS} rows")
    auto = pick_tiles(m, k, n)
    bm, bn, bk = (auto[i] if t is None else t
                  for i, t in enumerate((bm, bn, bk)))
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k},{n}) not divisible by tiles "
                         f"({bm},{bn},{bk})")
    if bk % _BITS:
        raise ValueError(f"bk={bk} must be a multiple of 32 (packed words)")
    if _BITS % group:
        raise ValueError(f"group={group} must divide 32 (the groups of one "
                         f"packed word)")
    # the TPU block rule; the interpreter has none
    for name, tile, full, align in (("bm", bm, m, 8), ("bn", bn, n, 128),
                                    ("bk", bk, k, _BK_ALIGN)):
        if not interpret and tile != full and tile % align:
            raise ValueError(f"{name}={tile} is not a legal TPU tile: it "
                             f"must be a multiple of {align} or {full}")
    k_steps = k // bk
    words = bk // _BITS
    grid = (m // bm, n // bn, k_steps)

    # bit-major activations: x3[t, b, i, w] = x[i, t * bk + 32 w + b]
    x3 = x.reshape(m, k_steps, words, _BITS).transpose(1, 3, 0, 2)
    # per-bit shift slabs: s2[byte, c, w, n] = shifts[w * (32 // M) + c, n]
    n_bytes = shifts.shape[-1]
    s2 = shifts.reshape(kw, _BITS // group, n, n_bytes).transpose(3, 1, 0, 2)
    scale2d = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(1, -1), (1, n))

    kernel = functools.partial(
        _swis_matmul_kernel,
        n_shifts=n_shifts,
        group=group,
        k_steps=k_steps,
        consecutive=consecutive,
        keep_slices=keep_slices,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        name="swis_matmul_packed",
        in_specs=[
            pl.BlockSpec((1, _BITS, bm, words),
                         lambda i, j, kk: (kk, 0, i, 0)),
            pl.BlockSpec((words, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((n_shifts, words, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((n_bytes, _BITS // group, words, bn),
                         lambda i, j, kk: (0, 0, kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x3, sign_plane, mask_planes, s2, scale2d)
