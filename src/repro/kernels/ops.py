"""Jit'd public wrappers around the SWIS Pallas kernels.

``swis_matmul`` dispatches between the Pallas kernel (compiled for the TPU;
``interpret=True`` for CPU validation) and the pure-jnp reference path
(CPU + dry-run: identical math and identical *packed* HBM operands, so
cost_analysis sees the compressed weight bytes either way).

A custom VJP makes the packed matmul differentiable w.r.t. the activations
(weights are frozen post-PTQ), so packed serving graphs can still be
jacobian-tested.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.packing import PackedWeight
from repro.kernels import ref as _ref
from repro.kernels.swis_matmul import swis_matmul_packed


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul(x, planes, static):
    group, n_shifts, use_pallas, interpret, consecutive, keep_slices = static
    sign_plane, mask_planes, shifts, scale = planes
    if use_pallas:
        return swis_matmul_packed(
            x, sign_plane, mask_planes, shifts, scale,
            n_shifts=n_shifts, group=group, interpret=interpret,
            consecutive=consecutive, keep_slices=keep_slices,
        )
    return _ref.swis_matmul_ref(
        x, sign_plane, mask_planes, shifts, scale, group=group,
        consecutive=consecutive, keep_slices=keep_slices,
    )


def _matmul_fwd(x, planes, static):
    return _matmul(x, planes, static), planes


def _matmul_bwd(static, planes, g):
    group, consecutive, keep_slices = static[0], static[4], static[5]
    sign_plane, mask_planes, shifts, scale = planes
    # the gradient of a truncated matmul w.r.t. x is the truncated w^T:
    # keep_slices flows into the bwd dequant so jacobian tests stay exact
    w = _ref.dequant_ref(sign_plane, mask_planes, shifts, scale, group=group,
                         dtype=g.dtype, consecutive=consecutive,
                         keep_slices=keep_slices)
    return (g @ w.T, None)


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def swis_matmul(
    x: jnp.ndarray,
    pw: PackedWeight,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    keep_slices=None,
) -> jnp.ndarray:
    """``x @ dequant(pw)`` for arbitrary-rank ``x`` (matmul over last axis).

    ``keep_slices=k`` evaluates only the k most significant bit-planes
    (truncated-precision execution; None = all planes)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    static = (pw.group_size, pw.n_shifts, use_pallas, interpret,
              pw.method == "swis_c", keep_slices)
    planes = (pw.sign_plane, pw.mask_planes, pw.shifts, pw.scale)
    y = _matmul(x2, planes, static)
    return y.reshape(*shape[:-1], y.shape[-1])
