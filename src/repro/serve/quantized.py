"""SWIS-packed parameters for serving.

``pack_tree`` walks a parameter tree and replaces every eligible GEMM weight
(2-D ``{'w': (K, C)}`` leaves and 3-D per-expert stacks) with its packed SWIS
representation {sign_plane, mask_planes, shifts, scale}. The model's
``dense`` path detects packed leaves and dequantizes in-kernel (Pallas on
TPU, jnp reference on CPU/dry-run) — HBM weight traffic is the *packed*
bytes, which is where the paper's compression lands on TPU.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.swis import QuantConfig, quantize

PACKED_KEYS = ("sign_plane", "mask_planes", "shifts", "scale")


def is_packed(leaf) -> bool:
    return isinstance(leaf, dict) and "mask_planes" in leaf


def _eligible(path_keys, arr) -> bool:
    # any rank >= 2: trailing (K, C) is the GEMM matrix, leading dims are
    # stacked layers and/or experts
    if len(arr.shape) < 2:
        return False
    k = arr.shape[-2]
    if k % 32 or k < 64:
        return False
    name = str(path_keys[-1])
    if name not in ("w", "wi", "wo", "wg", "shared_wi", "shared_wo",
                    "shared_wg"):
        return False
    joined = "/".join(str(p) for p in path_keys)
    if "embed" in joined or "router" in joined or "frontend" in joined:
        return False
    return True


@functools.partial(jax.jit, static_argnums=1)  # one compile per (K, C)
def _pack_matrix(w: jnp.ndarray, qcfg: QuantConfig) -> Dict[str, jnp.ndarray]:
    qw = quantize(jnp.asarray(w, jnp.float32), qcfg)
    pw = packing.pack(qw)
    return {
        "sign_plane": pw.sign_plane,
        "mask_planes": pw.mask_planes,
        "shifts": pw.shifts,
        "scale": jnp.asarray(pw.scale, jnp.float32).reshape(1, -1)
        if jnp.ndim(pw.scale) else jnp.full((1, w.shape[-1]), pw.scale),
    }


def pack_tree(params, qcfg: QuantConfig):
    """Returns (packed_tree, stats). Non-eligible leaves pass through."""
    n_packed = 0
    dense_bits = 0
    packed_bits = 0

    def walk(path, node):
        nonlocal n_packed, dense_bits, packed_bits
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        arr = node
        if not _eligible(path, arr):
            return arr
        if arr.ndim > 2:
            lead = arr.shape[:-2]
            flat = arr.reshape(-1, *arr.shape[-2:])
            packed = [_pack_matrix(flat[i], qcfg)
                      for i in range(flat.shape[0])]
            out = {k: jnp.stack([p[k] for p in packed]).reshape(
                lead + packed[0][k].shape) for k in PACKED_KEYS}
        else:
            out = _pack_matrix(arr, qcfg)
        n_packed += 1
        k, c = arr.shape[-2], arr.shape[-1]
        e = int(np.prod(arr.shape[:-2])) if arr.ndim > 2 else 1
        dense_bits += e * k * c * 8
        n = int(out["mask_planes"].shape[-3])
        groups = k // qcfg.group_size * c
        shift_bits = 3 if qcfg.method == "swis_c" else 3 * n
        packed_bits += e * (k * c * (1 + n) + groups * shift_bits)
        return out

    tree = walk((), params)
    stats = {
        "n_packed": n_packed,
        "dense_bits": dense_bits,
        "packed_bits": packed_bits,
        "compression": dense_bits / max(packed_bits, 1),
    }
    return tree, stats


def pack_placeholders(tree, qcfg: QuantConfig):
    """Placeholder-tree version of :func:`pack_tree` (dry-run: shapes +
    logical axes only, no data). Eligible P leaves become dicts of P leaves
    with the packed shapes; sharding rules apply to them like any other."""
    from repro.models.params import P, is_placeholder

    n_eff = int(np.ceil(qcfg.n_shifts))
    m = qcfg.group_size

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        p = node
        if not is_placeholder(p) or not _eligible(path, p):
            return p
        lead = p.shape[:-2]
        lead_axes = p.axes[:-2]
        k, c = p.shape[-2], p.shape[-1]
        ak, ac = p.axes[-2], p.axes[-1]
        if k % m:
            return p
        return {
            "sign_plane": P(lead + (k // 32, c), lead_axes + (ak, ac),
                            init="zeros", dtype=jnp.uint32),
            "mask_planes": P(lead + (n_eff, k // 32, c),
                             lead_axes + (None, ak, ac),
                             init="zeros", dtype=jnp.uint32),
            # nibble-packed shift values (SWIS-C: one offset byte/group)
            "shifts": P(lead + (k // m, c,
                                1 if qcfg.method == "swis_c"
                                else (n_eff + 1) // 2),
                        lead_axes + (ak, ac, None),
                        init="zeros", dtype=jnp.uint8),
            "scale": P(lead + (1, c), lead_axes + (None, ac),
                       init="ones", dtype=jnp.float32),
        }

    return walk((), tree)


def total_slices(tree) -> int:
    """Number of SWIS bit-slices (mask planes) in a packed tree, from the
    first packed leaf (``pack_tree`` packs every leaf with one config, so
    the count is uniform). 0 when the tree holds no packed leaves — the
    engine uses this to validate ``draft_slices`` for speculative decode.
    """
    found = 0

    def walk(node):
        nonlocal found
        if found:
            return
        if is_packed(node):
            found = int(node["mask_planes"].shape[-3])
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v)

    walk(tree)
    return found


def packed_stats(tree) -> Dict[str, int]:
    n = 0

    def count(node):
        nonlocal n
        if is_packed(node):
            n += 1
            return
        if isinstance(node, dict):
            for v in node.values():
                count(v)

    count(tree)
    return {"n_packed_leaves": n}


def dequant_leaf(leaf: Dict[str, jnp.ndarray], dtype=jnp.float32,
                 consecutive: bool = False) -> jnp.ndarray:
    """Dense weights from a packed leaf (2-D or stacked 3-D)."""
    from repro.kernels.ref import dequant_ref

    mask = leaf["mask_planes"]
    if mask.ndim == 4:  # (E, N, K/32, C)
        k = leaf["sign_plane"].shape[-2] * 32
        group = k // leaf["shifts"].shape[-3]
        return jax.vmap(
            lambda s, m, sh, sc: dequant_ref(s, m, sh, sc, group=group,
                                             dtype=dtype,
                                             consecutive=consecutive)
        )(leaf["sign_plane"], mask, leaf["shifts"], leaf["scale"])
    k = leaf["sign_plane"].shape[0] * 32
    group = k // leaf["shifts"].shape[0]
    return dequant_ref(leaf["sign_plane"], mask, leaf["shifts"],
                       leaf["scale"], group=group, dtype=dtype,
                       consecutive=consecutive)
