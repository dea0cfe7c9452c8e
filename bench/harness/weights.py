"""Seeded weights, drawn on the device, keyed by a stable hash of the leaf
path (``zlib.crc32``), so any process draws the same values from a seed.

A stacked leaf of shape ``(L, ...)`` holds ``L`` layers, each drawn from
its own key ``fold_in(leaf_key, layer)``: the program's whole tree is
drawn in one jitted call (a vmap over layers), and the reference draws
one layer at a time with the same function and gets the same values.

Kinds of leaf, chosen by the last name on the path:

* ``w`` / ``wi`` / ``wg`` / ``wo``: bf16 normal, std ``1/sqrt(fan_in)``;
* ``scale`` of a norm: bf16 ``1 + 0.1 * normal``;
* ``tok`` (embedding, rows = vocab) and ``unembed`` (columns = vocab):
  bf16 normal, std 0.02, drawn at the published vocabulary and zero-padded
  to the program's padded width, so a padded id is never the argmax;
* SWIS planes of a packed GEMM (paper section 3.3): per weight a sign bit
  and ``n_shifts`` mask bits, each a fair coin; per group of
  ``group_size`` weights along K, ``n_shifts`` distinct shift positions
  in ``[0, 2**shift_bits)``, ascending; a float32 scale per column sized
  so that the weights' std is ``1/sqrt(K)``.
"""
from __future__ import annotations

import itertools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

SHIFT_RANGE = 8  # 3-bit shift positions
EMBED_STD = 0.02


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (more than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(root: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def dense_leaf(key, name: str, shape, vocab: int):
    """One layer's (or an unstacked) dense leaf. ``shape`` is the shape the
    program holds; ``vocab`` the published vocabulary."""
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if name == "tok":  # (V_padded, d)
        w = _normal(key, (vocab, shape[1]), EMBED_STD)
        return jnp.pad(w, ((0, shape[0] - vocab), (0, 0)))
    if name == "unembed":  # (d, V_padded)
        w = _normal(key, (shape[0], vocab), EMBED_STD)
        return jnp.pad(w, ((0, 0), (0, shape[1] - vocab)))
    return _normal(key, shape, 1.0 / np.sqrt(shape[-2]))


def _mean_square_magnitude(n_shifts: int) -> float:
    """E[(sum_j m_j 2**s_j)**2] over fair mask bits and a uniform choice of
    ``n_shifts`` distinct positions in ``[0, SHIFT_RANGE)``."""
    subsets = list(itertools.combinations(range(SHIFT_RANGE), n_shifts))
    total = 0.0
    for s in subsets:
        for bits in itertools.product((0, 1), repeat=n_shifts):
            total += sum(b << p for b, p in zip(bits, s)) ** 2
    return total / (len(subsets) * 2 ** n_shifts)


def _subset_table(n_shifts: int) -> np.ndarray:
    """Every choice of ``n_shifts`` distinct positions, ascending, already
    nibble-packed: (C(SHIFT_RANGE, n), ceil(n/2)) uint8."""
    rows = []
    for sub in itertools.combinations(range(SHIFT_RANGE), n_shifts):
        pos = list(sub) + [0] * (n_shifts % 2)
        rows.append([pos[i] | (pos[i + 1] << 4)
                     for i in range(0, len(pos), 2)])
    return np.asarray(rows, np.uint8)


def swis_planes(key, k: int, c: int, n_shifts: int, group: int):
    """One layer's packed SWIS leaf, in the program's storage layout:
    sign (K/32, C) u32, masks (N, K/32, C) u32, shifts (K/M, C, ceil(N/2))
    u8 nibble-packed (low nibble = even plane), scale (1, C) f32. Each
    group's shift positions are a uniform choice among the subsets of
    ``n_shifts`` distinct positions."""
    ks, km, kh, kc = jax.random.split(key, 4)
    sign = jax.random.bits(ks, (k // 32, c), jnp.uint32)
    masks = jax.random.bits(km, (n_shifts, k // 32, c), jnp.uint32)
    table = jnp.asarray(_subset_table(n_shifts))
    pick = jax.random.randint(kh, (k // group, c), 0, table.shape[0])
    # one (K/M, C) byte plane at a time, stacked last: a gather with a
    # minor axis of 1 or 2 bytes would be laid out padded on the TPU
    shifts = jnp.stack([table[:, i][pick] for i in range(table.shape[1])],
                       axis=-1)
    base = 1.0 / np.sqrt(k * _mean_square_magnitude(n_shifts))
    scale = base * jnp.exp2(jax.random.uniform(kc, (1, c), jnp.float32,
                                               -0.25, 0.25))
    return {"sign_plane": sign, "mask_planes": masks, "shifts": shifts,
            "scale": scale}


def _stacked(fn, key, n_layers: int):
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_layers, dtype=jnp.uint32))
    return jax.vmap(fn)(keys)


def layer_leaf(root, path: str, layer: int, fn):
    """The reference's draw of one layer of a stacked leaf."""
    return fn(jax.random.fold_in(leaf_key(root, path), layer))


def draw_tree(spec, seed: int, vocab: int, swis=None):
    """Draw a whole parameter tree on the device in one jitted call.

    ``spec``: nested dict whose leaves are ``(shape, stacked, kind)``:
    kind ``"dense"`` or ``"swis"`` (shape then is the dense (L, K, C) or
    (K, C) it stands for). ``swis``: ``{"n_shifts", "group_size"}``.
    """
    def walk(root, node, path):
        if isinstance(node, dict):
            return {k: walk(root, v, f"{path}/{k}" if path else k)
                    for k, v in node.items()}
        shape, stacked, kind = node
        name = path.rsplit("/", 1)[-1]
        lead = shape[1:] if stacked else shape
        if kind == "swis":
            def fn(key):
                return swis_planes(key, lead[-2], lead[-1],
                                   swis["n_shifts"], swis["group_size"])
        else:
            def fn(key):
                return dense_leaf(key, name, lead, vocab)
        key = leaf_key(root, path)
        return _stacked(fn, key, shape[0]) if stacked else fn(key)

    return jax.jit(lambda root: walk(root, spec, ""))(root_key(seed))
