"""Pallas SWIS matmul kernel: shape/dtype/group/shift sweep vs the pure-jnp
oracle and vs dense fake-quant (exact same function)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing, swis
from repro.kernels import ops, ref
from conftest import interpret_modes

SWEEP = [
    # (M, K, N, group, n_shifts, dtype)
    (8, 128, 128, 4, 2, jnp.float32),
    (16, 256, 256, 8, 3, jnp.float32),
    (32, 512, 128, 4, 4, jnp.float32),
    (8, 64, 256, 16, 5, jnp.float32),
    (8, 128, 128, 4, 3, jnp.bfloat16),
    (4, 96, 128, 4, 3, jnp.float32),  # K not multiple of default bk
]


def _make(rng, k, n, group, n_shifts):
    w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
    qw = swis.quantize(jnp.asarray(w),
                       swis.QuantConfig(n_shifts=n_shifts, group_size=group))
    return qw, packing.pack(qw)


@pytest.mark.parametrize("interpret", interpret_modes())
@pytest.mark.parametrize("m,k,n,group,n_shifts,dtype", SWEEP)
def test_pallas_matches_oracle(rng, m, k, n, group, n_shifts, dtype,
                               interpret):
    qw, pw = _make(rng, k, n, group, n_shifts)
    x = jnp.asarray(rng.normal(0, 1, (m, k)), dtype)
    want = np.asarray(ref.swis_matmul_ref(
        x, pw.sign_plane, pw.mask_planes, pw.shifts, pw.scale,
        group=group), np.float32)
    got = np.asarray(ops.swis_matmul(x, pw, use_pallas=True,
                                     interpret=interpret))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("m,k,n,group,n_shifts,dtype", SWEEP[:4])
def test_oracle_matches_fake_quant(rng, m, k, n, group, n_shifts, dtype):
    # packed matmul == x @ fake_quant(w): the paper's Eq. 7 equivalence
    w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
    qw = swis.quantize(jnp.asarray(w),
                       swis.QuantConfig(n_shifts=n_shifts, group_size=group))
    pw = packing.pack(qw)
    x = jnp.asarray(rng.normal(0, 1, (m, k)), dtype)
    got = np.asarray(ops.swis_matmul(x, pw))
    want = np.asarray(x @ qw.qweights.astype(dtype), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("interpret", interpret_modes())
@pytest.mark.parametrize("n_shifts", [2, 3, 4])
def test_swis_c_offset_packed(rng, n_shifts, interpret):
    # SWIS-C stores one offset byte per group (paper §2.2 compression edge)
    w = rng.normal(0, 0.05, (256, 128)).astype(np.float32)
    qw = swis.quantize(jnp.asarray(w),
                       swis.QuantConfig(method="swis_c", n_shifts=n_shifts,
                                        group_size=4))
    pw = packing.pack(qw)
    assert pw.shifts.shape[-1] == 1 and pw.method == "swis_c"
    x = jnp.asarray(rng.normal(0, 1, (8, 256)).astype(np.float32))
    want = np.asarray(x @ qw.qweights)
    for use_pallas in (False, True):
        got = np.asarray(ops.swis_matmul(x, pw, use_pallas=use_pallas,
                                         interpret=interpret))
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


def test_higher_rank_input(rng):
    qw, pw = _make(rng, 128, 64, 4, 3)
    x = jnp.asarray(rng.normal(0, 1, (2, 5, 128)).astype(np.float32))
    y = ops.swis_matmul(x, pw)
    assert y.shape == (2, 5, 64)


def test_custom_vjp(rng):
    qw, pw = _make(rng, 128, 128, 4, 3)
    x = jnp.asarray(rng.normal(0, 1, (4, 128)).astype(np.float32))
    g = jax.grad(lambda xx: ops.swis_matmul(xx, pw).sum())(x)
    want = np.ones((4, 128)) @ np.asarray(qw.qweights).T
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-4)


def test_tile_shape_validation(rng):
    qw, pw = _make(rng, 128, 128, 4, 3)
    x = jnp.ones((8, 128), jnp.float32)
    from repro.kernels.swis_matmul import swis_matmul_packed

    with pytest.raises(ValueError):
        swis_matmul_packed(x, pw.sign_plane, pw.mask_planes, pw.shifts,
                           pw.scale, n_shifts=3, group=4, bm=8, bn=128,
                           bk=48)  # bk not a multiple of 32


# ---------------------------------------------------------------------------
# Parametrized kernel sweep: consecutive (SWIS-C) x n_shifts x tile shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("interpret", interpret_modes())
@pytest.mark.parametrize("consecutive", [False, True])
@pytest.mark.parametrize("n_shifts", [1, 2, 3])
@pytest.mark.parametrize("bm,bn,bk", [(8, 128, 64), (16, 128, 32)])
def test_packed_kernel_param_sweep(rng, consecutive, n_shifts, bm, bn, bk,
                                   interpret):
    m, k, n, group = 16, 128, 128, 4
    method = "swis_c" if consecutive else "swis"
    w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
    qw = swis.quantize(jnp.asarray(w),
                       swis.QuantConfig(method=method, n_shifts=n_shifts,
                                        group_size=group))
    pw = packing.pack(qw)
    assert (pw.method == "swis_c") == consecutive
    x = jnp.asarray(rng.normal(0, 1, (m, k)).astype(np.float32))
    want = np.asarray(ref.swis_matmul_ref(
        x, pw.sign_plane, pw.mask_planes, pw.shifts, pw.scale,
        group=group, consecutive=consecutive))
    from repro.kernels.swis_matmul import swis_matmul_packed

    got = np.asarray(swis_matmul_packed(
        x, pw.sign_plane, pw.mask_planes, pw.shifts, pw.scale,
        n_shifts=n_shifts, group=group, bm=bm, bn=bn, bk=bk,
        interpret=interpret, consecutive=consecutive))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("kwargs,match", [
    # bk=48: not a multiple of 32 (divides k=192 so the shape check passes)
    (dict(bm=8, bn=128, bk=48), "multiple of 32"),
    # bk=64 is a multiple of 32 but not of group=48
    (dict(bm=8, bn=128, bk=64, group=48), "group"),
    # k=192 is not divisible by bk=160
    (dict(bm=8, bn=128, bk=160), "not divisible"),
    (dict(bm=5, bn=128, bk=192), "not divisible"),  # m=8 % bm=5
])
def test_tile_error_paths(rng, kwargs, match):
    from repro.kernels.swis_matmul import swis_matmul_packed

    k, n, n_shifts = 192, 128, 3
    group = kwargs.pop("group", 4)
    x = jnp.ones((8, k), jnp.float32)
    sign = jnp.zeros((k // 32, n), jnp.uint32)
    mask = jnp.zeros((n_shifts, k // 32, n), jnp.uint32)
    shifts = jnp.zeros((k // group, n, (n_shifts + 1) // 2), jnp.uint8)
    scale = jnp.ones((1, n), jnp.float32)
    with pytest.raises(ValueError, match=match):
        swis_matmul_packed(x, sign, mask, shifts, scale, n_shifts=n_shifts,
                           group=group, **kwargs)


# ---------------------------------------------------------------------------
# pick_tiles: the kernel's tile-shape heuristic
# ---------------------------------------------------------------------------


def _legal(tile, full, align):
    return tile == full or tile % align == 0


def test_pick_tiles_divisibility_invariants():
    from repro.kernels.swis_matmul import pick_tiles

    for m, k, n in [(128, 512, 256), (16, 256, 128), (8, 64, 256),
                    (4, 96, 128), (8, 576, 1536), (24, 1536, 576),
                    (8, 8192, 192)]:
        bm, bn, bk = pick_tiles(m, k, n)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0
        # the TPU block rule: (8, 128)-aligned or the whole dimension
        assert _legal(bm, m, 8) and _legal(bn, n, 128)
        assert _legal(bk, k, 4096)


def test_pick_tiles_odd_prime_dims():
    from repro.kernels.swis_matmul import pick_tiles

    # prime dims have no aligned divisor: every tile is the whole
    # dimension (still valid: whole-axis single tile)
    assert pick_tiles(7, 97, 13) == (7, 13, 97)


def test_pick_tiles_group_forces_bk_fallback():
    from repro.kernels.swis_matmul import pick_tiles, swis_matmul_packed

    # no multiple of 4096 divides K: bk falls back to the whole K
    assert pick_tiles(8, 96, 128)[2] == 96
    assert pick_tiles(8, 576, 128)[2] == 576
    # one that does keeps the tile aligned
    assert pick_tiles(8, 8192, 128)[2] == 4096
    # a group must tile a packed word, so group=3 is refused outright
    x = jnp.ones((8, 96), jnp.float32)
    with pytest.raises(ValueError, match="group"):
        swis_matmul_packed(
            x, jnp.zeros((3, 128), jnp.uint32),
            jnp.zeros((2, 3, 128), jnp.uint32),
            jnp.zeros((32, 128, 1), jnp.uint8), jnp.ones((1, 128)),
            n_shifts=2, group=3, interpret=True)
