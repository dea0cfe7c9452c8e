"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed without a chip attached: a described
``v5e:2x2`` topology lets ``jit(...).lower(shapes).compile()`` raise what
the chip's compiler would (block-shape rules, VMEM limits, Mosaic
lowering), at smollm-135m widths and, for the paged kernel, phi3-mini's. Nothing runs, so these say nothing about
results or speed. The topology is described inside a fixture (never at
import) and the tests skip where it cannot be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.swis_matmul import swis_matmul_packed

# smollm-135m geometry: 9 query / 3 kv heads of 64, the engine's default
# 8-token blocks, 8 decode slots of 320 tokens
B, H, HKV, DH, BS, NB = 8, 9, 3, 64, 8, 40
SMOLLM_GEMMS = [(576, 576), (576, 192), (576, 1536), (1536, 576)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_op(name, hlo_text):
    """The custom-call instruction named ``name`` (``%name.N = ...``)."""
    return re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", hlo_text)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("sq", [1, 16], ids=["decode", "mixed"])
def test_paged_attention_compiles_for_v5e(one_chip, sq):
    n_blocks = B * NB + 1
    compiled = _compile(
        lambda q, k, v, pos, t, qp, ql: paged_attention_decode(
            q, k, v, pos, t, qp, q_lens=ql, impl="pallas"),
        one_chip,
        ((B, sq, H, DH), jnp.bfloat16),
        ((n_blocks, BS, HKV, DH), jnp.bfloat16),
        ((n_blocks, BS, HKV, DH), jnp.bfloat16),
        ((n_blocks, BS), jnp.int32), ((B, NB), jnp.int32),
        ((B,), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's pinned name is what a device trace finds it by
    assert _kernel_op("_paged_attention_pallas", compiled.as_text())


# phi3-mini's fused chunk step (4 slots and one chunk row of 128 queries,
# 32 heads of 96, 2048-token tables) and its S=1 decode step
PHI3_PAGED = {"chunk": (5, 128), "decode": (4, 1)}


@pytest.mark.parametrize("step", sorted(PHI3_PAGED))
def test_paged_attention_compiles_at_phi3_width(one_chip, step):
    b, sq = PHI3_PAGED[step]
    h, dh, bs, nb, n_blocks = 32, 96, 8, 256, 1025
    compiled = _compile(
        lambda q, k, v, pos, t, qp, ql: paged_attention_decode(
            q, k, v, pos, t, qp, q_lens=ql, impl="pallas"),
        one_chip,
        ((b, sq, h, dh), jnp.bfloat16),
        ((n_blocks, bs, h, dh), jnp.bfloat16),
        ((n_blocks, bs, h, dh), jnp.bfloat16),
        ((n_blocks, bs), jnp.int32), ((b, nb), jnp.int32),
        ((b,), jnp.int32), ((b,), jnp.int32))
    assert _kernel_op("_paged_attention_pallas", compiled.as_text())


@pytest.mark.parametrize("k,n", SMOLLM_GEMMS,
                         ids=[f"{k}x{n}" for k, n in SMOLLM_GEMMS])
def test_swis_matmul_compiles_for_v5e(one_chip, k, n):
    n_shifts, group = 4, 4
    compiled = _compile(
        lambda x, s, m, sh, sc: swis_matmul_packed(
            x, s, m, sh, sc, n_shifts=n_shifts, group=group),
        one_chip,
        ((B, k), jnp.bfloat16), ((k // 32, n), jnp.uint32),
        ((n_shifts, k // 32, n), jnp.uint32),
        ((k // group, n, (n_shifts + 1) // 2), jnp.uint8),
        ((1, n), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    assert _kernel_op("swis_matmul_packed", compiled.as_text())
