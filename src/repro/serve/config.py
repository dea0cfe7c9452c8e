"""Typed construction / submission surface for the serve engine.

:class:`EngineConfig` gathers what used to be 15 loose
``ContinuousBatchingEngine.__init__`` kwargs into one frozen, validated
dataclass (grouped: capacity, cache, prefill, kernel, observability), and
:class:`SamplingParams` replaces the positional ``n_tokens / temperature /
key / seed`` threading through ``submit()``. Validation that used to
surface deep inside the engine (or worse, inside a jitted step — an
unknown ``paged_impl`` used to sail through construction and explode on
the first decode) happens eagerly in ``__post_init__`` with actionable
messages. The legacy kwarg surfaces still work behind
``DeprecationWarning`` shims in the engine; see docs/serving.md for the
migration table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from repro.core.swis import QuantConfig
from repro.kernels.paged_attention import VALID_PAGED_IMPLS


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """All :class:`~repro.serve.engine.ContinuousBatchingEngine` knobs.

    Capacity:
      max_len — per-slot token capacity (prompt + generated);
      n_slots — concurrent decode slots.
    Cache:
      block_size — KV arena block granularity (block mode);
      n_cache_blocks — extra arena blocks beyond the slots' own capacity
        (None: two slots' worth, for cached-but-unreferenced prefixes);
      cache_dtype — KV storage dtype (None: the model's compute
        dtype);
      prefix_cache — block arena + radix prefix cache (uniform attention
        families only; the engine falls back to contiguous rows when the
        family's cache is not block-compatible).
    Prefill:
      prefill_chunk — max prompt tokens prefetched per step (None:
        whole-prompt prefill); rounded up to a block multiple;
      prefill_backlog — max in-flight chunk groups before admission
        pauses;
      bucket_prompts — pad prefill lengths to pow2 buckets (bounded jit
        cache);
      fused_step — fold each step's prefill chunk and decode batch into
        ONE ``mixed_step`` dispatch (requires prefill_chunk; the separate
        two-launch path remains the token-exact parity reference when
        off).
    Kernel:
      packed — serve from SWIS bit-plane packed weights;
      quant_cfg — packing config (None: the arch's default policy);
      use_paged_kernel — paged-attention decode over the arena (no
        gathered K/V);
      paged_impl — kernel backend override: one of "pallas",
        "pallas_interpret", "xla" (None: auto — "pallas" on TPU, "xla"
        elsewhere).
    Speculative decode:
      spec_decode — self-speculative multi-token decode: a draft pass
        proposes up to spec_k tokens per step, one full-precision verify
        launch scores them all, the longest matching prefix (plus the
        verify's bonus token) is accepted — token-exact vs. plain decode
        for every accept pattern (block mode only);
      spec_k — max draft tokens proposed per step (>= 1);
      draft_slices — run draft passes with SWIS weights truncated to this
        many most-significant bit-slices (requires packed=True; None:
        the draft runs at full precision, accept rate 1.0).
    Observability:
      enable_metrics — phase timers / counters / lifecycle tracer;
      trace_capacity — trace ring size (events).
    """

    max_len: int = 256
    n_slots: int = 4
    # cache
    block_size: int = 8
    n_cache_blocks: Optional[int] = None
    cache_dtype: Any = None
    prefix_cache: bool = True
    # prefill
    prefill_chunk: Optional[int] = None
    prefill_backlog: int = 2
    bucket_prompts: bool = True
    fused_step: bool = False
    # kernel
    packed: bool = False
    quant_cfg: Optional[QuantConfig] = None
    use_paged_kernel: bool = False
    paged_impl: Optional[str] = None
    # speculative decode
    spec_decode: bool = False
    spec_k: int = 3
    draft_slices: Optional[int] = None
    # observability
    enable_metrics: bool = True
    trace_capacity: int = 65536

    def __post_init__(self):
        for name, floor in (("max_len", 1), ("n_slots", 1),
                            ("block_size", 1), ("prefill_backlog", 1),
                            ("trace_capacity", 1)):
            if getattr(self, name) < floor:
                raise ValueError(f"{name} must be >= {floor}, "
                                 f"got {getattr(self, name)}")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (or None for whole-prompt "
                f"prefill), got {self.prefill_chunk}")
        if self.n_cache_blocks is not None and self.n_cache_blocks < 0:
            raise ValueError(
                f"n_cache_blocks must be >= 0, got {self.n_cache_blocks}")
        # block-mode requirements, checked here so misconfiguration fails
        # at construction, not steps deep into serving (the engine still
        # rejects block-incompatible model families at build time)
        if self.prefill_chunk is not None and not self.prefix_cache:
            raise ValueError(
                "prefill_chunk requires the block-mode prefix cache "
                "(prefix_cache=True)")
        if self.use_paged_kernel and not self.prefix_cache:
            raise ValueError(
                "use_paged_kernel requires the block-mode prefix cache "
                "(prefix_cache=True)")
        if self.fused_step and self.prefill_chunk is None:
            raise ValueError(
                "fused_step fuses the per-step prefill chunk into the "
                "decode dispatch and requires prefill_chunk to be set")
        # an unknown impl used to sail through __init__ and only fail
        # inside the first jitted decode step — reject it eagerly
        if (self.paged_impl is not None
                and self.paged_impl not in VALID_PAGED_IMPLS):
            raise ValueError(
                f"unknown paged_impl {self.paged_impl!r}; valid impls: "
                f"{', '.join(VALID_PAGED_IMPLS)} (or None for backend "
                f"auto-pick)")
        if self.paged_impl is not None and not self.use_paged_kernel:
            raise ValueError(
                "paged_impl is set but use_paged_kernel=False — enable "
                "the paged kernel or drop the impl override")
        if self.spec_decode and not self.prefix_cache:
            raise ValueError(
                "spec_decode requires the block-mode prefix cache "
                "(prefix_cache=True): draft and verify launches route "
                "per-row token counts through the block tables")
        if self.spec_decode and self.spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1 when spec_decode is on, got "
                f"{self.spec_k}")
        if self.draft_slices is not None:
            if not self.spec_decode:
                raise ValueError(
                    "draft_slices is set but spec_decode=False — enable "
                    "speculative decode or drop the truncation knob")
            if not self.packed:
                raise ValueError(
                    "draft_slices truncates the SWIS bit-plane kernel "
                    "path and requires packed=True (unpacked weights "
                    "have no slices to truncate)")
            if self.draft_slices < 1:
                raise ValueError(
                    f"draft_slices must be >= 1, got {self.draft_slices}")


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling contract for ``submit(prompt, params)``.

    max_tokens — tokens to generate (0 allowed: prefill-only request);
    temperature — 0 greedy, > 0 seeded categorical;
    seed / key — reproducible sampling stream (mutually exclusive; when
      neither is given the engine derives a distinct auto-key per
      request, so independent clients never draw identical streams).
    """

    max_tokens: int
    temperature: float = 0.0
    seed: Optional[int] = None
    key: Any = None

    def __post_init__(self):
        if self.max_tokens < 0:
            raise ValueError(
                f"max_tokens must be >= 0, got {self.max_tokens}")
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.seed is not None and self.key is not None:
            raise ValueError("seed and key are mutually exclusive — pass "
                             "one reproducibility handle, not both")
