"""Serve engines over SWIS-packed weights.

Two engines share the same model, packing path, and seeded sampler:

* :class:`ContinuousBatchingEngine` — the serving hot path. A
  :class:`~repro.serve.scheduler.RequestScheduler` admits requests from a
  queue into free slots of a :class:`~repro.serve.kv_cache.SlotKVCache`;
  admitted requests prefill into their slot (grouped by prompt length)
  while the other slots keep decoding, one batched per-slot decode step at
  a time (``submit`` / ``step`` / ``drain``). With ``packed=True`` the
  whole hot path runs on SWIS bit-plane weights (``pack_tree``) — HBM
  weight traffic per decode step is the compressed bytes, the paper's
  serving-side win.

* :class:`DecodeEngine` — the legacy static-batch engine (one lockstep
  batch, fresh cache per call). Kept as the parity oracle:
  ``ContinuousBatchingEngine.generate`` reproduces its greedy tokens
  exactly, and its seeded-temperature tokens exactly too because both
  engines sample through :func:`sample_step` with identical per-row keys.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import warnings
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.swis import QuantConfig
from repro.kernels.paged_attention import live_tiles, tile_pages
from repro.models import params as pp
from repro.models.model import Model
from repro.serve import trace as tr
from repro.serve.config import EngineConfig, SamplingParams
from repro.serve.costmodel import CostModel
from repro.serve.kv_cache import SlotKVCache
from repro.serve.metrics import MetricsRegistry, cost_buckets
from repro.serve.prefix_cache import BlockPool, RadixPrefixCache
from repro.serve.quantized import pack_tree, total_slices
from repro.serve.scheduler import Finished, RequestScheduler
from repro.serve.trace import RequestTracer

# shared bucket edges for per-dispatch cost histograms (the registry
# only consults edges when a histogram is first created)
_COST_EDGES = cost_buckets()
_COST_FIELDS = ("flops", "hbm_bytes", "swis_cycles")


class _Phase:
    """One engine phase: a ``jax.profiler`` span ``serve.<name>`` around
    the phase's timer. The span lands in the profiler's host plane, on the
    device trace's clock, only while a profiler trace records; otherwise
    entering it costs about a microsecond. ``with`` yields the span, so a
    dispatch phase can attach its launch's counts
    (``if span.is_enabled(): span.set_metadata(...)``)."""

    __slots__ = ("span", "_timer")

    def __init__(self, name: str, timer):
        self.span = jax.profiler.TraceAnnotation(name)
        self._timer = timer

    def __enter__(self):
        self.span.__enter__()
        self._timer.__enter__()
        return self.span

    def __exit__(self, *exc):
        self._timer.__exit__(*exc)
        self.span.__exit__(*exc)
        return False


@jax.jit
def sample_step(logits, keys, steps, temps):
    """Seeded per-row sampling shared by both engines.

    Row r draws from ``categorical(fold_in(keys[r], steps[r]),
    logits[r] / temps[r])`` (argmax when temps[r] <= 0). Because the key is
    per-row, a request's tokens depend only on its own (key, step, logits)
    — not on batch size, slot position, or who else is in flight.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(key, step, row, t):
        k = jax.random.fold_in(key, step)
        return jax.random.categorical(
            k, row / jnp.maximum(t, 1e-6)).astype(jnp.int32)

    sampled = jax.vmap(one)(keys, steps, logits, temps)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _row_keys(rng, b: int):
    """Per-row sampling keys for a lockstep batch: row r gets
    fold_in(rng, r) — the same derivation ``generate()`` compat uses."""
    return jax.vmap(lambda r: jax.random.fold_in(rng, r))(
        jnp.arange(b, dtype=jnp.uint32))


def _maybe_pack(cfg: ArchConfig, params, packed: bool,
                quant_cfg: Optional[QuantConfig]):
    """Common packing path: returns (cfg, params, pack_stats)."""
    if not packed:
        return cfg, params, None
    qcfg = quant_cfg or cfg.quant.cfg
    params, stats = pack_tree(params, qcfg)
    # record the pack method so dense()/moe dispatch the right
    # (consecutive vs sparse) unpack semantics
    from repro.configs.base import QuantPolicy

    return cfg.replace(quant=QuantPolicy(cfg=qcfg, mode="off")), params, stats


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class ContinuousBatchingEngine:
    """Step-driven serve engine: requests join mid-flight.

    API: construct with ``ContinuousBatchingEngine(cfg, params,
    config=EngineConfig(...))``; ``submit(prompt_1d,
    SamplingParams(max_tokens, ...)) -> rid``; ``step()`` runs one
    scheduler round (admit + prefill new slots, one batched decode step)
    and returns the requests that finished; ``drain()`` steps until idle.
    ``generate`` is the drop-in static-batch compatibility wrapper. The
    pre-:class:`EngineConfig` loose-kwarg construction and the positional
    ``submit(prompt, n_tokens, temperature=..., seed=...)`` signature
    still work for one release behind ``DeprecationWarning`` shims
    (docs/serving.md has the migration table).

    With ``prefix_cache=True`` (default, for families whose caches are
    uniform attention ring buffers) the KV cache is a physical-block arena
    behind per-slot block tables, and a :class:`RadixPrefixCache` maps
    committed prompt prefixes to block chains: an admitted request
    references the longest cached block-aligned prefix of its prompt
    (refcount++, zero recompute) and prefills only the uncached suffix;
    on completion its full blocks are committed back into the trie.
    ``prefix_stats()`` reports hit rate and prefill tokens saved.

    With ``prefill_chunk=N`` (block mode only) admitted prompts prefill at
    most ``N`` tokens per ``step()``: the uncached part of each prompt is
    split into fixed chunks, the slot sits in the scheduler's PREFILLING
    phase while its chunks land, and every other slot keeps decoding each
    step — per-step latency is bounded by one chunk of prefill plus one
    batched decode regardless of prompt length, killing the head-of-line
    blocking a monolithic prefill causes. Output is token-exact vs
    unchunked prefill. ``prefill_backlog`` caps how many chunk-prefill
    groups may be in flight before admission pauses (in-flight chunk work
    the admission gate accounts for).

    With ``use_paged_kernel=True`` (block mode only) the decode step runs
    the fused paged-attention kernel: attention reads each slot's K/V
    through its block table *in place* instead of materializing the
    gathered arena view every step (``paged_impl`` overrides the backend
    auto-pick — ``"pallas"`` on TPU, ``"xla"`` scan fallback elsewhere).
    Token-exact vs the gather path; see ``docs/serving.md``.

    With ``fused_step=True`` (requires ``prefill_chunk``) a step that
    services a chunk-prefill group issues ONE ``mixed_step`` dispatch
    covering the whole decode batch *and* the chunk: the chunk's rows are
    concatenated after the per-slot decode rows, every row routes through
    its own block table with a per-row valid-token count, and the chunk's
    K/V commits into the arena inside the same launch — the separate
    chunk-then-decode sequencing (two dispatches plus a host-side block
    commit) remains the token-exact parity reference when off.

    With ``spec_decode=True`` (block mode only) pure-decode steps run
    self-speculatively: ``spec_k`` draft tokens are proposed by the model
    itself with SWIS weights truncated to ``draft_slices`` bit-planes
    (``None``: full precision), one full-precision verify launch scores
    every proposal, and the longest matching prefix plus the verify's
    bonus token is accepted — several tokens per step when drafts agree,
    never fewer than one, and token-exact vs. plain decode for every
    accept pattern (see docs/serving.md "Speculative decode"). Steps that
    service a fused chunk group still run the plain ``_mixed_once`` path.
    """

    def __init__(self, cfg: ArchConfig, params: Any,
                 config: Optional[EngineConfig] = None, **legacy):
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or the legacy "
                    "loose kwargs, not both")
            known = {f.name for f in dataclasses.fields(EngineConfig)}
            unknown = set(legacy) - known
            if unknown:
                raise TypeError(
                    f"unknown engine kwargs {sorted(unknown)}; valid "
                    f"EngineConfig fields: {sorted(known)}")
            warnings.warn(
                "ContinuousBatchingEngine(cfg, params, max_len=..., ...) "
                "loose kwargs are deprecated; pass "
                "config=EngineConfig(...) instead", DeprecationWarning,
                stacklevel=2)
            config = EngineConfig(**legacy)
        elif config is None:
            config = EngineConfig()
        elif not isinstance(config, EngineConfig):
            raise TypeError(
                f"config must be an EngineConfig, got "
                f"{type(config).__name__} (legacy positional max_len is "
                f"not supported here — pass EngineConfig(max_len=...))")
        self.config = config
        self.cfg, self.params, self.pack_stats = _maybe_pack(
            cfg, params, config.packed, config.quant_cfg)
        max_len = config.max_len
        n_slots = config.n_slots
        prefill_chunk = config.prefill_chunk
        enable_metrics = config.enable_metrics
        # observability substrate (docs/serving.md "Observability"):
        # phase timers + counters in the registry, per-request lifecycle
        # events in the tracer, all surfaced through engine.metrics().
        # enable_metrics=False swaps in no-op instruments — the hot path
        # pays one attribute check per phase.
        self.metrics_registry = MetricsRegistry(enabled=enable_metrics)
        self.tracer = RequestTracer(capacity=config.trace_capacity,
                                    enabled=enable_metrics)
        self.max_len = max_len
        self.n_slots = n_slots
        self.model = Model(self.cfg)
        uniform = SlotKVCache.supports_blocks(self.model, max_len)
        # bucket padding is only sound for pure attention caches: the pad
        # tokens' cache writes are masked out by pos. Stateful caches
        # (mamba/rec) would absorb the pads into their recurrent state and
        # a window-truncated ring could roll real KV out in their favor.
        self.bucket_prompts = config.bucket_prompts and uniform
        self.scheduler = RequestScheduler(n_slots)
        cache_dtype = jnp.dtype(config.cache_dtype or self.cfg.compute_dtype)
        if config.prefix_cache and uniform:
            bps = -(-max_len // config.block_size)
            extra = (2 * bps if config.n_cache_blocks is None
                     else config.n_cache_blocks)
            n_blocks = n_slots * bps + extra + 1  # +1: trash block
            self.cache = SlotKVCache(self.model, n_slots, max_len,
                                     cache_dtype,
                                     block_size=config.block_size,
                                     n_blocks=n_blocks)
            self.prefix_cache: Optional[RadixPrefixCache] = RadixPrefixCache(
                BlockPool(n_blocks, config.block_size))
            self._wire_scheduler()
            self._slot_meta: Dict[int, dict] = {}
        else:
            # recurrent / window-truncated caches: contiguous per-slot rows
            self.cache = SlotKVCache(self.model, n_slots, max_len,
                                     cache_dtype)
            self.prefix_cache = None
        if prefill_chunk is not None:
            if self.prefix_cache is None:
                raise ValueError(
                    "prefill_chunk requires the block-mode prefix cache "
                    "(uniform attention caches with prefix_cache=True)")
            # chunk boundaries must be block-aligned so each chunk commits
            # whole blocks into the arena as it lands
            bs = self.cache.block_size
            prefill_chunk = max(bs, -(-prefill_chunk // bs) * bs)
        self.prefill_chunk = prefill_chunk
        self.prefill_backlog = config.prefill_backlog
        self.fused_step = config.fused_step
        self._prefill_groups: collections.deque = collections.deque()
        # fused paged-attention decode: indexes the KV arena through the
        # block tables *inside* the attention kernel, so the per-step
        # gathered K/V copy of the reference path is never materialized.
        # "pallas" is the TPU kernel; "xla" is the scan fallback with the
        # same masking/accumulation contract for backends without Pallas
        # compile support; "pallas_interpret" exists for validation.
        if config.use_paged_kernel and self.prefix_cache is None:
            raise ValueError(
                "use_paged_kernel requires the block-mode prefix cache "
                "(uniform attention caches with prefix_cache=True)")
        if config.use_paged_kernel:
            self.paged_impl = config.paged_impl or (
                "pallas" if jax.default_backend() == "tpu" else "xla")
        else:
            self.paged_impl = None
        # the Pallas kernel's K/V tile (keys, tiles per table row), for the
        # kv_tiles count of the dispatch spans
        self._kv_tile = None
        if self.paged_impl in ("pallas", "pallas_interpret"):
            nb, bs = self.cache.blocks_per_slot, self.cache.block_size
            pages = tile_pages(nb, bs, self.cfg.n_kv_heads,
                               self.cfg.head_dim, cache_dtype)
            self._kv_tile = (pages * bs, -(-nb // pages))
        # analytical per-dispatch cost model (costmodel.py): every model
        # launch records predicted FLOPs / HBM bytes / SWIS shift-pass
        # cycles as cost.* counters + per-kind histograms. Built from the
        # live (possibly packed) params, so packed GEMMs are costed at
        # their bit-plane footprint.
        self.cost_model = CostModel.for_engine(self)
        self._step_no = 0
        self._prefill_flat = jax.jit(self.model.prefill_bucketed)
        self._prefill_sfx = jax.jit(self.model.prefill_chunk)
        self._decode = jax.jit(
            functools.partial(self.model.decode_step, paged=self.paged_impl),
            donate_argnums=(2,))
        self._mixed = jax.jit(
            functools.partial(self.model.mixed_step, paged=self.paged_impl),
            donate_argnums=(2,))
        # self-speculative decode: the draft model IS the target model —
        # same packed params, same arena — under a quant policy whose
        # keep_slices truncates every packed GEMM to the top draft_slices
        # bit-planes (draft_slices=None: full-precision draft, accept
        # rate 1.0 by construction). Draft steps are S=1 q_lens-masked
        # mixed launches; the verify launch scores all spec_k+1 positions
        # at full precision in one dispatch (Model.verify_step).
        self.spec_decode = config.spec_decode
        self.spec_k = config.spec_k
        if config.spec_decode:
            if self.prefix_cache is None:
                raise ValueError(
                    "spec_decode requires the block-mode prefix cache "
                    "(uniform attention caches with prefix_cache=True)")
            if config.draft_slices is None:
                self.draft_model = self.model
            else:
                total = total_slices(self.params)
                if not 1 <= config.draft_slices <= total:
                    raise ValueError(
                        f"draft_slices={config.draft_slices} out of range: "
                        f"the packed weights carry {total} bit-slices "
                        f"(1 <= draft_slices <= {total})")
                self.draft_model = Model(self.cfg.replace(
                    quant=dataclasses.replace(
                        self.cfg.quant, keep_slices=config.draft_slices)))
            self._draft = jax.jit(
                functools.partial(self.draft_model.mixed_step,
                                  paged=self.paged_impl),
                donate_argnums=(2,))
            self._verify = jax.jit(
                functools.partial(self.model.verify_step,
                                  paged=self.paged_impl),
                donate_argnums=(2,))
        self._dummy_key = jax.random.key(0)
        self._stat_prefill_tokens = 0
        self._stat_saved_tokens = 0
        self._stat_chunk_steps = 0

    # -- request API ----------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               n_tokens: Optional[int] = None, temperature: float = 0.0,
               key=None, seed: Optional[int] = None, extra=None) -> int:
        """Enqueue a request: ``submit(prompt, SamplingParams(max_tokens,
        temperature=..., seed=...), extra=...)``. ``seed`` (or an explicit
        ``key``) makes the request's sampling reproducible; when neither
        is given, each request gets a distinct auto-key — independent
        clients must not draw identical streams. The legacy positional
        signature ``submit(prompt, n_tokens, temperature=..., key=...,
        seed=...)`` still works behind a ``DeprecationWarning``."""
        if isinstance(params, SamplingParams):
            if (n_tokens is not None or temperature or key is not None
                    or seed is not None):
                raise TypeError(
                    "legacy sampling kwargs (n_tokens/temperature/key/"
                    "seed) cannot be combined with SamplingParams")
        else:
            if isinstance(params, (int, np.integer)):
                if n_tokens is not None:
                    raise TypeError(
                        "got both a positional token budget and n_tokens")
                n_tokens = int(params)
            elif params is not None:
                raise TypeError(
                    f"submit() expects SamplingParams, got "
                    f"{type(params).__name__}")
            if n_tokens is None:
                raise TypeError(
                    "submit() needs a SamplingParams (or the deprecated "
                    "n_tokens kwarg)")
            warnings.warn(
                "submit(prompt, n_tokens, temperature=..., key=..., "
                "seed=...) is deprecated; pass "
                "submit(prompt, SamplingParams(max_tokens, ...))",
                DeprecationWarning, stacklevel=2)
            params = SamplingParams(
                max_tokens=int(n_tokens), temperature=temperature,
                seed=seed if key is None else None,
                key=key)
        return self._submit(prompt, params, extra)

    def _submit(self, prompt, sp: SamplingParams, extra=None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + sp.max_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_tokens ({sp.max_tokens}) "
                f"exceeds max_len ({self.max_len})")
        key = sp.key
        if key is None:
            if sp.seed is not None:
                key = jax.random.key(sp.seed)
            else:
                key = jax.random.fold_in(self._dummy_key,
                                         self.scheduler.next_rid())
        rid = self.scheduler.submit(prompt, sp.max_tokens, sp.temperature,
                                    key, extra)
        self.tracer.event(tr.SUBMIT, rid, prompt_len=int(prompt.size),
                          n_tokens=int(sp.max_tokens))
        return rid

    def step(self) -> List[Finished]:
        """One scheduler round: admit queued requests (unless the chunked
        backlog is full), run at most one chunk of prefill work, then one
        batched decode step over the DECODING slots. With ``fused_step``
        the chunk and the decode batch ride ONE ``mixed_step`` dispatch
        (``step.mixed_dispatch_s``) instead of two sequenced launches.

        Phase timers (``step.*_s`` histograms in ``metrics_registry``):
        admit, prefix_match, group_setup, build_batch, prefill_dispatch,
        chunk_advance, mixed_dispatch, decode_dispatch, device_sync,
        sample_host, record — plus ``step.total_s`` for the whole round.
        Each phase is also a profiler span ``serve.<phase>`` (docs/
        serving.md "Observability"). ``step.model_dispatches`` counts
        forward launches (the fused win the dispatch-count test and the
        mixed_load bench gate measure)."""
        m = self.metrics_registry
        self.tracer.current_step = self._step_no
        with self._phase("step.total_s", "step"):
            if len(self._prefill_groups) < self.prefill_backlog:
                with self._phase("step.admit_s", "admit"):
                    admitted = self.scheduler.admit()
                    for slot, st in admitted:
                        self.tracer.event(tr.ADMIT, st.req.rid, slot=slot)
                if admitted:
                    self._prefill_admitted(admitted)
            decoded = False
            if self._prefill_groups:
                if self._prefill_groups[0].get("fused"):
                    # one dispatch services the chunk AND the decode batch
                    self._mixed_once()
                    decoded = True
                else:
                    with self._phase("step.chunk_advance_s",
                                     "chunk_advance"):
                        self._advance_chunk()
            if not decoded and self.scheduler.needs_decode():
                if self.spec_decode:
                    self._spec_once()
                else:
                    self._decode_once()
            with self._phase("step.record_s", "record"):
                finished = self.scheduler.pop_finished()
                for f in finished:
                    self.tracer.event(tr.FINISH, f.rid,
                                      n_tokens=len(f.tokens))
                m.counter("step.count").inc()
                self._step_no += 1
        if m.enabled:
            # model-vs-measured utilization: bytes the cost model says the
            # issued dispatches should have moved, over measured step time
            total = m.histogram("step.total_s").total
            if total > 0.0:
                m.gauge("cost.hbm_bytes_per_s").set(
                    m.counter("cost.hbm_bytes").value / total)
                m.gauge("cost.flops_per_s").set(
                    m.counter("cost.flops").value / total)
        return finished

    def drain(self) -> Dict[int, np.ndarray]:
        """Step until idle. Returns {rid: prompt + generated tokens}."""
        out: Dict[int, np.ndarray] = {}
        while self.scheduler.pending():
            for f in self.step():
                out[f.rid] = np.concatenate([f.prompt, f.tokens])
        return out

    # -- static-batch compatibility wrapper -----------------------------

    def generate(self, prompt: np.ndarray, n_tokens: int,
                 extra: Optional[Dict[str, Any]] = None,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Drop-in for ``DecodeEngine.generate``: prompt (B, S0) int32 ->
        (B, S0 + n_tokens). Row r samples with key fold_in(key(seed), r),
        matching the legacy engine token-for-token."""
        b, s0 = prompt.shape
        assert s0 + n_tokens <= self.max_len
        if self.scheduler.pending():
            raise RuntimeError(
                "generate() requires an idle engine (drain() would consume "
                "in-flight requests' results); use submit/step/drain")
        rng = jax.random.key(seed)
        rids = []
        for r in range(b):
            ex = ({k: np.asarray(v)[r] for k, v in extra.items()}
                  if extra else None)
            rids.append(self._submit(
                prompt[r],
                SamplingParams(max_tokens=n_tokens, temperature=temperature,
                               key=jax.random.fold_in(rng, r)),
                extra=ex))
        out = self.drain()
        return np.stack([out[rid] for rid in rids])

    def reset(self) -> None:
        """Return an idle engine to its post-construction state (empty
        queue, empty prefix cache, zeroed stats) *without* dropping the
        jit caches — benchmarks measure steady-state serving by running a
        warmup pass, resetting, and measuring the second pass on already
        compiled shapes. Stale arena K/V is left in place: every
        allocation path scrubs the blocks it takes over (whole-tree
        scatter unchunked, ``invalidate_blocks`` chunked) before their
        positions can enter a mask."""
        if self.scheduler.pending():
            raise RuntimeError("reset() requires an idle engine")
        self.scheduler = RequestScheduler(self.n_slots)
        self._prefill_groups.clear()
        if self.prefix_cache is not None:
            self.prefix_cache = RadixPrefixCache(
                BlockPool(self.cache.n_blocks, self.cache.block_size))
            self._wire_scheduler()
            self._slot_meta = {}
            for slot in range(self.n_slots):
                self.cache.clear_table(slot)
        self._stat_prefill_tokens = 0
        self._stat_saved_tokens = 0
        self._stat_chunk_steps = 0
        # back-to-back bench runs on one engine must start from clean
        # counters: fresh lifecycle data, zeroed phase timers
        self.metrics_registry.reset()
        self.tracer.reset()
        self._step_no = 0

    # -- observability ---------------------------------------------------

    def _phase(self, hist: str, span: str) -> _Phase:
        """Phase timing context: one clock-pair feeds the ``hist``
        histogram AND (tracer enabled) a named span in the trace ring —
        the span nests under the enclosing ``step`` span by timestamp
        containment in the Chrome trace export. The same phase is a
        profiler span ``serve.<span>`` whether or not metrics are on."""
        if self.tracer.enabled:
            timer = self.tracer.span_timer(
                span, self.metrics_registry.histogram(hist))
        else:
            timer = self.metrics_registry.timer(hist)
        return _Phase("serve." + span, timer)

    def _record_cost(self, cost) -> None:
        """Record one dispatch's predicted cost: global + per-kind
        ``cost.*`` counters, per-kind per-dispatch histograms."""
        m = self.metrics_registry
        if not m.enabled:
            return
        for field in _COST_FIELDS:
            v = getattr(cost, field)
            m.counter(f"cost.{field}").inc(v)
            m.counter(f"cost.{cost.kind}.{field}").inc(v)
            m.histogram(f"cost.{cost.kind}.{field}",
                        _COST_EDGES).observe(v)
        if cost.gathered_bytes:
            m.counter("cost.gathered_bytes").inc(cost.gathered_bytes)

    def metrics(self) -> Dict[str, Any]:
        """One unified observability snapshot: engine phase timers and
        counters, scheduler gauges, prefix-cache / BlockPool stats, and
        trace-ring health. ``prefix_stats()`` is a view of the
        ``prefix_cache`` section; metric names/units are tabulated in
        docs/serving.md ("Observability")."""
        snap = self.metrics_registry.snapshot()
        out: Dict[str, Any] = {
            "engine": {"n_slots": self.n_slots, "max_len": self.max_len,
                       "prefill_chunk": self.prefill_chunk,
                       "paged_impl": self.paged_impl,
                       "chunk_backlog_depth": len(self._prefill_groups),
                       "phases": snap["histograms"],
                       "counters": snap["counters"],
                       "gauges": snap["gauges"],
                       "cost_model": self.cost_model.summary()},
            "scheduler": self.scheduler.gauges(),
            "prefix_cache": self._prefix_cache_section(),
            "trace": {"events": len(self.tracer),
                      "dropped": self.tracer.dropped,
                      "capacity": self.tracer.capacity,
                      "spans": len(self.tracer.spans()),
                      "dropped_spans": self.tracer.dropped_spans},
        }
        if self.prefix_cache is not None:
            out["block_pool"] = self.prefix_cache.pool.occupancy()
        return out

    def _prefix_cache_section(self) -> Dict[str, Any]:
        if self.prefix_cache is None:
            return {"enabled": False,
                    "prefill_tokens": self._stat_prefill_tokens,
                    "saved_tokens": 0, "prefill_chunk": None,
                    "prefill_chunk_steps": 0}
        out = self.prefix_cache.stats()
        out.update(enabled=True, block_size=self.cache.block_size,
                   prefill_tokens=self._stat_prefill_tokens,
                   saved_tokens=self._stat_saved_tokens,
                   hit_tokens=self._stat_saved_tokens,
                   prefill_chunk=self.prefill_chunk,
                   prefill_chunk_steps=self._stat_chunk_steps)
        return out

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-cache health: hit rate, tokens saved vs computed, block
        commits/evictions, arena occupancy. Delegates to
        :meth:`metrics` — same dict as ``metrics()['prefix_cache']``."""
        return self._prefix_cache_section()

    # -- internals ------------------------------------------------------

    def _wire_scheduler(self) -> None:
        self.scheduler.on_release = self._release_slot
        self.scheduler.admission_priority = self._hit_score

    # -- internals ------------------------------------------------------

    def _hit_score(self, req) -> int:
        """Cache-aware admission: expected cached-prefix tokens (0 for
        requests with extra inputs, which never share prefixes)."""
        if req.extra:
            return 0
        bs = self.cache.block_size
        return bs * self.prefix_cache.peek_blocks(
            req.prompt, max_blocks=(len(req.prompt) - 1) // bs)

    def _bucket(self, s: int, prefix_len: int) -> int:
        """Pad a (suffix) prefill length up to a power-of-two bucket so the
        jit cache holds one entry per bucket, not one per distinct prompt
        length. Clamped to the cache capacity past the prefix."""
        cap = (self.cache.eff_len if self.prefix_cache is not None
               else self.max_len) - prefix_len
        if not self.bucket_prompts:
            return s
        return min(max(8, 1 << max(s - 1, 0).bit_length()), cap)

    def _assign_blocks(self, admitted):
        """Block-mode admission: match each request's prompt against the
        radix trie, reference the cached prefix blocks, and allocate owned
        blocks for the rest (evicting unreferenced LRU blocks on pressure).
        Requests the pool cannot cover yet go back to the queue."""
        pool = self.prefix_cache.pool
        bs = self.cache.block_size
        ok, failed = [], []
        for slot, st in admitted:
            rid = st.req.rid
            req = st.req
            s0 = len(req.prompt)
            need = -(-(s0 + req.n_tokens) // bs)
            # cap the match so at least one suffix token runs through the
            # model — its logits seed generation
            matched = ([] if req.extra else self.prefix_cache.match(
                req.prompt, max_blocks=(s0 - 1) // bs))
            pool.incref(matched)
            own = need - len(matched)
            if pool.n_free() < own:
                self.prefix_cache.evict(own - pool.n_free())
            ids = pool.alloc(own)
            if ids is None:
                self.prefix_cache.release(matched)
                failed.append(slot)
                self.tracer.event(tr.UNADMIT, rid, slot=slot,
                                  blocks_needed=own,
                                  blocks_free=pool.n_free())
                continue
            if not req.extra:
                self.prefix_cache.count_lookup(matched)
            if matched:
                self.tracer.event(tr.PREFIX_HIT, rid, slot=slot,
                                  blocks=len(matched),
                                  tokens=len(matched) * bs)
            pool.incref(ids)
            if self.prefill_chunk is None:
                self.cache.set_table(slot, matched + ids)
            # chunked mode: the table stays on the trash block until the
            # last chunk lands — a PREFILLING slot's dummy decode row must
            # not write into (possibly shared) live blocks
            self._slot_meta[slot] = {"matched": matched, "owned": ids,
                                     "need": need,
                                     "prefix_blocks": len(matched)}
            self._stat_saved_tokens += len(matched) * bs
            ok.append((slot, st))
        for slot in reversed(failed):  # appendleft: reverse keeps FIFO
            self.scheduler.unadmit(slot)
        return ok

    def _release_slot(self, slot: int, st) -> None:
        """Scheduler release hook (block mode): commit the request's full
        token blocks into the trie, drop its block references, and point
        the freed slot's table at the trash block so dummy decode writes
        cannot touch live blocks."""
        meta = self._slot_meta.pop(slot, None)
        if meta is None:
            return
        if not st.req.extra:
            # cache rows hold K/V for prompt + all *fed-back* tokens (the
            # final sampled token never re-enters the model)
            seq = np.concatenate(
                [st.req.prompt, np.asarray(st.tokens[:-1], np.int32)])
            n_commit = min(len(seq) // self.cache.block_size, meta["need"])
            self.prefix_cache.commit(
                seq, self.cache.block_tables[slot, :n_commit].tolist())
        self.prefix_cache.release(meta["matched"] + meta["owned"])
        self.cache.clear_table(slot)

    def _prefill_admitted(self, admitted) -> None:
        # Group by (prefix length, bucketed suffix length, extra-input
        # signature — so requests with and without e.g. vlm patches never
        # share a batch): one batched prefill per group keeps the jit
        # shapes bounded and makes lockstep admission numerically identical
        # to a static-batch prefill.
        if self.prefix_cache is not None:
            with self._phase("step.prefix_match_s", "prefix_match"):
                admitted = self._assign_blocks(admitted)
            if self.prefill_chunk is not None:
                # staging stays in ``chunk_advance``, its histogram's
                # meaning on both paths; ``group_setup`` is the inner span
                with self._phase("step.chunk_advance_s", "chunk_advance"), \
                        self._phase("step.group_setup_s", "group_setup"):
                    self._stage_chunked(admitted)
                return
        self._run_prefill(admitted)

    def _dispatch_counts(self, span, start, q_lens, n_decode: int,
                         kernel_q_lens=None) -> None:
        """Attach a launch's counts to its dispatch span, only while a
        profiler trace records. Rows ``[0, n_decode)`` are decode rows, the
        rest a chunk group's; row r feeds ``q_lens[r]`` tokens from
        position ``start[r]`` and so attends ``start[r] + q_lens[r]``
        positions. Where the launch runs the Pallas paged kernel, with
        ``kernel_q_lens`` queries a row, ``kv_tiles`` counts the (row, K/V
        tile) pairs the kernel visits, by its own rule."""
        if not span.is_enabled():
            return
        live = q_lens > 0
        counts = dict(
            decode_rows=int(live[:n_decode].sum()),
            chunk_rows=int(live[n_decode:].sum()),
            chunk_tokens=int(q_lens[n_decode:].sum()),
            kv_tokens=int((start + q_lens)[live].sum()))
        if kernel_q_lens is not None and self._kv_tile is not None:
            tk, n_tiles = self._kv_tile
            lo, hi = live_tiles(np.asarray(start), np.asarray(kernel_q_lens),
                                tk=tk, n_tiles=n_tiles, causal=True,
                                window=None)
            counts["kv_tiles"] = int((hi - lo).sum())
        span.set_metadata(**counts)

    def _run_prefill(self, admitted) -> None:
        with self._phase("step.group_setup_s", "group_setup"):
            groups: Dict[Any, list] = {}
            for slot, st in admitted:
                ex = st.req.extra
                sig = (tuple(sorted((k, np.shape(v))
                                    for k, v in ex.items()))
                       if ex else None)
                pb = (self._slot_meta[slot]["prefix_blocks"]
                      if self.prefix_cache is not None else 0)
                p_len = pb * (self.cache.block_size or 0)
                s_real = len(st.req.prompt) - p_len
                groups.setdefault((p_len, self._bucket(s_real, p_len), sig),
                                  []).append((slot, st))
        for (p_len, s_pad, _), group in groups.items():
            with self._phase("step.build_batch_s", "build_batch"):
                g = len(group)
                toks = np.zeros((g, s_pad), np.int32)
                lasts = np.empty(g, np.int32)
                for i, (_, st) in enumerate(group):
                    sfx = st.req.prompt[p_len:]
                    toks[i, :len(sfx)] = sfx
                    lasts[i] = len(sfx) - 1
                batch = {"tokens": jnp.asarray(toks)}
                extras = [st.req.extra for _, st in group]
                if extras[0]:
                    for k in extras[0]:
                        batch[k] = jnp.asarray(
                            np.stack([ex[k] for ex in extras]))
                last_idx = jnp.asarray(lasts)
                self._stat_prefill_tokens += int(lasts.sum()) + g
                self.metrics_registry.counter("step.model_dispatches").inc()
                self._record_cost(self.cost_model.prefill(g, s_pad))
                if self.prefix_cache is not None:
                    meta = [self._slot_meta[slot] for slot, _ in group]
                    cache = self.cache.prefix_tree(
                        [m["matched"] for m in meta], p_len)
                else:
                    cache = self.cache.fresh(g)
            with self._phase("step.prefill_dispatch_s",
                             "prefill_dispatch") as span:
                self._dispatch_counts(
                    span, np.full(g, p_len, np.int32), lasts + 1, 0)
                if p_len:
                    logits, cache = self._prefill_sfx(
                        self.params, batch, cache, jnp.int32(p_len),
                        last_idx)
                else:
                    logits, cache = self._prefill_flat(
                        self.params, batch, cache, last_idx)
            with self._phase("step.record_s", "record"):
                if self.prefix_cache is not None:
                    for i, (slot, st) in enumerate(group):
                        self.cache.scatter_row(
                            cache, i, meta[i]["owned"],
                            meta[i]["prefix_blocks"],
                            len(st.req.prompt) - p_len)
                else:
                    cache = self.cache.mask_pos_tail(
                        cache, [len(st.req.prompt) for _, st in group])
                    self.cache.write_slots(cache,
                                           [slot for slot, _ in group])
            with self._phase("step.sample_host_s", "sample_host"):
                keys = jnp.stack([st.req.key for _, st in group])
                temps = jnp.asarray(
                    [st.req.temperature for _, st in group], jnp.float32)
                steps = jnp.zeros(g, jnp.int32)
                first = np.asarray(sample_step(logits, keys, steps, temps))
            with self._phase("step.record_s", "record"):
                for (slot, st), tok in zip(group, first):
                    self.tracer.event(tr.FIRST_TOKEN, st.req.rid, slot=slot)
                    self.scheduler.record_prefill(slot, tok)

    def _stage_chunked(self, admitted) -> None:
        """Stage admitted requests as chunk-prefill groups (no model work
        yet — ``_advance_chunk`` runs one chunk per engine step). Grouping
        key: (prefix length, chunk count, bucketed final-chunk length,
        extra-input signature), so every row of a group advances through
        the same chunk geometry in lockstep and one jit'd call per step
        covers the whole group. The working tree is gathered once here
        (cached prefix only) and carried across steps; chunk boundaries
        are block-aligned, so each chunk commits whole blocks as it
        lands."""
        chunk = self.prefill_chunk
        bs = self.cache.block_size
        groups: Dict[Any, list] = {}
        for slot, st in admitted:
            ex = st.req.extra
            sig = (tuple(sorted((k, np.shape(v)) for k, v in ex.items()))
                   if ex else None)
            p_len = self._slot_meta[slot]["prefix_blocks"] * bs
            s_real = len(st.req.prompt) - p_len
            n_chunks = -(-s_real // chunk)
            tail = self._bucket(s_real - (n_chunks - 1) * chunk,
                                p_len + (n_chunks - 1) * chunk)
            groups.setdefault((p_len, n_chunks, tail, sig),
                              []).append((slot, st))
        for (p_len, n_chunks, tail, sig), members in groups.items():
            g = len(members)
            s_pad = (n_chunks - 1) * chunk + tail
            toks = np.zeros((g, s_pad), np.int32)
            lasts = np.empty(g, np.int32)
            metas = []
            for i, (slot, st) in enumerate(members):
                meta = self._slot_meta[slot]
                metas.append(meta)
                sfx = st.req.prompt[p_len:]
                toks[i, :len(sfx)] = sfx
                lasts[i] = len(sfx) - (n_chunks - 1) * chunk - 1
            # owned blocks commit chunk by chunk, so scrub their stale pos
            # up front (one batched call): the not-yet-reached tail must
            # never enter an attention mask (unchunked mode scrubs by
            # scattering the whole fresh working tree instead)
            self.cache.invalidate_blocks(
                [b for m in metas for b in m["owned"]])
            grp = {
                "members": members, "metas": metas, "toks": toks,
                "lasts": lasts, "p_len": p_len, "n_chunks": n_chunks,
                "tail": tail, "done": 0, "tree": None,
                "extra": [st.req.extra for _, st in members]}
            if self.fused_step and sig is None:
                # fused groups need no working tree at all: each chunk
                # commits straight into the arena through the group's
                # per-row block tables inside the mixed launch (extra-input
                # groups fall back to the separate path — mixed batches
                # carry no per-row side inputs)
                grp["fused"] = True
                grp["tables"] = self.cache.group_tables(
                    [m["matched"] + m["owned"] for m in metas])
            else:
                # the working tree only needs committed + padded-suffix
                # rows, not the slot's full capacity — chunk attention
                # stays O(chunk * committed) instead of O(chunk *
                # eff_len). Rounded up to a pow2 (then a block multiple)
                # so distinct prefix-hit lengths share jit cache entries
                # instead of compiling per p_len.
                need = p_len + s_pad
                length = -(-(1 << max(need - 1, 0).bit_length()) // bs) * bs
                length = min(self.cache.eff_len, max(length, bs))
                grp["tree"] = self.cache.prefix_tree(
                    [m["matched"] for m in metas], p_len, length=length)
                grp["tree_len"] = length  # chunk cost: attended positions
            self._prefill_groups.append(grp)

    def _advance_chunk(self) -> None:
        """Run one chunk of prefill for the head group, round-robin across
        in-flight groups: prefill the chunk's tokens at the group's
        committed offset, attend over everything committed so far, and
        scatter the chunk's blocks into the arena. On the final chunk,
        sample each row's first token and flip its slot to DECODING (its
        block table goes live now, never earlier)."""
        grp = self._prefill_groups[0]
        chunk = self.prefill_chunk
        bs = self.cache.block_size
        k = grp["done"]
        final = k == grp["n_chunks"] - 1
        s_chunk = grp["tail"] if final else chunk
        lo = k * chunk
        g = len(grp["members"])
        committed = grp["p_len"] + lo
        with self._phase("step.build_batch_s", "build_batch"):
            batch = {"tokens": jnp.asarray(grp["toks"][:, lo:lo + s_chunk])}
            extras = grp["extra"]
            if extras[0]:
                for key in extras[0]:
                    batch[key] = jnp.asarray(
                        np.stack([ex[key] for ex in extras]))
            last_idx = (jnp.asarray(grp["lasts"]) if final
                        else jnp.full((g,), s_chunk - 1, jnp.int32))
            n_valids = np.asarray(
                [min(len(st.req.prompt) - grp["p_len"] - lo, s_chunk)
                 for _, st in grp["members"]], np.int32)
            self._stat_chunk_steps += 1
            self.metrics_registry.counter("step.model_dispatches").inc()
            self._record_cost(self.cost_model.chunk(g, s_chunk,
                                                    grp["tree_len"]))
        with self._phase("step.prefill_dispatch_s",
                         "prefill_dispatch") as span:
            self._dispatch_counts(span, np.full(g, committed, np.int32),
                                  n_valids, 0)
            if committed == 0:
                # first chunk of an uncached prompt: nothing committed, the
                # chunk attends over its own K/V like a whole-prompt prefill
                logits, tree = self._prefill_flat(self.params, batch,
                                                  grp["tree"], last_idx)
            else:
                logits, tree = self._prefill_sfx(
                    self.params, batch, grp["tree"], jnp.int32(committed),
                    last_idx)
        with self._phase("step.record_s", "record"):
            grp["tree"] = tree
            grp["done"] = k + 1
            # append this chunk at its offset into each row's owned blocks
            b0 = lo // bs
            for i, (slot, st) in enumerate(grp["members"]):
                meta = grp["metas"][i]
                n_valid = int(n_valids[i])
                nb = -(-n_valid // bs)
                self.cache.scatter_row(tree, i, meta["owned"][b0:b0 + nb],
                                       meta["prefix_blocks"] + b0, n_valid)
                self.tracer.event(tr.PREFILL_CHUNK, st.req.rid, slot=slot,
                                  index=k, n_chunks=grp["n_chunks"],
                                  tokens=n_valid)
            if not final:
                # round-robin across in-flight groups: a 1-chunk group
                # (short prompt) admitted behind a long prefill is serviced
                # on the very next step instead of waiting out every long
                # chunk
                self._prefill_groups.rotate(-1)
                return
            self._prefill_groups.popleft()
            for i, (slot, st) in enumerate(grp["members"]):
                meta = grp["metas"][i]
                self.cache.set_table(slot, meta["matched"] + meta["owned"])
                self._stat_prefill_tokens += (len(st.req.prompt)
                                              - grp["p_len"])
        with self._phase("step.sample_host_s", "sample_host"):
            keys = jnp.stack([st.req.key for _, st in grp["members"]])
            temps = jnp.asarray(
                [st.req.temperature for _, st in grp["members"]],
                jnp.float32)
            first = np.asarray(sample_step(logits, keys,
                                           jnp.zeros(g, jnp.int32), temps))
        with self._phase("step.record_s", "record"):
            for (slot, st), tok in zip(grp["members"], first):
                self.tracer.event(tr.FIRST_TOKEN, st.req.rid, slot=slot)
                self.scheduler.record_prefill(slot, tok)

    def _mixed_once(self) -> None:
        """Service the head fused chunk group AND the whole decode batch
        in ONE ``mixed_step`` dispatch. Batch layout: rows [0, n_slots)
        are the per-slot decode rows (token in column 0, ``q_lens`` 1 for
        DECODING slots, 0 for free/PREFILLING slots — their rows are
        fully masked no-ops), rows [n_slots, n_slots + g) are the chunk
        group's rows (``q_lens`` = real tokens this chunk, block tables =
        matched prefix + owned blocks). Every row commits its valid K/V
        through its own table inside the launch, so the host-side
        ``scatter_row`` of the separate path never runs; invalid tokens
        route to the trash block. Per-request token streams are identical
        to the separate path — a slot whose final chunk lands this step
        simply joins the decode batch next step, same as before."""
        m = self.metrics_registry
        grp = self._prefill_groups[0]
        chunk = self.prefill_chunk
        k = grp["done"]
        final = k == grp["n_chunks"] - 1
        s_chunk = grp["tail"] if final else chunk
        lo = k * chunk
        g = len(grp["members"])
        n = self.n_slots
        with self._phase("step.build_batch_s", "build_batch"):
            toks, idxs, steps, temps, keys = self.scheduler.decode_batch(
                self._dummy_key)
            decoding = self.scheduler.decoding_slots()
            live = [(s, self.scheduler.slots[s].req.rid, int(steps[s]))
                    for s in decoding] if self.tracer.enabled else []
            btoks = np.zeros((n + g, s_chunk), np.int32)
            btoks[:n, 0] = toks
            btoks[n:] = grp["toks"][:, lo:lo + s_chunk]
            q_lens = np.zeros(n + g, np.int32)
            q_lens[decoding] = 1
            start = np.zeros(n + g, np.int32)
            start[:n] = idxs
            start[n:] = grp["p_len"] + lo
            last_idx = np.zeros(n + g, np.int32)
            last_idx[n:] = grp["lasts"] if final else s_chunk - 1
            for i, (slot, st) in enumerate(grp["members"]):
                q_lens[n + i] = min(len(st.req.prompt) - grp["p_len"] - lo,
                                    s_chunk)
            tables = np.concatenate([self.cache.block_tables,
                                     grp["tables"]])
            self._stat_chunk_steps += 1
            m.counter("step.model_dispatches").inc()
            self._record_cost(self.cost_model.mixed(n + g, s_chunk))
            args = (jnp.asarray(start), jnp.asarray(q_lens),
                    jnp.asarray(last_idx), jnp.asarray(tables))
            batch = {"tokens": jnp.asarray(btoks)}
        with self._phase("step.mixed_dispatch_s", "mixed_dispatch") as span:
            self._dispatch_counts(span, start, q_lens, n, q_lens)
            logits, tree = self._mixed(self.params, batch, self.cache.tree,
                                       *args)
            self.cache.tree = tree
        if m.enabled:
            with self._phase("step.device_sync_s", "device_sync"):
                jax.block_until_ready(logits)
        with self._phase("step.sample_host_s", "sample_host"):
            all_keys = list(keys) + [st.req.key for _, st in grp["members"]]
            all_steps = np.concatenate([steps, np.zeros(g, np.int32)])
            all_temps = np.concatenate(
                [temps, np.asarray([st.req.temperature
                                    for _, st in grp["members"]],
                                   np.float32)])
            nxt = np.asarray(sample_step(
                logits, jnp.stack(all_keys), jnp.asarray(all_steps),
                jnp.asarray(all_temps)))
        with self._phase("step.record_s", "record"):
            self.scheduler.record_decode(nxt[:n])
            for slot, rid, step in live:
                self.tracer.event(tr.DECODE_STEP, rid, slot=slot, step=step)
            grp["done"] = k + 1
            for i, (slot, st) in enumerate(grp["members"]):
                self.tracer.event(tr.PREFILL_CHUNK, st.req.rid, slot=slot,
                                  index=k, n_chunks=grp["n_chunks"],
                                  tokens=int(q_lens[n + i]))
            if not final:
                self._prefill_groups.rotate(-1)
                return
            self._prefill_groups.popleft()
            for i, (slot, st) in enumerate(grp["members"]):
                meta = grp["metas"][i]
                self.cache.set_table(slot, meta["matched"] + meta["owned"])
                self._stat_prefill_tokens += (len(st.req.prompt)
                                              - grp["p_len"])
                self.tracer.event(tr.FIRST_TOKEN, st.req.rid, slot=slot)
                self.scheduler.record_prefill(slot, int(nxt[n + i]))

    def _decode_once(self) -> None:
        m = self.metrics_registry
        with self._phase("step.build_batch_s", "build_batch"):
            toks, idxs, steps, temps, keys = self.scheduler.decode_batch(
                self._dummy_key)
            decoding = self.scheduler.decoding_slots()
            # (slot, rid, step) of the live rows — captured before
            # record_decode frees finished slots
            live = [(s, self.scheduler.slots[s].req.rid, int(steps[s]))
                    for s in decoding] if self.tracer.enabled else []
            m.counter("step.model_dispatches").inc()
            self._record_cost(self.cost_model.decode(self.n_slots))
            args = (jnp.asarray(toks)[:, None], self.cache.tree,
                    jnp.asarray(idxs))
            if self.prefix_cache is not None:
                args += (self.cache.tables_device(),)
        with self._phase("step.decode_dispatch_s",
                         "decode_dispatch") as span:
            if span.is_enabled():
                q1 = np.zeros(self.n_slots, np.int32)
                q1[decoding] = 1
                # the decode step gives the kernel no query counts: every
                # row, idle ones too, has one query
                self._dispatch_counts(span, idxs, q1, self.n_slots,
                                      np.ones(self.n_slots, np.int32))
            logits, tree = self._decode(self.params, *args)
            self.cache.tree = tree
        if m.enabled:
            # split device wait from host-side sampling: logits are about
            # to be consumed either way, so the sync is not extra work
            with self._phase("step.device_sync_s", "device_sync"):
                jax.block_until_ready(logits)
        with self._phase("step.sample_host_s", "sample_host"):
            nxt = np.asarray(sample_step(
                logits, jnp.stack(keys), jnp.asarray(steps),
                jnp.asarray(temps)))
        with self._phase("step.record_s", "record"):
            self.scheduler.record_decode(nxt)
            for slot, rid, step in live:
                self.tracer.event(tr.DECODE_STEP, rid, slot=slot, step=step)

    def _spec_once(self) -> None:
        """One self-speculative decode round over the DECODING slots.

        Draft: ``k_max`` sequential S=1 launches of the truncated-slice
        draft model, each proposing the next token per row through the
        SAME seeded sampler (key, step) the verify targets use — so a
        draft that produces the full-precision logits reproduces the
        target token exactly. Rows draft only up to their budget
        ``k_rows[r] = min(spec_k, remaining - 1)`` (the verify's bonus
        token is the +1); beyond it their ``q_lens`` drops to 0 and their
        writes route to the trash block.

        Verify: ONE full-precision launch feeds ``[t0, d1..dk]`` per row
        through :meth:`Model.verify_step`, scoring all ``k_max + 1``
        positions and rewriting every draft-fed arena position at full
        precision (which is the whole KV rollback story — see
        kv_cache.py). Targets for all positions come from one flattened
        ``sample_step``; row r accepts drafts while ``d[j] ==
        target[j-1]`` and always emits at least target[0] — the token
        plain decode would have produced, hence token-exactness for every
        accept pattern.
        """
        m = self.metrics_registry
        n = self.n_slots
        with self._phase("step.build_batch_s", "build_batch"):
            toks, idxs, steps, temps, keys = self.scheduler.decode_batch(
                self._dummy_key)
            decoding = self.scheduler.decoding_slots()
            k_rows = np.zeros(n, np.int32)
            for s in decoding:
                st = self.scheduler.slots[s]
                k_rows[s] = min(self.spec_k, st.req.n_tokens - st.n_gen - 1)
            k_max = int(k_rows.max(initial=0))
            if k_max:
                live = [(s, self.scheduler.slots[s].req.rid, int(steps[s]))
                        for s in decoding] if self.tracer.enabled else []
                tables = self.cache.tables_device()
                keys_dev = jnp.stack(keys)
                zeros = jnp.zeros(n, jnp.int32)
                draft_toks = np.zeros((n, k_max), np.int32)
                cur = toks
                m.counter("spec.steps").inc()
        if k_max == 0:
            # every live row is one token from its budget: speculation
            # degenerates to plain decode, so run exactly that
            self._decode_once()
            return
        with self._phase("spec.draft_s", "spec_draft"):
            for j in range(k_max):
                with self._phase("step.build_batch_s", "build_batch"):
                    q1 = (k_rows > j).astype(np.int32)
                    m.counter("step.model_dispatches").inc()
                    self._record_cost(self.cost_model.draft(
                        n, keep_slices=self.config.draft_slices))
                    batch = {"tokens": jnp.asarray(cur)[:, None]}
                    args = (jnp.asarray(idxs + j), jnp.asarray(q1), zeros,
                            tables)
                with self._phase("spec.draft_dispatch_s",
                                 "draft_dispatch") as span:
                    self._dispatch_counts(span, idxs + j, q1, n, q1)
                    logits, tree = self._draft(self.params, batch,
                                               self.cache.tree, *args)
                    self.cache.tree = tree
                with self._phase("step.sample_host_s", "sample_host"):
                    d = np.asarray(sample_step(logits, keys_dev,
                                               jnp.asarray(steps + j),
                                               jnp.asarray(temps)))
                    draft_toks[:, j] = d
                    cur = d
        s_v = k_max + 1
        with self._phase("step.build_batch_s", "build_batch"):
            btoks = np.zeros((n, s_v), np.int32)
            btoks[:, 0] = toks
            btoks[:, 1:] = draft_toks
            q_lens = np.zeros(n, np.int32)
            for s in decoding:
                q_lens[s] = k_rows[s] + 1
            m.counter("step.model_dispatches").inc()
            self._record_cost(self.cost_model.verify(n, s_v))
            batch = {"tokens": jnp.asarray(btoks)}
            args = (jnp.asarray(idxs), jnp.asarray(q_lens), tables)
        with self._phase("spec.verify_s", "spec_verify") as span:
            self._dispatch_counts(span, idxs, q_lens, n, q_lens)
            logits, tree = self._verify(self.params, batch, self.cache.tree,
                                        *args)
            self.cache.tree = tree
        if m.enabled:
            with self._phase("step.device_sync_s", "device_sync"):
                jax.block_until_ready(logits)
        with self._phase("step.sample_host_s", "sample_host"):
            # one flattened sample over all (row, position) pairs: entry
            # (r, j) draws with (keys[r], steps[r] + j) — exactly the
            # (key, step) plain decode would use for that token index
            flat_keys = jnp.stack([k for k in keys for _ in range(s_v)])
            flat_steps = (steps[:, None]
                          + np.arange(s_v, dtype=np.int32)[None, :])
            targets = np.asarray(sample_step(
                logits.reshape(n * s_v, -1), flat_keys,
                jnp.asarray(flat_steps.reshape(-1)),
                jnp.asarray(np.repeat(temps, s_v)))).reshape(n, s_v)
        with self._phase("step.record_s", "record"):
            accepted: Dict[int, np.ndarray] = {}
            for s in decoding:
                k_r = int(k_rows[s])
                a = 0
                while a < k_r and draft_toks[s, a] == targets[s, a]:
                    a += 1
                accepted[s] = targets[s, :a + 1]
            m.counter("spec.proposed").inc(int(k_rows.sum()))
            m.counter("spec.accepted").inc(
                sum(len(v) - 1 for v in accepted.values()))
            m.counter("spec.tokens").inc(
                sum(len(v) for v in accepted.values()))
            self.scheduler.record_spec(accepted)
            for slot, rid, step in live:
                got = len(accepted[slot])
                self.tracer.event(tr.SPEC_ACCEPT, rid, slot=slot,
                                  proposed=int(k_rows[slot]),
                                  accepted=got - 1, tokens=got)
                for j in range(got):
                    self.tracer.event(tr.DECODE_STEP, rid, slot=slot,
                                      step=step + j)


# ---------------------------------------------------------------------------
# Legacy static-batch engine (parity oracle)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DecodeEngine:
    """Static-batch decode: prefill + lockstep decode over a ring KV cache,
    fresh cache per ``generate`` call. Superseded by
    :class:`ContinuousBatchingEngine` on the serving path; retained as the
    reference implementation the parity tests pin the new engine against."""

    cfg: ArchConfig
    params: Any
    max_len: int = 256
    batch: int = 1
    packed: bool = False
    quant_cfg: Optional[QuantConfig] = None
    cache_dtype: Any = None  # None: the model's compute dtype

    def __post_init__(self):
        self.cfg, self.params, self.pack_stats = _maybe_pack(
            self.cfg, self.params, self.packed, self.quant_cfg)
        self.model = Model(self.cfg)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))

    def new_cache(self):
        tree = self.model.build_cache(
            self.batch, self.max_len,
            jnp.dtype(self.cache_dtype or self.cfg.compute_dtype))
        return pp.init_params(tree, jax.random.key(0))

    def generate(self, prompt: np.ndarray, n_tokens: int,
                 extra: Optional[Dict[str, Any]] = None,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """prompt: (B, S0) int32. Returns (B, S0 + n_tokens)."""
        b, s0 = prompt.shape
        assert b == self.batch and s0 + n_tokens <= self.max_len
        cache = self.new_cache()
        batch = {"tokens": jnp.asarray(prompt, jnp.int32)}
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        logits, cache = self._prefill(self.params, batch, cache)
        rng = jax.random.key(seed)
        keys = _row_keys(rng, b)
        temps = jnp.full((b,), temperature, jnp.float32)
        out = [jnp.asarray(prompt, jnp.int32)]
        tok = self._sample(logits, keys, temps, 0)
        for i in range(n_tokens):
            out.append(tok)
            if i == n_tokens - 1:
                break
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.int32(s0 + i))
            tok = self._sample(logits, keys, temps, i + 1)
        return np.asarray(jnp.concatenate(out, axis=1))

    @staticmethod
    def _sample(logits, keys, temps, i):
        steps = jnp.full((logits.shape[0],), i, jnp.int32)
        return sample_step(logits, keys, steps, temps)[:, None]
