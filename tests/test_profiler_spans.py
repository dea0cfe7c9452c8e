"""The program's own spans and scopes, as a profiler trace records them.

* Every engine phase is a ``jax.profiler`` span ``serve.<phase>`` on the
  profiler's host plane: inside each ``serve.step`` the child phases
  cover all but a small remainder of the step, on the fused, separate,
  unchunked and speculative paths.
* Each dispatch span carries its launch's counts (``decode_rows``,
  ``chunk_rows``, ``chunk_tokens``, ``kv_tokens``), which agree with the
  launch's own arguments.
* The step programs' ops carry the model's name scopes (``gemm``,
  ``attention``, ``kv_commit``, ``norm``, ``embed``) in their metadata.

All on the CPU at smoke size; what the scopes and spans say on a TPU is
read by ``bench/`` from a recorded chip trace.
"""
import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.configs as C
from repro.models import params as pp
from repro.models.model import Model
from repro.serve import (ContinuousBatchingEngine, EngineConfig,
                         SamplingParams)

MAX_LEN = 64
BS = 8
DISPATCH = ("serve.mixed_dispatch", "serve.decode_dispatch",
            "serve.prefill_dispatch", "serve.draft_dispatch",
            "serve.spec_verify")
COUNTS = ("decode_rows", "chunk_rows", "chunk_tokens", "kv_tokens")


@functools.cache
def _setup():
    cfg = C.get_smoke("smollm-135m").replace(compute_dtype="float32")
    params = pp.init_params(Model(cfg).build(), jax.random.key(0))
    return cfg, params


PATHS = {
    "fused": dict(prefill_chunk=BS, fused_step=True),
    "separate": dict(prefill_chunk=BS, fused_step=False),
    "unchunked": dict(),
    "speculative": dict(spec_decode=True, spec_k=2),
}


def _engine(path):
    cfg, params = _setup()
    return ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        max_len=MAX_LEN, n_slots=2, block_size=BS, **PATHS[path]))


def _serve(eng, rng, n_req=3, prompt=19, tokens=5):
    """Staggered requests: the second and third arrive while the first
    decodes, so chunk and decode rows share launches."""
    cfg, _ = _setup()
    eng.submit(rng.integers(0, cfg.vocab, prompt).astype(np.int32),
               SamplingParams(max_tokens=tokens))
    eng.step()
    for _ in range(n_req - 1):
        eng.submit(rng.integers(0, cfg.vocab, prompt).astype(np.int32),
                   SamplingParams(max_tokens=tokens))
    eng.drain()


def _traced(eng, drive, tmp_path):
    """``drive()`` under a profiler trace; the host events named
    ``serve.*`` as (name, start_ns, end_ns, stats)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _covered(lo, hi, spans):
    """Nanoseconds of [lo, hi) inside the union of ``spans``."""
    total, end = 0, lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in spans
                       if e > lo and s < hi):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phases_cover_each_step(path, rng, tmp_path):
    eng = _engine(path)
    _serve(eng, rng)  # compile every shape outside the trace
    eng.reset()
    events = _traced(eng, lambda: _serve(eng, rng), tmp_path)
    steps = [(s, e) for n, s, e, _ in events if n == "serve.step"]
    assert len(steps) == eng.metrics_registry.counter("step.count").value
    children = [(s, e) for n, s, e, _ in events if n != "serve.step"]
    # every phase lies inside a step
    assert all(any(a <= s and e <= b for a, b in steps)
               for s, e in children)
    total = sum(e - s for s, e in steps)
    covered = sum(_covered(s, e, children) for s, e in steps)
    assert covered / total > 0.9, (path, covered / total)
    names = {n for n, *_ in events}
    assert {"serve.build_batch", "serve.sample_host",
            "serve.record"} <= names
    assert names & set(DISPATCH)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_dispatch_spans_carry_the_launch_counts(path, rng, tmp_path):
    """Each dispatch span's counts equal what its launch was given: rows
    with tokens, tokens of the chunk rows, and positions attended."""
    eng = _engine(path)
    launches = []

    def record(kind, fn, n_decode):
        def call(*args):
            if kind == "decode":  # (params, token, cache, index, tables)
                start = np.asarray(args[3])
                q = (np.asarray(args[4])[:, 0] != 0).astype(np.int32)
            elif kind == "prefill":  # (params, batch, tree, [start,] last)
                q = np.asarray(args[-1]) + 1
                start = np.full(q.shape, int(args[3]) if len(args) == 5
                                else 0)
            else:  # (params, batch, cache, start, q_lens, ...)
                start, q = np.asarray(args[3]), np.asarray(args[4])
            live = q > 0
            launches.append({
                "decode_rows": int(live[:n_decode].sum()),
                "chunk_rows": int(live[n_decode:].sum()),
                "chunk_tokens": int(q[n_decode:].sum()),
                "kv_tokens": int((start + q)[live].sum())})
            return fn(*args)
        return call

    n = eng.n_slots
    for attr, kind, n_decode in (
            ("_mixed", "mixed", n), ("_decode", "decode", n),
            ("_draft", "draft", n), ("_verify", "verify", n),
            ("_prefill_flat", "prefill", 0), ("_prefill_sfx", "prefill", 0)):
        if hasattr(eng, attr):
            setattr(eng, attr, record(kind, getattr(eng, attr), n_decode))
    events = _traced(eng, lambda: _serve(eng, rng), tmp_path)
    spans = [{k: int(stats[k]) for k in COUNTS}
             for name, _, _, stats in events if name in DISPATCH]
    assert spans == launches
    assert sum(s["decode_rows"] for s in spans) > 0
    assert sum(s["chunk_tokens"] for s in spans) > 0


def test_new_phases_feed_their_histograms(rng):
    """With no profiler running each phase still feeds its histogram."""
    eng = _engine("fused")
    _serve(eng, rng)
    hist = eng.metrics()["engine"]["phases"]
    for name in ("step.total_s", "step.build_batch_s",
                 "step.mixed_dispatch_s", "step.record_s",
                 "step.group_setup_s", "step.chunk_advance_s"):
        assert hist[name]["count"] > 0, name


# the serve-bench workloads whose phases benchmarks/baseline.json gates,
# with the engine path each runs
GATED = {"mixed_load": "fused", "long_prompt": "separate",
         "shared_prefix": "unchunked", "decode_heavy": "unchunked",
         "spec_decode": "speculative"}


@pytest.mark.parametrize("workload", sorted(GATED))
def test_gated_phases_stay_recorded(workload, rng):
    """Every phase the baseline gate lists for a workload is still
    recorded on that workload's path (a phase that stops being recorded
    fails the gate's attribution)."""
    base = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                        "baseline.json")
    with open(base) as f:
        want = json.load(f)[workload]["phases"]
    eng = _engine(GATED[workload])
    _serve(eng, rng)
    hist = eng.metrics()["engine"]["phases"]
    for name in want:
        assert hist.get(name, {}).get("count", 0) > 0, name


def _lowered(packed):
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        max_len=MAX_LEN, n_slots=2, block_size=BS, prefill_chunk=BS,
        use_paged_kernel=True, paged_impl="xla", packed=packed))
    b = eng.n_slots + 1
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    mixed = eng._mixed.lower(
        eng.params, {"tokens": i32((b, BS))}, eng.cache.tree, i32(b),
        i32(b), i32(b), i32((b, eng.cache.blocks_per_slot)))
    decode = eng._decode.lower(
        eng.params, i32((2, 1)), eng.cache.tree, i32(2),
        i32((2, eng.cache.blocks_per_slot)))
    return [m.as_text(debug_info=True) for m in (mixed, decode)]


@pytest.mark.parametrize("packed", [False, True], ids=["bf16", "packed"])
def test_step_programs_carry_the_model_scopes(packed):
    for text in _lowered(packed):
        for scope in ("gemm", "attention", "kv_commit", "norm", "embed"):
            assert f"/{scope}/" in text, scope
        # each scope names its own work: no op is in two of them
        for outer in ("attention", "kv_commit", "norm", "embed"):
            assert f"/{outer}/gemm/" not in text, outer


def test_dispatch_spans_count_the_kernels_kv_tiles(rng, tmp_path):
    """With the Pallas paged kernel, each fused and decode span's
    ``kv_tiles`` is the (row, K/V tile) pairs the kernel visits for the
    launch's start and query counts: a row walks the tiles up to the one
    holding its last query, and a fused step's idle rows walk none. The
    decode step gives the kernel no query counts, so each of its rows has
    one. Blocks of 64 tokens make a tile 4 blocks (256 keys), so prompts
    of 280 tokens end in a table's second tile."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        max_len=320, n_slots=2, block_size=64, prefill_chunk=64,
        fused_step=True, use_paged_kernel=True,
        paged_impl="pallas_interpret"))
    assert eng._kv_tile == (256, 2)
    launches = []

    def record(fn, decode):
        def call(*args):
            start = np.asarray(args[3])
            q = np.ones_like(start) if decode else np.asarray(args[4])
            tiles = np.minimum(-(-(start + q) // 256), 2)
            launches.append(int(np.where(q > 0, tiles, 0).sum()))
            return fn(*args)
        return call

    eng._mixed = record(eng._mixed, decode=False)
    eng._decode = record(eng._decode, decode=True)
    events = _traced(eng, lambda: _serve(eng, rng, n_req=2, prompt=280,
                                         tokens=3), tmp_path)
    spans = [int(stats["kv_tiles"]) for name, _, _, stats in events
             if name in ("serve.mixed_dispatch", "serve.decode_dispatch")]
    assert spans == launches
    assert max(spans) >= 3  # a second tile was walked
    mixed = [int(stats["kv_tiles"]) for name, _, _, stats in events
             if name == "serve.mixed_dispatch"]
    assert min(mixed) == 1  # one chunk row alone: its idle rows walk none
