"""Continuous-batching serve benchmark: tokens/sec, per-step latency, TTFT.

Workloads model the traffic shapes a serving fleet actually sees:

  uniform        every request arrives up front with the same prompt length
                 (the static engine's best case — measures pure decode rate)
  mixed          prompt lengths spread 4-32 tokens, token budgets spread too,
                 arrivals staggered so slots are recycled mid-flight (the
                 case that requires continuous batching)
  shared_prefix  N requests over K distinct system prompts (each request =
                 one of K long shared prefixes + a short unique tail) —
                 the shape the radix prefix cache exists for; the report
                 adds hit rate and prefill tokens avoided
  long_prompt    a few very long prompts land while short requests decode —
                 the head-of-line-blocking shape chunked prefill exists
                 for; run twice (chunked + unchunked) and report the p95
                 per-step latency each way plus the speedup
  decode_heavy   many slots decoding against long committed contexts with
                 almost no prefill — the shape where the reference decode
                 path's per-step gathered K/V copy dominates; run twice
                 (fused paged kernel + gather reference) and report p50/p95
                 step latency each way plus the per-step gathered bytes
                 each path materializes
  mixed_load     chunked long prompts landing while a deep decode
                 population keeps generating — every chunk-servicing step
                 pays prefill AND decode; run twice (fused mixed step +
                 separate chunk-then-decode) and report p50/p95 step
                 latency each way plus model dispatches per pass (the
                 fused step's one-launch win)
  spec_decode    deep decode budgets over short prompts — the shape
                 self-speculative decode exists for; run twice (spec on
                 with a truncated bit-slice draft + plain decode on the
                 same traffic, both packed) and report the accept rate,
                 tokens per spec step, dispatch counts, and the
                 PER-TOKEN p95 step-latency speedup (a spec step emits
                 several tokens, so raw per-step latency is the wrong
                 unit; on CPU the k+1 launches per step usually cost
                 more wall time than they save — the accept rate and
                 dispatch accounting are the signal, the speedup gate is
                 a floor against collapse, not a win claim)

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--packed] \
          [--arch smollm-135m --n-slots 4 --requests 12] \
          [--no-prefix-cache] [--block-size 8] [--prefill-chunk 32] \
          [--json-out BENCH_serve.json] \
          [--check-baseline benchmarks/baseline.json] [--update-baseline]

Prints one JSON line per (workload, engine-config) with wall seconds,
generated tokens/sec, p50/p95 per-step wall time, and time-to-first-token
percentiles (plus prefix_stats fields when the cache is on).

Per-step percentiles come from the engine's own ``step.total_s`` phase
histogram and TTFT / TPOT from the request-lifecycle trace
(``engine.tracer.summary()``) — the bench no longer keeps hand-rolled
``perf_counter`` bookkeeping, so its numbers are definitionally the same
ones ``engine.metrics()`` reports in production.

``--json-out`` additionally writes one JSON object per workload (a dict
keyed by workload name) — the CI perf trajectory artifact. With
``--check-baseline`` the run exits non-zero if tokens/sec or p95 step
latency regresses more than ``--baseline-tolerance`` (default 25%) vs the
committed baseline; ``--update-baseline`` rewrites that baseline from the
current run (gated fields with headroom, plus per-phase p95s and cost
counters for ``check_bench.py --baseline`` regression *attribution*).
``--artifacts-dir DIR`` exports, per workload variant, the last measured
pass's trace (``trace_<tag>.jsonl``), Chrome trace-event JSON
(``chrome_trace_<tag>.json`` — load in Perfetto), and full
``engine.metrics()`` snapshot (``metrics_<tag>.json``) — the CI bench job
uploads these, and ``check_bench.py --require-metrics DIR`` validates
them (including the cost counters and the Chrome trace schema).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

import repro.configs as C
from repro.core.swis import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pp
from repro.models.model import Model
from repro.serve import ContinuousBatchingEngine, EngineConfig, SamplingParams

MAX_LEN = 64
LONG_MAX_LEN = 512
LONG_PREFILL_CHUNK = 32
LONG_PROMPT_LEN = 14 * LONG_PREFILL_CHUNK  # 448 tokens, 14 chunks
HEAVY_MAX_LEN = 192
HEAVY_PREFIX_LEN = 120  # 15 blocks of committed context per request
HEAVY_N_SLOTS = 8
MIXED_MAX_LEN = 160
MIXED_PREFILL_CHUNK = 16
MIXED_PROMPT_LEN = 6 * MIXED_PREFILL_CHUNK  # 96 tokens, 6 chunks
MIXED_N_SLOTS = 6


def _requests_uniform(rng, cfg, n):
    return [(rng.integers(0, cfg.vocab, (8,)).astype(np.int32), 16, 0)
            for _ in range(n)]


def _requests_mixed(rng, cfg, n):
    out = []
    for i in range(n):
        s0 = int(rng.integers(4, 33))
        n_tok = int(rng.integers(8, MAX_LEN - s0 + 1))
        arrive = int(rng.integers(0, 12)) if i >= n // 3 else 0
        out.append((rng.integers(0, cfg.vocab, (s0,)).astype(np.int32),
                    n_tok, arrive))
    return out


def _requests_shared_prefix(rng, cfg, n, n_sys=3, sys_len=24):
    sys_prompts = [rng.integers(0, cfg.vocab, (sys_len,)).astype(np.int32)
                   for _ in range(n_sys)]
    out = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab,
                            (int(rng.integers(3, 9)),)).astype(np.int32)
        prompt = np.concatenate([sys_prompts[i % n_sys], tail])
        arrive = int(rng.integers(0, 10)) if i >= n_sys else 0
        out.append((prompt, int(rng.integers(8, 17)), arrive))
    return out


def _requests_long_prompt(rng, cfg, n):
    """Prompts of 14x the chunk size arrive while short requests decode:
    unchunked, each long prefill stalls every decoding slot for one
    monolithic step; chunked, the same work lands 32 tokens at a time."""
    n_long = max(1, min(4, n // 2))
    out = []
    for i in range(n_long):
        prompt = rng.integers(0, cfg.vocab,
                              (LONG_PROMPT_LEN,)).astype(np.int32)
        out.append((prompt, 12, i * 4))
    for i in range(max(0, n - n_long)):
        prompt = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
        out.append((prompt, 12, i * 2))
    return out


def _requests_decode_heavy(rng, cfg, n):
    """Every slot decodes a long tail against a long committed context:
    one shared long prefix (cached after the first admission) + a few
    unique tokens, then a deep decode. Prefill is a sliver of the work;
    the steady state is all slots deep in paged decode — the shape where
    the gather path re-materializes the whole arena view every step."""
    prefix = rng.integers(0, cfg.vocab,
                          (HEAVY_PREFIX_LEN,)).astype(np.int32)
    out = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab,
                            (int(rng.integers(2, 6)),)).astype(np.int32)
        out.append((np.concatenate([prefix, tail]), 48, 0))
    return out


def _requests_spec_decode(rng, cfg, n):
    """Short prompts, deep decode budgets, a couple of late arrivals:
    almost every step is a pure-decode step, which is exactly where the
    speculative draft/verify rounds replace plain one-token steps."""
    out = []
    for i in range(n):
        prompt = rng.integers(0, cfg.vocab,
                              (int(rng.integers(6, 14)),)).astype(np.int32)
        arrive = int(rng.integers(0, 8)) if i >= n - n // 4 else 0
        out.append((prompt, 32, arrive))
    return out


def _requests_mixed_load(rng, cfg, n):
    """Deep decoders occupy most slots from step 0 while chunked long
    prompts keep arriving: every chunk-servicing step pays one chunk of
    prefill AND a full decode batch — separate, that is two sequenced
    launches per step; fused, one mixed dispatch."""
    n_long = max(1, n // 3)
    out = []
    for i in range(max(0, n - n_long)):
        prompt = rng.integers(0, cfg.vocab, (6,)).astype(np.int32)
        out.append((prompt, 40, 0))
    for i in range(n_long):
        prompt = rng.integers(0, cfg.vocab,
                              (MIXED_PROMPT_LEN,)).astype(np.int32)
        out.append((prompt, 8, 2 + i * 3))
    return out


WORKLOADS = {"uniform": _requests_uniform, "mixed": _requests_mixed,
             "shared_prefix": _requests_shared_prefix,
             "long_prompt": _requests_long_prompt,
             "decode_heavy": _requests_decode_heavy,
             "mixed_load": _requests_mixed_load,
             "spec_decode": _requests_spec_decode}
WORKLOAD_MAX_LEN = {"long_prompt": LONG_MAX_LEN,
                    "decode_heavy": HEAVY_MAX_LEN,
                    "mixed_load": MIXED_MAX_LEN}
WORKLOAD_N_SLOTS = {"decode_heavy": HEAVY_N_SLOTS,
                    "mixed_load": MIXED_N_SLOTS}


def _decode_gathered_bytes(eng, cfg):
    """Peak bytes of gathered K/V one decode step materializes, summed over
    layers. The reference path rebuilds each slot's contiguous arena view
    (n_blocks_per_slot * block_size positions); the fused XLA fallback
    touches one block_size slab per scan step; the Pallas kernel indexes
    the arena in place and gathers nothing."""
    kv = 2 * eng.n_slots * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
    itemsize = np.dtype(eng.cache.dtype).itemsize
    if eng.paged_impl is None:
        return kv * eng.cache.eff_len * itemsize
    if eng.paged_impl == "xla":
        return kv * eng.cache.block_size * itemsize
    return 0  # pallas: in-kernel indirection, no gathered copy


def run_workload(name, cfg, params, *, n_slots, requests, packed, qcfg,
                 prefix_cache=True, block_size=8, prefill_chunk=None,
                 max_len=None, passes=3, use_paged_kernel=False,
                 fused_step=False, spec_decode=False, spec_k=3,
                 draft_slices=None, artifacts_dir=None, artifact_tag=None):
    max_len = max_len or WORKLOAD_MAX_LEN.get(name, MAX_LEN)
    n_slots = WORKLOAD_N_SLOTS.get(name, n_slots)
    if not prefix_cache:
        prefill_chunk = None  # chunking needs block mode; degrade, not crash
    rng = np.random.default_rng(0)
    reqs = WORKLOADS[name](rng, cfg, requests)
    total_tokens = sum(n for _, n, _ in reqs)

    eng = ContinuousBatchingEngine(cfg, params, config=EngineConfig(
        max_len=max_len, n_slots=n_slots, packed=packed, quant_cfg=qcfg,
        prefix_cache=prefix_cache, block_size=block_size,
        prefill_chunk=prefill_chunk, use_paged_kernel=use_paged_kernel,
        fused_step=fused_step, spec_decode=spec_decode, spec_k=spec_k,
        draft_slices=draft_slices))

    def one_pass():
        """Drive the traffic; all timing observability comes from the
        engine's metrics/trace layer, not bench-side bookkeeping."""
        pending = sorted(range(len(reqs)), key=lambda i: reqs[i][2])
        t0 = time.perf_counter()
        step = 0
        done = 0
        while done < len(reqs):
            while pending and reqs[pending[0]][2] <= step:
                i = pending.pop(0)
                eng.submit(reqs[i][0],
                           SamplingParams(max_tokens=reqs[i][1]))
            done += len(eng.step())
            step += 1
        return time.perf_counter() - t0

    def pass_report(dt):
        hist = eng.metrics_registry.histogram("step.total_s")
        hs = hist.summary()
        ts = eng.tracer.summary()
        snap = eng.metrics_registry.snapshot()
        # per-phase p95s + cost-model counters ride the report so
        # check_bench --baseline can attribute a regression to the phase
        # / cost counter that moved (docs/serving.md "Observability")
        phases = {k: round(s["p95"], 6)
                  for k, s in snap["histograms"].items()
                  if k.endswith("_s") and s["count"]}
        cost = {k: v for k, v in snap["counters"].items()
                if k.startswith("cost.") and "." not in k[5:]}
        return {"wall_s": round(dt, 3),
                "phases": phases, "cost": cost,
                "tok_per_s": round(total_tokens / dt, 1),
                "steps": hs["count"],
                "model_dispatches":
                    eng.metrics_registry.counter(
                        "step.model_dispatches").value,
                "p50_step_s": round(hs["p50"], 5),
                "p95_step_s": round(hs["p95"], 5),
                "max_step_s": round(hs["max"], 5),
                "ttft_p50_s": round(ts["ttft_s"]["p50"], 5),
                "ttft_p95_s": round(ts["ttft_s"]["p95"], 5),
                "tpot_p50_s": round(ts["tpot_s"]["p50"], 6),
                "queue_wait_p95_s": round(ts["queue_wait_s"]["p95"], 5)}

    # warmup pass compiles every prefill/decode shape; reset() keeps the
    # jit caches (and clears metrics + trace), so the measured passes are
    # steady-state serving with clean counters. Each metric takes its
    # best pass — host scheduling noise (GC, interrupts) only ever
    # worsens a pass, while a real regression shifts them all.
    one_pass()
    best = None
    for _ in range(passes):
        eng.reset()
        cur = pass_report(one_pass())
        if best is None:
            best = cur
        else:
            best["tok_per_s"] = max(best["tok_per_s"], cur["tok_per_s"])
            for k in ("wall_s", "p50_step_s", "p95_step_s", "max_step_s",
                      "ttft_p50_s", "ttft_p95_s", "tpot_p50_s",
                      "queue_wait_p95_s"):
                best[k] = min(best[k], cur[k])
            # best-of per phase (noise only worsens a pass); cost counters
            # are deterministic given the traffic, keep the last pass's
            best["phases"] = {
                k: min(best["phases"].get(k, v), v)
                for k, v in cur["phases"].items()}
            best["cost"] = cur["cost"]
    if artifacts_dir:
        # last measured pass's lifecycle trace + Chrome trace + unified
        # metrics snapshot
        tag = artifact_tag or name
        os.makedirs(artifacts_dir, exist_ok=True)
        eng.tracer.export_jsonl(
            os.path.join(artifacts_dir, f"trace_{tag}.jsonl"))
        eng.tracer.export_chrome_trace(
            os.path.join(artifacts_dir, f"chrome_trace_{tag}.json"))
        with open(os.path.join(artifacts_dir,
                               f"metrics_{tag}.json"), "w") as f:
            json.dump(eng.metrics(), f, indent=2, sort_keys=True)
            f.write("\n")
    rep = {"workload": name, "engine": "continuous", "packed": packed,
           "prefix_cache": eng.prefix_cache is not None,
           "prefill_chunk": eng.prefill_chunk,
           "paged_impl": eng.paged_impl,
           "fused_step": eng.fused_step,
           "spec_decode": eng.spec_decode,
           "requests": len(reqs), "n_slots": n_slots,
           "gen_tokens": total_tokens, **best}
    if spec_decode:
        # accept accounting from the last measured pass — the traffic is
        # deterministic, so the accept pattern is identical across passes
        c = eng.metrics_registry.snapshot()["counters"]
        rep["spec_k"] = spec_k
        rep["draft_slices"] = draft_slices
        rep["spec_steps"] = c.get("spec.steps", 0)
        rep["spec_proposed"] = c.get("spec.proposed", 0)
        rep["spec_accepted"] = c.get("spec.accepted", 0)
        rep["accept_rate"] = round(
            c.get("spec.accepted", 0) / max(c.get("spec.proposed", 0), 1), 3)
        rep["spec_tokens_per_step"] = round(
            c.get("spec.tokens", 0) / max(c.get("spec.steps", 0), 1), 3)
    if eng.prefix_cache is not None:
        rep["materializes_gathered_kv"] = eng.paged_impl is None
        rep["decode_gathered_bytes_per_step"] = _decode_gathered_bytes(
            eng, cfg)
    stats = eng.prefix_stats()
    prompt_tokens = sum(len(p) for p, _, _ in reqs)
    rep["prompt_tokens"] = prompt_tokens
    rep["prefill_tokens"] = stats["prefill_tokens"]
    if stats["enabled"]:
        rep["hit_rate"] = round(stats["hit_rate"], 3)
        rep["prefill_tokens_saved"] = stats["saved_tokens"]
        rep["evictions"] = stats["evictions"]
        rep["prefill_chunk_steps"] = stats["prefill_chunk_steps"]
    return rep


GATED_FIELDS = (
    # (field, direction: +1 means higher-is-better, -1 lower-is-better)
    ("tok_per_s", +1),
    ("p95_step_s", -1),
)

# --update-baseline records measured * headroom, not the raw measurement:
# the committed baseline is the *floor of acceptable*, and the check
# tolerance sits on top of it. CPU smoke numbers are noisy at the
# millisecond scale and CI runners are slower than dev machines, and the
# gate's job is catching step-function regressions (an order-of-magnitude
# cliff), not re-measuring the trajectory — that is what the
# BENCH_serve.json artifact records.
BASELINE_HEADROOM = {"tok_per_s": 0.5, "p95_step_s": 2.0}


def check_baseline(results, baseline, tolerance):
    """Return a list of regression strings: any gated field more than
    ``tolerance`` (fraction) worse than the committed baseline."""
    regressions = []
    for name, base in baseline.items():
        cur = results.get(name)
        if cur is None:
            regressions.append(f"{name}: workload missing from this run")
            continue
        for field, sign in GATED_FIELDS:
            if field not in base:
                continue
            want, got = float(base[field]), float(cur[field])
            if sign > 0:
                ok = got >= want * (1.0 - tolerance)
            else:
                ok = got <= want * (1.0 + tolerance)
            if not ok:
                regressions.append(
                    f"{name}.{field}: {got} vs baseline {want} "
                    f"(tolerance {tolerance:.0%})")
    return regressions


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--n-shifts", type=int, default=4)
    ap.add_argument("--workloads", default="uniform,mixed,shared_prefix")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="contiguous per-slot KV (no block sharing)")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: at most this many prompt tokens "
                         "per step (long_prompt defaults to "
                         f"{LONG_PREFILL_CHUNK})")
    ap.add_argument("--json-out", default=None,
                    help="write one JSON object per workload to this file")
    ap.add_argument("--artifacts-dir", default=None, metavar="DIR",
                    help="export per-workload trace JSONL + engine.metrics()"
                         " snapshots (CI observability artifacts)")
    ap.add_argument("--check-baseline", default=None, metavar="PATH",
                    help="fail if tok/s or p95 step latency regresses vs "
                         "this baseline JSON")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite --check-baseline PATH from this run")
    ap.add_argument("--baseline-tolerance", type=float, default=0.25)
    ap.add_argument("--passes", type=int, default=3,
                    help="measured passes per workload (best-of)")
    args = ap.parse_args()
    if args.update_baseline and not args.check_baseline:
        ap.error("--update-baseline needs --check-baseline PATH to write")
    enable_compile_cache()

    cfg = C.get_smoke(args.arch).replace(compute_dtype="float32")
    params = pp.init_params(Model(cfg).build(), jax.random.key(0))
    qcfg = QuantConfig(method="swis", n_shifts=args.n_shifts, group_size=4)

    names = [n.strip() for n in args.workloads.split(",")]
    unknown = [n for n in names if n not in WORKLOADS]
    if unknown:
        ap.error(f"unknown workload(s) {unknown}; "
                 f"choose from {sorted(WORKLOADS)}")
    common = dict(n_slots=args.n_slots, requests=args.requests,
                  packed=args.packed, qcfg=qcfg,
                  prefix_cache=not args.no_prefix_cache,
                  block_size=args.block_size, passes=args.passes,
                  artifacts_dir=args.artifacts_dir)
    results = {}
    for name in names:
        if name == "long_prompt" and not args.no_prefix_cache:
            chunk = args.prefill_chunk or LONG_PREFILL_CHUNK
            rep = run_workload(name, cfg, params, prefill_chunk=chunk,
                               **common)
            rep_un = run_workload(name, cfg, params, prefill_chunk=None,
                                  artifact_tag=f"{name}_unchunked",
                                  **common)
            rep["p95_step_s_unchunked"] = rep_un["p95_step_s"]
            rep["p95_step_speedup"] = round(
                rep_un["p95_step_s"] / rep["p95_step_s"], 2)
            print(json.dumps(rep_un))
        elif name == "decode_heavy" and not args.no_prefix_cache:
            # fused paged decode vs the gather reference on the same
            # traffic: the fused report is the gated one, with the gather
            # pass's latency and gathered-copy size alongside
            rep = run_workload(name, cfg, params, use_paged_kernel=True,
                               prefill_chunk=args.prefill_chunk, **common)
            rep_g = run_workload(name, cfg, params, use_paged_kernel=False,
                                 prefill_chunk=args.prefill_chunk,
                                 artifact_tag=f"{name}_gather", **common)
            rep["p50_step_s_gather"] = rep_g["p50_step_s"]
            rep["p95_step_s_gather"] = rep_g["p95_step_s"]
            rep["decode_gathered_bytes_per_step_gather"] = \
                rep_g["decode_gathered_bytes_per_step"]
            rep["paged_p95_speedup"] = round(
                rep_g["p95_step_s"] / rep["p95_step_s"], 2)
            print(json.dumps(rep_g))
        elif name == "mixed_load" and not args.no_prefix_cache:
            # fused mixed step vs the separate chunk-then-decode path on
            # the same traffic: the fused report is the gated one, with
            # the separate pass's latency and dispatch count alongside —
            # the dispatch delta is the fused step's structural win
            chunk = args.prefill_chunk or MIXED_PREFILL_CHUNK
            rep = run_workload(name, cfg, params, prefill_chunk=chunk,
                               fused_step=True, **common)
            rep_s = run_workload(name, cfg, params, prefill_chunk=chunk,
                                 fused_step=False,
                                 artifact_tag=f"{name}_separate", **common)
            rep["p50_step_s_separate"] = rep_s["p50_step_s"]
            rep["p95_step_s_separate"] = rep_s["p95_step_s"]
            rep["model_dispatches_separate"] = rep_s["model_dispatches"]
            rep["fused_p95_speedup"] = round(
                rep_s["p95_step_s"] / rep["p95_step_s"], 2)
            print(json.dumps(rep_s))
        elif name == "spec_decode" and not args.no_prefix_cache:
            # self-speculative decode vs plain decode on the same traffic,
            # both serving packed weights (the truncated-slice draft only
            # exists on the packed kernel path). The spec report is the
            # gated one; per-step latency is normalized per TOKEN on BOTH
            # sides (a plain step emits up to n_slots tokens, a spec step
            # several per row), so the speedup compares token cost, not
            # step cost
            spec_common = {**common, "packed": True}
            draft = max(1, args.n_shifts - 1)
            rep = run_workload(name, cfg, params, spec_decode=True,
                               draft_slices=draft,
                               prefill_chunk=args.prefill_chunk,
                               **spec_common)
            rep_p = run_workload(name, cfg, params,
                                 prefill_chunk=args.prefill_chunk,
                                 artifact_tag=f"{name}_plain", **spec_common)
            rep["p95_step_s_plain"] = rep_p["p95_step_s"]
            rep["model_dispatches_plain"] = rep_p["model_dispatches"]
            tok_per_step = rep["gen_tokens"] / max(rep["steps"], 1)
            tok_per_step_p = rep_p["gen_tokens"] / max(rep_p["steps"], 1)
            per_token = rep["p95_step_s"] / max(tok_per_step, 1e-9)
            per_token_p = rep_p["p95_step_s"] / max(tok_per_step_p, 1e-9)
            rep["spec_p95_speedup"] = round(per_token_p / per_token, 2)
            print(json.dumps(rep_p))
        else:
            rep = run_workload(name, cfg, params,
                               prefill_chunk=args.prefill_chunk, **common)
        print(json.dumps(rep))
        results[name] = rep

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.check_baseline:
        if args.update_baseline:
            # gated fields carry headroom; phase p95s get the same 2x
            # latency headroom; cost counters are recorded raw (they are
            # deterministic model outputs, not measurements — any drift
            # is a real cost-model/dispatch change worth naming)
            base = {}
            for name, rep in results.items():
                entry = {field: round(rep[field]
                                      * BASELINE_HEADROOM[field], 5)
                         for field, _ in GATED_FIELDS}
                entry["phases"] = {k: round(v * 2.0, 6)
                                   for k, v in rep.get("phases", {}).items()}
                entry["cost"] = rep.get("cost", {})
                base[name] = entry
            with open(args.check_baseline, "w") as f:
                json.dump(base, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"baseline updated: {args.check_baseline}", file=sys.stderr)
            return
        with open(args.check_baseline) as f:
            baseline = json.load(f)
        regressions = check_baseline(results, baseline,
                                     args.baseline_tolerance)
        if regressions:
            for r in regressions:
                print(f"PERF REGRESSION {r}", file=sys.stderr)
            sys.exit(1)
        print("baseline check passed", file=sys.stderr)


if __name__ == "__main__":
    main()
