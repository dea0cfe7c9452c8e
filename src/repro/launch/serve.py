"""Serving launcher: continuous-batching decode with optional SWIS-packed
weights (see docs/serving.md for the engine architecture).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 8 --prompt-len 8 --tokens 24 --packed --n-shifts 4

``--engine static`` runs the legacy lockstep DecodeEngine instead (equal
prompt lengths only) — useful for A/B-ing the two hot paths.

The continuous engine emits a periodic observability line every
``--metrics-every`` steps (queue depth, slot states, pool occupancy,
p50/p95 step latency — all from ``engine.metrics()``), a full phase +
cost report at the end, and with ``--trace-out PATH`` exports the trace:
a ``.json`` path gets Chrome trace-event JSON (open in Perfetto —
nested step/phase spans + per-request tracks), anything else the raw
request-lifecycle JSONL (see docs/serving.md "Observability").
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import numpy as np

import repro.configs as C
from repro.core.swis import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pp
from repro.models.model import Model
from repro.serve import (ContinuousBatchingEngine, DecodeEngine,
                         EngineConfig, SamplingParams)
from repro.serve.metrics import format_report


def _metrics_line(step: int, m: dict) -> str:
    """One compact periodic report line from an ``engine.metrics()``
    snapshot."""
    sched = m["scheduler"]
    parts = [f"[step {step}]",
             f"queue={sched['queue_depth']}",
             f"active={sched['active_slots']}",
             f"prefilling={sched['prefilling_slots']}",
             f"finished={sched['finished']}"]
    if "block_pool" in m:
        parts.append(f"pool_occ={m['block_pool']['occupancy']:.2f}")
        parts.append(f"hit_rate={m['prefix_cache']['hit_rate']:.2f}")
    total = m["engine"]["phases"].get("step.total_s")
    if total and total["count"]:
        parts.append(f"p50_step={total['p50'] * 1e3:.2f}ms")
        parts.append(f"p95_step={total['p95'] * 1e3:.2f}ms")
    return " ".join(parts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(C.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests to serve")
    ap.add_argument("--n-slots", type=int, default=4,
                    help="concurrent decode slots (continuous engine)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: at most this many prompt tokens "
                         "per engine step (continuous engine, block mode)")
    ap.add_argument("--fused", action="store_true",
                    help="fused mixed step: the per-step prefill chunk and "
                         "the decode batch share ONE dispatch (requires "
                         "--prefill-chunk)")
    ap.add_argument("--spec", action="store_true",
                    help="self-speculative decode: draft --spec-k tokens "
                         "with the model truncated to --draft-slices SWIS "
                         "bit-planes, verify in one full-precision launch "
                         "(continuous engine; token-exact vs plain decode)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="max draft tokens per speculative round")
    ap.add_argument("--draft-slices", type=int, default=None,
                    help="bit-slices kept for the draft pass (requires "
                         "--packed; default: full precision)")
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--n-shifts", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir to serve")
    ap.add_argument("--metrics-every", type=int, default=25,
                    help="print a metrics line every N engine steps "
                         "(continuous engine; 0 disables)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the trace: *.json -> Chrome trace-event "
                         "JSON (load in Perfetto), else lifecycle JSONL")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    model = Model(cfg)
    if args.ckpt:
        from repro.checkpoint import CheckpointManager
        from repro.train.steps import init_state

        template = init_state(pp.abstract_params(model.build()))
        state, _ = CheckpointManager(args.ckpt).restore(template)
        params = state.params
    else:
        params = pp.init_params(model.build(), jax.random.key(0))

    qcfg = QuantConfig(method="swis", n_shifts=args.n_shifts,
                       group_size=args.group_size)
    max_len = args.prompt_len + args.tokens + 1
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab, (args.requests, args.prompt_len)).astype(np.int32)

    if args.engine == "static":
        eng = DecodeEngine(cfg, params, max_len=max_len, batch=args.requests,
                           packed=args.packed, quant_cfg=qcfg)
        t0 = time.perf_counter()
        out = eng.generate(prompts, args.tokens,
                           temperature=args.temperature)
        dt = time.perf_counter() - t0
        sample = out[0]
    else:
        eng = ContinuousBatchingEngine(
            cfg, params, config=EngineConfig(
                max_len=max_len, n_slots=args.n_slots, packed=args.packed,
                quant_cfg=qcfg, prefill_chunk=args.prefill_chunk,
                fused_step=args.fused, spec_decode=args.spec,
                spec_k=args.spec_k, draft_slices=args.draft_slices))
        sp = functools.partial(SamplingParams, max_tokens=args.tokens,
                               temperature=args.temperature)
        rids = [eng.submit(p, sp(seed=i)) for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        results = {}
        step = 0
        while eng.scheduler.pending():
            for f in eng.step():
                results[f.rid] = np.concatenate([f.prompt, f.tokens])
            step += 1
            if args.metrics_every and step % args.metrics_every == 0:
                print(_metrics_line(step, eng.metrics()), file=sys.stderr)
        dt = time.perf_counter() - t0
        sample = results[rids[0]]
        print(format_report(eng.metrics_registry.snapshot(),
                            title="serve metrics"), file=sys.stderr)
        if args.trace_out:
            if args.trace_out.endswith(".json"):
                n = eng.tracer.export_chrome_trace(args.trace_out)
                print(f"trace: {n} Chrome trace events -> "
                      f"{args.trace_out} (open at https://ui.perfetto.dev)",
                      file=sys.stderr)
            else:
                n = eng.tracer.export_jsonl(args.trace_out)
                print(f"trace: {n} events -> {args.trace_out}",
                      file=sys.stderr)

    report = {"arch": cfg.name, "engine": args.engine,
              "requests": args.requests, "n_slots": args.n_slots,
              "tokens": args.tokens, "wall_s": round(dt, 2),
              "tok_per_s": round(args.requests * args.tokens / dt, 1)}
    if eng.pack_stats:
        report["packed_weights"] = eng.pack_stats["n_packed"]
        report["compression"] = round(eng.pack_stats["compression"], 2)
    if args.engine != "static":
        stats = eng.prefix_stats()
        if stats.get("enabled"):
            report["prefix_hit_rate"] = round(stats["hit_rate"], 3)
            report["prefill_tokens_saved"] = stats["saved_tokens"]
        snap = eng.metrics_registry.snapshot()
        if "cost.hbm_bytes" in snap["counters"]:
            # cost-model totals: predicted traffic of the issued
            # dispatches, and the model-implied bandwidth over the run
            report["cost_hbm_mib"] = round(
                snap["counters"]["cost.hbm_bytes"] / 2**20, 2)
            report["cost_gflops"] = round(
                snap["counters"]["cost.flops"] / 1e9, 3)
            report["cost_hbm_bytes_per_s"] = round(
                snap["gauges"].get("cost.hbm_bytes_per_s", 0.0), 1)
        tsum = eng.tracer.summary()
        if tsum["ttft_s"]:
            report["ttft_p50_s"] = round(tsum["ttft_s"]["p50"], 5)
            report["ttft_p95_s"] = round(tsum["ttft_s"]["p95"], 5)
        if tsum["tpot_s"]:
            report["tpot_p50_s"] = round(tsum["tpot_s"]["p50"], 6)
        if args.spec:
            c = eng.metrics_registry.snapshot()["counters"]
            report["spec_proposed"] = c.get("spec.proposed", 0)
            report["spec_accepted"] = c.get("spec.accepted", 0)
            report["spec_accept_rate"] = round(
                c.get("spec.accepted", 0)
                / max(c.get("spec.proposed", 0), 1), 3)
    print(json.dumps(report, indent=1))
    print("sample:", sample.tolist())


if __name__ == "__main__":
    main()
