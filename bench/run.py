"""Chip benchmark of the SWIS serving stack: one cell of BENCHMARK.json.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from the seed on the device, warms every step
shape its traffic uses, serves the traffic for ``--seconds``, checks the
served tokens and the logits they were sampled from against the float32
reference, and prints one JSON object
as the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the first seconds of the window
with the profiler and reports its per-layer metrics. There is no CPU
fallback: without a TPU (or with fewer chips than the cell asks for) it
exits non-zero and prints no result.
"""
import time

T_START = time.time()  # set-up runs from here to the window's start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_SECONDS = 6.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Sums XLA compile time (a persistent-cache load counts as a compile)
    and counts the compiles the persistent cache served."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "from_cache": self.cache_hits - mark[2]}


class TracedPart:
    """The profiler, on for the first ``seconds`` of the window: host spans
    from the harness, the step programs' row layouts, and the device's
    events, all inside one ``bench.window`` span."""

    def __init__(self, rec, seconds: float):
        self.rec, self.seconds = rec, seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on, self.lo, self.hi, self._window = False, None, None, None

    def start(self) -> None:
        import jax

        from bench.harness.trace import capture_options

        jax.profiler.start_trace(self.dir, profiler_options=capture_options())
        self.rec.on = self.rec.annotate = True
        self.rec.annotate_sampling(True)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.on, self.lo = True, time.perf_counter()

    def span(self, name: str):
        import jax

        if self.on:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def tick(self, now: float) -> None:
        if self.on and now >= self.lo + self.seconds:
            self.stop(now)

    def stop(self, now: float) -> None:
        import jax

        if not self.on:
            return
        self.rec.on = self.rec.annotate = False
        self.rec.annotate_sampling(False)
        self._window.__exit__(None, None, None)
        self.on, self.hi = False, now
        jax.profiler.stop_trace()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def sample_for_check(win, check: dict, seed: int):
    """The longest finished request and others drawn from the seed."""
    import numpy as np

    done = [s for s in win.served if s.tokens is not None]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.prompt) + len(s.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 0x5EED])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    return [longest] + rest[:check["sample_seqs"] - 1]


def main(argv=None, *, root: str = ROOT, device=None, fault=None) -> int:
    """``device`` and ``fault`` are for tests on the CPU: a stand-in for
    the chip's description (skipping the look for one), and a function
    applied to the engine before the window to break the timed path."""
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    import jax

    if device is None:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        device = device_or_exit(cell.chips)
    device = dict(device)
    clock = CompileClock()

    from bench.harness import counts, driver, program, traffic
    from bench.harness import trace as tr
    from bench.harness.reference import Reference, widest_gap

    peak = counts.peaks(device["kind"])
    cfg, mix, deploy = cell.config, cell.mix, cell.deploy
    vocab = cfg["vocab_size"]
    t0, c0 = time.time(), clock.mark()
    engine, rec = program.build(cfg, deploy, args.seed)
    t1, c1 = time.time(), clock.mark()
    warm = program.warm_up(engine, deploy, mix, vocab, args.seed)
    t2 = time.time()
    log(f"set-up: imports {t0 - T_START:.3f} s; weights and engine "
        f"{t1 - t0:.3f} s ({clock.since(c0)}); warm-up {t2 - t1:.3f} s, "
        f"{warm['groups']} chunk groups ({clock.since(c1)})")
    requests = traffic.generate(mix, args.seed, args.seconds, vocab)
    if fault is not None:
        fault(engine)

    traced = TracedPart(rec, TRACE_SECONDS) if args.trace else None
    c_win = clock.mark()
    setup_s = time.time() - T_START
    if traced:
        traced.start()
    rec.keep_logits = True
    win = driver.run(engine, program.submit, requests, mix, args.seconds,
                     span=traced.span if traced else driver.no_span,
                     tick=traced.tick if traced else None)
    if traced:
        traced.stop(time.perf_counter())
    rec.keep_logits = False
    in_window = clock.since(c_win)
    e2e = driver.end_to_end(win)
    device["memory_peak_bytes"] = program.memory_peak_bytes()
    late = sorted(win.late_s)
    log(f"window {args.seconds} s: {e2e}; compiles in the window and the "
        f"drain: {in_window}; drained {win.drained}")
    log(f"readings with no bound: ttft_p50_ms {e2e['ttft_p50_ms']!r}, "
        f"ttft_p95_ms {e2e['ttft_p95_ms']!r} over {e2e['n_ttft']} requests; "
        f"itl_p50_ms {e2e['itl_p50_ms']!r}, itl_p99_ms {e2e['itl_p99_ms']!r}"
        f" over {e2e['n_gaps']} gaps")
    if late:
        log(f"generator lateness: p50 {1e3 * driver.percentile(late, 50):.3f}"
            f" ms, p95 {1e3 * driver.percentile(late, 95):.3f} ms, max "
            f"{1e3 * late[-1]:.3f} ms over {len(late)} submissions")

    result = {"correct": False, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": device}
    if args.trace:
        form = tr.extract(tr.find_xplane(traced.dir))
        shutil.rmtree(traced.dir, ignore_errors=True)
        red = tr.Reduced(form)
        run = driver.RunData(
            win=win, rows=rec.rows(), trace=red,
            shapes=counts.Shapes.from_config(cfg), peak=peak,
            events=[(e.kind, e.rid, e.ts) for e in engine.tracer.events()],
            traced=(traced.lo, traced.hi), cell=cell)
        for m in cell.per_layer:
            value = spec.reader(m["name"], root)(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
        log(f"trace: {len(form['device'])} device events, "
            f"{len(form['host'])} host spans, {len(rec.calls)} launches")
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    # the check: served tokens and their logits against the reference,
    # once the program's state is gone from the device
    check = cell.mix["check"]
    sample = sample_for_check(win, check, args.seed)
    seqs = [(s.prompt, s.tokens) for s in sample]
    served_logits = [rec.served_logits(s.rid, len(s.tokens))
                     for s in sample]
    del engine, rec
    gc.collect()
    t3 = time.time()
    limits = cfg["limits"]
    if seqs:
        gap = widest_gap(Reference(cfg, args.seed), seqs,
                         pad_len=deploy["max_len"],
                         pad_seqs=check["sample_seqs"],
                         logits=served_logits)
    else:
        gap = {"widest_gap": float("inf"), "tokens": 0, "median_gap": None,
               "logit_err": float("inf"), "median_logit_err": None}
    log(f"reference: {time.time() - t3:.3f} s over {len(seqs)} requests, "
        f"median gap {gap['median_gap']}, median logit error "
        f"{gap['median_logit_err']}")
    checks = {
        "widest_gap": {"value": gap["widest_gap"],
                       "limit": limits["widest_gap"]},
        "logit_err": {"value": gap["logit_err"],
                      "limit": limits["logit_err"]},
        "failed_requests": {"value": e2e["failed"], "limit": 0},
        "tokens_compared": {"value": gap["tokens"],
                            "limit": check["min_tokens"]},
    }
    result["correct"] = (gap["widest_gap"] <= limits["widest_gap"]
                         and gap["logit_err"] <= limits["logit_err"]
                         and e2e["failed"] == 0
                         and gap["tokens"] >= check["min_tokens"])
    result["check"] = checks
    log(f"check widest_gap {gap['widest_gap']!r} limit <= "
        f"{limits['widest_gap']}")
    log(f"check logit_err {gap['logit_err']!r} limit <= "
        f"{limits['logit_err']}")
    log(f"check failed_requests {e2e['failed']} limit <= 0")
    log(f"check tokens_compared {gap['tokens']} limit >= "
        f"{check['min_tokens']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
