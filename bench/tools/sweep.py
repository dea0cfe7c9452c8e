"""Find a cell's knee once, on the chip: serve its traffic mix at a list of
open-loop rates with one engine, and print per rate the end-to-end numbers
and whether the backlog grew.

  python3 bench/tools/sweep.py --workload phi3-bf16.chat \
      --rates 0.2,0.3,0.4 --seconds 51 --seed 1

The knee is the highest rate whose backlog stays flat: the requests due
in the window's second half wait about as long for their first token as
those of its first half, and few are still queued at its end. Not part
of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench_run.device_or_exit(cell.chips)
    from bench.harness import driver, program, traffic

    cfg, deploy = cell.config, cell.deploy
    engine, _ = program.build(cfg, deploy, args.seed)
    program.warm_up(engine, deploy, cell.mix, cfg["vocab_size"], args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        reqs = traffic.generate(mix, args.seed, args.seconds,
                                cfg["vocab_size"])
        t0 = time.perf_counter()
        win = driver.run(engine, program.submit, reqs, mix, args.seconds)
        e2e = driver.end_to_end(win)
        # backlog: requests due before the end still queued at the end;
        # a rate the engine sustains has its later requests wait no
        # longer for their first token than its earlier ones
        waiting = sum(1 for s in win.served if s.due < win.end
                      and (not s.token_times or s.token_times[0] > win.end))
        due = [s for s in win.served if s.due < win.end and s.token_times]
        half = len(due) // 2
        ttft = [1e3 * (s.token_times[0] - s.due) for s in due]
        halves = [driver.percentile(ttft[:half], 50) if half else None,
                  driver.percentile(ttft[half:], 50) if ttft else None]
        engine.drain()  # the window's drain may stop early
        print(json.dumps({"rate": rate, "backlog_at_end": waiting,
                          "ttft_p50_ms_halves": halves,
                          "drain_s": time.perf_counter() - t0 - args.seconds,
                          **e2e}), flush=True)
        engine.reset()


if __name__ == "__main__":
    main()
