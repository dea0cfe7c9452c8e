"""Does the seed change the work of a window? On the chip, serve a cell's
traffic for one window per listed seed with one engine (a seed may be
listed twice), and print per window its end-to-end numbers and the
lengths of the requests it sent.

  python3 bench/tools/seed_work.py --workload phi3-bf16.longdoc \
      --seeds 1,1,2,3 --seconds 51 --mix strata=1 --mix requests=16

``--mix key=value`` overrides a number of the cell's traffic mix, so an
earlier form of a mix can be read beside the current one. Two windows of
one seed that agree far more closely than the seeds do mean the seed
changes the work. The weights are drawn once, from the first seed: the
step programs' time does not depend on their values. Not part of a
benchmark run.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--mix", action="append", default=[])
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    mix = dict(cell.mix)
    for kv in args.mix:
        key, value = kv.split("=", 1)
        mix[key] = json.loads(value)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench_run.device_or_exit(cell.chips)
    from bench.harness import driver, program, traffic

    cfg, deploy = cell.config, cell.deploy
    seeds = [int(s) for s in args.seeds.split(",")]
    engine, _ = program.build(cfg, deploy, seeds[0])
    program.warm_up(engine, deploy, mix, cfg["vocab_size"], seeds[0])
    for seed in seeds:
        reqs = traffic.generate(mix, seed, args.seconds, cfg["vocab_size"])
        t0 = time.perf_counter()
        win = driver.run(engine, program.submit, reqs, mix, args.seconds)
        e2e = driver.end_to_end(win)
        engine.drain()  # the window's drain may stop early
        print(json.dumps({
            "seed": seed, "mix": args.mix,
            "sent": [[len(s.prompt), s.max_tokens] for s in win.served],
            "drain_s": time.perf_counter() - t0 - args.seconds,
            **{k: e2e[k] for k in ("tokens_per_s", "itl_p95_ms", "tokens",
                                   "attempted")}}), flush=True)
        engine.reset()


if __name__ == "__main__":
    main()
