"""Readings that the limits of ``correct`` are set from, on the chip: for
each seed, serve the cell's traffic for a short window at the cell's own
size and load, then compare the served tokens and their logits with the
float32 reference (the program), and the tokens and logits of the fp8
reference at the same positions (the control). One engine serves every
seed (its weights redrawn from each seed); the references run once the
engine is gone. One JSON line per seed.

  python3 bench/tools/calibrate.py --workload phi3-bf16.chat \
      --seeds 1,2,3 --control-seeds 1,2,3 --seconds 12
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import spec  # noqa: E402


def serve(cell, family, seeds, seconds):
    """Per seed: the sampled (prompt, served) pairs, their logits rows and
    the window's numbers."""
    from bench.harness import driver, program, traffic

    cfg, deploy, mix = cell.config, cell.deploy, cell.mix
    engine, rec = program.build(cfg, deploy, seeds[0], family)
    program.warm_up(engine, deploy, mix, cfg["vocab_size"], seeds[0])
    arch = family.arch_config(cfg)
    out = {}
    for seed in seeds:
        if seed != seeds[0]:
            engine.params = None
            gc.collect()
            engine.params = program.draw_params(cfg, arch, seed)
        reqs = traffic.generate(mix, seed, seconds, cfg["vocab_size"])
        rec.logits = {}
        rec.keep_logits = True
        win = driver.run(engine, program.submit, reqs, mix, seconds)
        rec.keep_logits = False
        engine.drain()
        e2e = driver.end_to_end(win)
        sample = bench_run.sample_for_check(win, mix["check"], seed)
        out[seed] = {
            "seqs": [(s.prompt, s.tokens) for s in sample],
            "logits": [rec.served_logits(s.rid, len(s.tokens))
                       for s in sample],
            "failed": e2e["failed"], "tokens_per_s": e2e["tokens_per_s"]}
        engine.reset()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench_run.device_or_exit(cell.chips)
    from bench.harness.reference import widest_gap

    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    family = spec.family(cell.config)
    served = serve(cell, family, seeds, args.seconds)
    gc.collect()
    kw = dict(pad_len=cell.deploy["max_len"],
              pad_seqs=cell.mix["check"]["sample_seqs"])
    for seed in seeds:
        got = served.pop(seed)
        ref = family.Reference(cell.config, seed)
        t0 = time.time()
        line = {"seed": seed, "failed": got["failed"],
                "tokens_per_s": got["tokens_per_s"],
                "program": widest_gap(ref, got["seqs"],
                                      logits=got["logits"], **kw)}
        line["reference_s"] = time.time() - t0
        if seed in control:
            line["control"] = widest_gap(ref, got["seqs"], control=True, **kw)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
