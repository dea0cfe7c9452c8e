"""One run of a cell whose trace is read with the program's own spans and
scopes (``bench/harness/spans.py``): the cell's per-layer metrics and the
four readers of what the program now names itself, ``step_host_ms``,
``gemm_device_ms``, ``layer_scan_ms`` and ``decode_rows_per_step``.

  python3 bench/tools/trace_program.py --workload phi3-bf16.longdoc \
      --seed 1 --seconds 51 --trace 1 [--fixture out.json.gz]

It is ``bench/run.py`` with :func:`bench.harness.spans.extract` and
:class:`bench.harness.spans.Spans` in place of the trace module's own, and
the four metrics added to the cell's list, so its last line is
``bench/run.py``'s result line. Before it, one line gives the host wall
time of the engine steps that start in the first ``TRACE_SECONDS`` of the
window (traced with ``--trace 1``, not with ``--trace 0``: run both with
one seed to read what the profiler costs), and with ``--trace 1`` one
more the step programs' device time per engine step by model scope.
``--fixture`` writes the reduced trace of a few consecutive engine steps
for the tests in ``bench/tests``. Not part of a benchmark run.

It swaps four harness globals for the run (``trace.extract``,
``trace.Reduced``, ``driver.run``, ``spec.load_cell``) because
``bench/run.py`` does not take the spans reduction yet. Once it does
(PERF.md §7 item 1), this tool and ``spans._harness_extract`` are
deleted.
"""
import argparse
import collections
import gzip
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402
from bench.harness import driver, spans, spec  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.steps import STEP_PROGRAMS  # noqa: E402

# the per-layer entries the four readers would take in BENCHMARK.json
METRICS = [
    {"name": "step_host_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "engine host",
     "moves": "itl_p95_ms"},
    {"name": "gemm_device_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels", "moves": "itl_p95_ms"},
    {"name": "layer_scan_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "model step",
     "moves": "itl_p95_ms"},
    {"name": "decode_rows_per_step", "unit": "rows", "better": "higher",
     "source": "device_trace", "layer": "scheduler",
     "moves": "tokens_per_s"},
]


def trim(form: dict, n_steps: int) -> dict:
    """The form of ``n_steps`` consecutive engine steps: the first run of
    them with the most kinds of launch (a fused chunk step and a decode
    step, where the traced part has both)."""
    steps = sorted((s, s + d) for name, s, d in form["host"]
                   if name == spans.STEP_SPAN)
    kinds = [{n for n, s, _, _ in form["dispatch"] if lo <= s < hi}
             for lo, hi in steps]
    n_steps = min(n_steps, len(steps))
    i = max(range(len(steps) - n_steps + 1),
            key=lambda i: len(set().union(*kinds[i:i + n_steps])))
    lo, hi = steps[i][0], steps[i + n_steps - 1][1]

    def inside(s, d):
        return lo <= s and s + d <= hi

    device = [e for e in form["device"] if inside(e[2], e[3])]
    kept = {e[1] for e in device}
    mods = {e[1] for e in device if e[0] == tr.MODULES_LINE}
    return {
        "host": [["bench.window", lo, hi - lo]]
        + [h for h in form["host"]
           if h[0] != "bench.window" and inside(h[1], h[2])],
        "device": device,
        "dispatch": [e for e in form["dispatch"] if inside(e[1], e[2])],
        "op_scope": [e for e in form["op_scope"]
                     if e[0] in mods and e[1] in kept],
    }


def main(argv=None, *, root: str = ROOT, device=None) -> int:
    """``root`` and ``device`` are for tests on the CPU, as in
    ``bench/run.py``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fixture", help="write a few steps' reduced trace")
    ap.add_argument("--fixture-steps", type=int, default=3)
    args = ap.parse_args(argv)
    kept, run = {}, driver.run

    def extract(path):
        kept["form"] = spans.extract(path)
        return kept["form"]

    def run_window(*a, **kw):
        kept["win"] = run(*a, **kw)
        return kept["win"]

    def load_cell(name, root=spec.ROOT):
        cell = load(name, root)
        cell.per_layer = cell.per_layer + METRICS
        return cell

    load = spec.load_cell
    saved = tr.extract, tr.Reduced, driver.run
    tr.extract, tr.Reduced = extract, spans.Spans
    driver.run, spec.load_cell = run_window, load_cell
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace)]
    walls = []
    try:
        rc = bench_run.main(argv, root=root, device=device)
    finally:
        tr.extract, tr.Reduced, driver.run = saved
        spec.load_cell = load
        win = kept.get("win")
        if win is not None:
            walls = [e - s for s, e in win.step_spans
                     if s < win.start + bench_run.TRACE_SECONDS]
        print(json.dumps({
            "steps_in_first_s": bench_run.TRACE_SECONDS,
            "traced": bool(args.trace), "n": len(walls),
            "mean_ms": 1e3 * statistics.mean(walls) if walls else None,
            "wall_ms": [1e3 * w for w in walls]}), file=sys.stderr)
    if "form" in kept:
        red = spans.Spans(kept["form"])
        by = red.scope_time(STEP_PROGRAMS) or {}
        n = len(red.steps())
        print(json.dumps({
            "device_ms_per_step_by_scope": {
                str(k): 1e3 * v / n for k, v in by.items()} if n else None,
            "dispatches": collections.Counter(
                name for name, *_ in kept["form"]["dispatch"])}),
            file=sys.stderr)
    if args.fixture and "form" in kept:
        form = trim(kept["form"], args.fixture_steps)
        with gzip.open(args.fixture, "wt") as f:
            json.dump(form, f, separators=(",", ":"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
