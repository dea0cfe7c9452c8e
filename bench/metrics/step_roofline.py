"""Model step: least time of the step programs' required work (weights
once per launch at the format's size, K/V at the rows' actual lengths,
logits once per row) over their device time, in percent."""
from bench.harness.counts import least_seconds
from bench.harness.steps import STEP_PROGRAMS


def read(run):
    secs, launches = run.trace.program_time(STEP_PROGRAMS)
    if not launches or launches != len(run.rows):
        return None
    need = 0.0
    for _, rows in run.rows:
        work = run.shapes.launch(rows)
        need += least_seconds(work["flops"], work["bytes"], run.peak)[0]
    return 100.0 * need / secs
