"""Model step: device time of the step programs (fused mixed step and
decode step) per engine step in the traced part of the window."""
from bench.harness.steps import STEP_PROGRAMS


def read(run):
    secs, launches = run.trace.program_time(STEP_PROGRAMS)
    steps = len(run.trace.spans("bench.step"))
    if not launches or not steps:
        return None
    return 1e3 * secs / steps
