"""Model step: device time per engine step of the step programs' ops that
carry no model scope (the layer scan's slicing of weights and KV arena,
its stacking of the new arena, copies the compiler inserted), in
milliseconds."""
from bench.harness.steps import STEP_PROGRAMS


def read(run):
    scope_time = getattr(run.trace, "scope_time", None)
    by = scope_time(STEP_PROGRAMS) if scope_time else None
    steps = len(run.trace.steps()) if by else 0
    return 1e3 * by.get(None, 0.0) / steps if steps else None
