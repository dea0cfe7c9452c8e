"""Kernels (GEMMs): device time per engine step of the step programs' ops
in the program's ``gemm`` name scope (every dense projection, bf16 or
SWIS-packed, and the unembedding), in milliseconds."""
from bench.harness.steps import STEP_PROGRAMS


def read(run):
    scope_time = getattr(run.trace, "scope_time", None)
    by = scope_time(STEP_PROGRAMS) if scope_time else None
    steps = len(run.trace.steps()) if by else 0
    return 1e3 * by.get("gemm", 0.0) / steps if steps else None
