"""Kernels: the paged attention kernel's least time for the attention its
launches require (rows at their actual lengths, not the cache's capacity)
over the kernel's device time, in percent."""
from bench.harness.counts import least_seconds
from bench.harness.steps import PAGED_KERNEL


def read(run):
    secs, n = run.trace.op_time(PAGED_KERNEL)
    if not n or not run.rows:
        return None
    need = 0.0
    for _, rows in run.rows:
        work = run.shapes.attention_kernel(rows)
        need += least_seconds(work["flops"], work["bytes"], run.peak)[0]
    return 100.0 * need / secs
