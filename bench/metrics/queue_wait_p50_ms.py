"""Scheduler: median of due time to admission into a slot, over every
request due in the window (the engine's own ADMIT events)."""
from bench.harness.driver import percentile


def read(run):
    admit = {}
    for kind, rid, ts in run.events:
        if kind == "admit":
            admit.setdefault(rid, ts)
    waits = [admit[s.rid] - s.due for s in run.win.served
             if s.due < run.win.end and s.rid in admit]
    return 1e3 * percentile(waits, 50) if waits else None
