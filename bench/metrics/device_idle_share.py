"""Device: share of the engine steps' host time in which no device op
runs, in percent."""


def read(run):
    share = run.trace.idle_share_within("bench.step")
    return None if share is None else 100.0 * share
