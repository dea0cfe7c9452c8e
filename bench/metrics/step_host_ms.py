"""Engine host: mean time per engine step (``serve.step``, the program's
own span) in which no device op runs, in milliseconds."""


def read(run):
    per_step = getattr(run.trace, "host_time_per_step", None)
    secs = per_step() if per_step else None
    return None if secs is None else 1e3 * secs
