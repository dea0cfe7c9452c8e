"""Scheduler: mean live decode rows per model launch, read from the
``decode_rows`` count the program attaches to each dispatch span."""


def read(run):
    counts = getattr(run.trace, "dispatch_counts", None)
    rows = counts("decode_rows") if counts else None
    return sum(rows) / len(rows) if rows else None
