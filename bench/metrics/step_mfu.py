"""Device: the model FLOPs the traced steps required (every token the
steps processed, prompt and output, attention at actual length, logits
once per row) over the summed wall time of those engine steps times the
chip's peak, in percent."""


def read(run):
    lo, hi = run.traced
    wall = sum(e - s for s, e in run.win.step_spans if s >= lo and e <= hi)
    if not wall or not run.rows:
        return None
    flops = sum(run.shapes.launch(rows)["flops"] for _, rows in run.rows)
    return 100.0 * flops / (wall * run.peak["flops_per_s"])
