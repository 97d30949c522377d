"""Data pipeline: training-thread time per window step blocked on the
prefetch thread (the program's ``data.wait`` spans inside ``data.load``)."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    steps = ctx.out.get("window_steps")
    if not steps or not ps.named(spans, "data.load"):
        return None
    return 1e3 * sum(s.dur for s in ps.named(spans, "data.wait")) / steps
