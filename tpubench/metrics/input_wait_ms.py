"""Data pipeline: host time per window step spent in ``loader.load`` and in
putting the batch on the device (harness spans ``input`` and ``put``)."""


def read(ctx):
    steps = ctx.out.get("window_steps")
    if not steps:
        return None
    t0, t1 = ctx.window
    total = sum(ctx.spans.durations("input", t0, t1)) \
        + sum(ctx.spans.durations("put", t0, t1))
    return 1e3 * total / steps
