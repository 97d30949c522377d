"""Checkpoint: device idle seconds per save while the background save
commits, outside the training thread's own ``ckpt.save_async`` stall:
what the commit's host work costs the step loop (the union of device
operations, mean over chips, inside each background ``ckpt.save``)."""

from bench import program_spans as ps


def read(ctx):
    tr = ctx.trace_data
    spans = ps.window_spans(ctx)
    saves = ps.handed_off(spans, "ckpt.save", "ckpt.save_async")
    if tr is None or not tr.devices or not saves:
        return None
    stalls = [(s.start, s.end) for s in ps.named(spans, "ckpt.save_async")]
    total = 0.0
    for d in tr.devices:
        ops = [(o.start, o.start + o.dur) for o in tr.ops if o.device == d]
        total += sum(ps.idle_s(ops, s.start, s.end, stalls) for s in saves)
    return total / len(tr.devices) / len(saves)
