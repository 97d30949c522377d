"""Checkpoint: per resume, the per-leaf buffers: allocating and zeroing
them, and copying the read chunks into them (the ``ckpt.overlay`` spans
under each ``ckpt.restore``)."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    restores = ps.named(spans, "ckpt.restore")
    if not restores:
        return None
    return sum(c.dur for r in restores
               for c in ps.within(spans, r, "ckpt.overlay")) / len(restores)
