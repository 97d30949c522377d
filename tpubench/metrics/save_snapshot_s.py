"""Checkpoint: the device-to-host snapshot of the state per save, on the
training thread (the program's ``ckpt.snapshot`` spans)."""

from bench import program_spans as ps


def read(ctx):
    snaps = ps.named(ps.window_spans(ctx), "ckpt.snapshot")
    if not snaps:
        return None
    return sum(s.dur for s in snaps) / len(snaps)
