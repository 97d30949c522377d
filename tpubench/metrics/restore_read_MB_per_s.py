"""Engine: bytes the restores read over the time of their read sessions
(the ``bytes`` of the program's ``ckpt.read`` spans over their summed
duration: open, speculated preads, close)."""

from bench import program_spans as ps


def read(ctx):
    reads = ps.named(ps.window_spans(ctx), "ckpt.read")
    seconds = sum(s.dur for s in reads)
    if not reads or seconds <= 0:
        return None
    return sum(s.meta.get("bytes", 0) for s in reads) / seconds / 1e6
