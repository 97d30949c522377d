"""Checkpoint: the CRC passes of the manifest (per extent and per leaf)
per background save (``ckpt.crc`` under the ``ckpt.save`` that a
``ckpt.save_async`` of the same step handed to its thread)."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    saves = ps.handed_off(spans, "ckpt.save", "ckpt.save_async")
    if not saves:
        return None
    return sum(c.dur for s in saves for c in ps.within(spans, s, "ckpt.crc")) \
        / len(saves)
