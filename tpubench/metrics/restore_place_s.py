"""Trainer: per resume, putting the restored host state on its shardings
until it is ready on the device (the program's ``trainer.place`` spans)."""

from bench import program_spans as ps


def read(ctx):
    places = ps.named(ps.window_spans(ctx), "trainer.place")
    if not places:
        return None
    return sum(s.dur for s in places) / len(places)
