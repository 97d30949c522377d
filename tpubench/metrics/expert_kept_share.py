"""Model step (``models/mlp.py``): the share of the (token, k) assignments
to the experts held here that the capacity dispatch kept, over the
window's steps: the sum of ``moe_kept`` over the sum of ``moe_assigned``,
the metadata of the program's ``trainer.compute`` spans."""

from bench import program_spans as ps


def read(ctx):
    spans = [s for s in ps.named(ps.window_spans(ctx), "trainer.compute")
             if "moe_assigned" in s.meta]
    assigned = sum(int(s.meta["moe_assigned"]) for s in spans)
    if not assigned:
        return None
    return 100.0 * sum(int(s.meta["moe_kept"]) for s in spans) / assigned
