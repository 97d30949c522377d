"""Checkpoint layer: train-state bytes over the mean duration of the
manager's ``restore_latest`` (harness span) in the window's resumes."""


def read(ctx):
    t0, t1 = ctx.window
    d = ctx.spans.durations("restore_latest", t0, t1)
    if not d:
        return None
    return ctx.out["train_state_bytes"] / (sum(d) / len(d)) / 1e6
