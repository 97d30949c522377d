"""Model step: model FLOPs of the window's steps (6 N active per token plus
causal attention; no recompute, no expert dispatch) over the window's
seconds times the chips' bf16 peak."""

from bench.flops import train_flops_per_token
from bench.peaks import peaks


def read(ctx):
    steps = ctx.out.get("window_steps")
    if not steps:
        return None
    import jax

    peak = peaks(jax.devices()[0].device_kind)["bf16_flops"] * ctx.chips
    t = ctx.cell.traffic
    flops = steps * ctx.out["tokens_per_step"] * train_flops_per_token(
        ctx.cell.config, int(t["seq_len"]))
    t0, t1 = ctx.window
    return 100.0 * flops / ((t1 - t0) * peak)
