"""Model step of a latent-attention MoE configuration: model FLOPs of the
window's steps (``bench/flops_mla.py``: 6 N active per token, the held
experts at their share of the top-k, plus causal attention at Dqk + Dv)
over the window's seconds times the chips' bf16 peak."""

from bench.flops_mla import train_flops_per_token
from bench.peaks import peaks


def read(ctx):
    steps = ctx.out.get("window_steps")
    if not steps:
        return None
    import jax

    peak = peaks(jax.devices()[0].device_kind)["bf16_flops"] * ctx.chips
    flops = steps * ctx.out["tokens_per_step"] * train_flops_per_token(
        ctx.cell.config, int(ctx.cell.traffic["seq_len"]))
    t0, t1 = ctx.window
    return 100.0 * flops / ((t1 - t0) * peak)
