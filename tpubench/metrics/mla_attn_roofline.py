"""Kernels: the Pallas flash-attention forward's share of its roofline in
a latent-attention configuration, where q and k have one head size (Dqk)
and v another (Dv).

Each device event of the call in the traced window is one call over a
layer's heads at the cell's batch and length: q and k of [B, H, S, Dqk],
v (and the output) of [B, H, S, Dv], named in the event's HLO text.  The
least time such a call can take is the larger of its FLOPs (B H S^2 (Dqk
+ Dv), the lower triangle) over the bf16 peak and its bytes (q, k, v read
and o written) over the memory bandwidth.  The share is that least time,
times the events, over the events' summed device time.
"""

from bench.flops_mla import _Shapes, mla_fwd_cost
from bench.peaks import peaks


def kernel_matcher(cfg, batch: int, seq: int):
    s = _Shapes(cfg)
    qk = f"bf16[{batch},{s.H},{seq},{s.dqk}]"
    v = f"bf16[{batch},{s.H},{seq},{s.dv}]"

    def match(name: str) -> bool:
        return "tpu_custom_call" in name and name.count(qk) >= 2 and v in name
    return match


def read(ctx):
    tr = ctx.trace_data
    if tr is None:
        return None
    t = ctx.cell.traffic
    batch, seq = int(t["batch"]) // ctx.chips, int(t["seq_len"])
    seconds, count = tr.op_time(kernel_matcher(ctx.cell.config, batch, seq))
    if not count or seconds <= 0:
        return None
    import jax

    pk = peaks(jax.devices()[0].device_kind)
    fl, nb = mla_fwd_cost(ctx.cell.config, batch, seq)
    least = max(fl / pk["bf16_flops"], nb / pk["hbm_bytes_per_s"])
    return 100.0 * least * count / seconds
