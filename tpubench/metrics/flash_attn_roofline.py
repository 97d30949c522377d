"""Kernels: the Pallas flash-attention forward's share of its roofline.

Each of its device events in the traced window is one call over a whole
layer's heads at the cell's batch and length; the least time such a call
can take is the larger of its FLOPs over the bf16 peak and its bytes over
the memory bandwidth.  The share is that least time, times the events,
over the events' summed device time.
"""

from bench.flops import flash_fwd_cost
from bench.peaks import peaks



def kernel_matcher(cfg, batch: int, seq: int):
    """The trace names each call by its HLO text: a ``tpu_custom_call``
    with the attention's operand shapes, q of [B, H, S, hd] and k, v of
    [B, KV, S, hd] (the forward and its recomputation in the backward)."""
    from bench.flops import _dims

    D, H, KV, hd = _dims(cfg)
    q = f"bf16[{batch},{H},{seq},{hd}]"
    kv = f"bf16[{batch},{KV},{seq},{hd}]"

    def match(name: str) -> bool:
        return "tpu_custom_call" in name and name.count(q) >= 2 and kv in name
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
    fl, nb = flash_fwd_cost(ctx.cell.config, batch, seq)
    least = max(fl / pk["bf16_flops"], nb / pk["hbm_bytes_per_s"])
    return 100.0 * least * count / seconds
