"""Operations and bytes of a DeepSeek-V2 configuration file (latent
attention, a leading dense layer, then routed and shared experts), for
one expert-parallel rank's share.

Model FLOPs count what the mathematics of training needs, as
``flops.py`` does: six times the parameters that multiply each token,
plus causal attention.  A token's routed part counts the experts held
here at the share of its top-k that lands on them on average
(``k * held / router width``); the router, the shared experts, attention
and the output head count whole.  No recomputation, no dispatch or
combine, no optimizer.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


class _Shapes:
    def __init__(self, cfg: Dict[str, Any]):
        a = cfg.get("assumed", {})
        self.D = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.lat = int(cfg["kv_lora_rank"])
        self.nope = int(cfg["qk_nope_head_dim"])
        self.rope = int(cfg["qk_rope_head_dim"])
        self.dqk = self.nope + self.rope
        self.dv = int(cfg["v_head_dim"])
        self.L = int(cfg["num_hidden_layers"])
        self.dense = int(cfg["first_k_dense_replace"])
        self.F = int(cfg["intermediate_size"])
        self.Fe = int(cfg["moe_intermediate_size"])
        self.Fs = self.Fe * int(cfg["n_shared_experts"])
        self.E = int(cfg["published"]["n_routed_experts"])
        self.Eh = int(cfg["n_routed_experts"])
        self.K = int(cfg["num_experts_per_tok"])
        self.V = int(cfg["vocab_size"])
        r = int(a.get("vocab_round", 256))
        self.Vp = (self.V + r - 1) // r * r

    @property
    def attn(self) -> int:
        D, H = self.D, self.H
        return (D * H * self.dqk + D * (self.lat + self.rope)
                + self.lat * H * (self.nope + self.dv) + H * self.dv * D)

    @property
    def moe_layers(self) -> int:
        return self.L - self.dense


def active_params_per_token(cfg: Dict[str, Any]) -> float:
    """Parameters that multiply one token in a forward pass, the held
    experts at their expected share of its top-k."""
    s = _Shapes(cfg)
    dense = s.attn + 3 * s.D * s.F
    moe = (s.attn + s.D * s.E + s.K * s.Eh / s.E * 3 * s.D * s.Fe
           + 3 * s.D * s.Fs)
    return s.dense * dense + s.moe_layers * moe + s.D * s.V


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 N active + causal attention, 3 H S (Dqk +
    Dv) per layer (each query meets S/2 keys on average; QK^T and PV, two
    FLOPs a product, three passes)."""
    s = _Shapes(cfg)
    return (6.0 * active_params_per_token(cfg)
            + 3.0 * s.L * s.H * seq_len * (s.dqk + s.dv))


def mla_fwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal flash-attention forward call over a
    layer's heads: QK^T at Dqk and PV at Dv over the lower triangle, and
    q, k (Dqk) and v (Dv) read and o (Dv) written once."""
    s = _Shapes(cfg)
    n = batch * s.H * seq_len
    flops = float(n * seq_len * (s.dqk + s.dv))
    return flops, float(itemsize * n * 2 * (s.dqk + s.dv))


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters held here, the embedding and head at their padded rows."""
    s = _Shapes(cfg)
    per = s.attn + s.lat + 2 * s.D                     # + kv_norm, ln1, ln2
    dense = per + 3 * s.D * s.F
    moe = per + s.D * s.E + s.Eh * 3 * s.D * s.Fe + 3 * s.D * s.Fs
    return s.dense * dense + s.moe_layers * moe + 2 * s.Vp * s.D + s.D


def train_state_bytes(cfg: Dict[str, Any]) -> int:
    """Parameters in bfloat16 (the router in float32) plus float32 master,
    m and v, and the optimizer's int32 step counter."""
    s = _Shapes(cfg)
    router = s.moe_layers * s.D * s.E
    n = param_count(cfg)
    return 12 * n + 2 * (n - router) + 4 * router + 4
