"""Operations and bytes from a configuration's shapes.

Model FLOPs count what the mathematics of training needs: six times the
active parameters that multiply each token (embedding lookups are not
products; the output head over the real vocabulary is), plus causal
attention; no recomputation, no expert dispatch or combine, no optimizer.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def _dims(cfg: Dict[str, Any]):
    a = cfg.get("assumed", {})
    D = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    KV = int(cfg["num_key_value_heads"])
    hd = int(a.get("head_dim") or D // H)
    return D, H, KV, hd


def active_params_per_token(cfg: Dict[str, Any]) -> int:
    """Parameters that multiply one token in a forward pass."""
    D, H, KV, hd = _dims(cfg)
    L = int(cfg["num_hidden_layers"])
    F = int(cfg["intermediate_size"])
    E = int(cfg.get("num_local_experts", 0) or 0)
    K = int(cfg.get("num_experts_per_tok", 0) or 0)
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    ffn = (D * E + K * 3 * D * F) if E else 3 * D * F
    head = D * int(cfg["vocab_size"])
    return L * (attn + ffn) + head


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 N + causal attention, 6 H hd S per layer
    (each query meets S/2 keys on average, two products of 2 hd each)."""
    D, H, KV, hd = _dims(cfg)
    L = int(cfg["num_hidden_layers"])
    return 6.0 * active_params_per_token(cfg) + 6.0 * L * H * hd * seq_len


def flash_fwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal flash-attention forward call over a
    whole layer's heads: two products over the lower triangle, and q, k,
    v read and o written once."""
    D, H, KV, hd = _dims(cfg)
    flops = 2.0 * batch * H * seq_len * seq_len * hd
    nbytes = float(itemsize * (2 * batch * H * seq_len * hd
                               + 2 * batch * KV * seq_len * hd))
    return flops, nbytes


def param_count(cfg: Dict[str, Any]) -> int:
    """Parameters held, with the embedding at its padded row count."""
    D, H, KV, hd = _dims(cfg)
    a = cfg.get("assumed", {})
    L = int(cfg["num_hidden_layers"])
    F = int(cfg["intermediate_size"])
    E = int(cfg.get("num_local_experts", 0) or 0)
    r = int(a.get("vocab_round", 256))
    Vp = (int(cfg["vocab_size"]) + r - 1) // r * r
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    ffn = (D * E + E * 3 * D * F) if E else 3 * D * F
    norms = 2 * D
    emb = Vp * D * (1 if cfg.get("tie_word_embeddings") else 2)
    return L * (attn + ffn + norms) + emb + D


def train_state_bytes(cfg: Dict[str, Any]) -> int:
    """Parameters in bfloat16 (the router in float32) plus float32 master,
    m and v, and the optimizer's int32 step counter."""
    L = int(cfg["num_hidden_layers"])
    router = L * int(cfg["hidden_size"]) * int(cfg.get("num_local_experts", 0) or 0)
    n = param_count(cfg)
    return 12 * n + 2 * (n - router) + 4 * router + 4
