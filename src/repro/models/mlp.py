"""Feed-forward blocks: gated MLPs (SwiGLU / GeGLU) and mixture-of-experts.

The MoE layer uses the capacity-dispatch formulation (Switch/t5x style):
tokens pick top-k experts, positions inside an expert's buffer come from a
cumulative sum (no sort), and dispatch/combine are einsums against a
(tokens, experts, capacity) one-hot — the formulation GSPMD partitions
well with experts on the "model" axis (EP) and tokens on "data".
``group_chunk`` processes groups of sequences through a lax.map to bound
the transient dispatch tensors for very large shapes (the hillclimb knob).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import math

import jax
import jax.numpy as jnp

from .common import act_fn, constrain_dims, constrain_hidden, dense_init
from .config import ModelConfig, MoEConfig


# ---------------------------------------------------------------------------
# dense gated MLP
# ---------------------------------------------------------------------------
def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Dict:
    d_ff = d_ff or cfg.d_ff
    dt = cfg.param_jdtype()
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], cfg.d_model, (d_ff,), dt),    # gate proj
        "wg": dense_init(ks[1], cfg.d_model, (d_ff,), dt),    # up proj
        "wo": dense_init(ks[2], d_ff, (cfg.d_model,), dt),
    }


def mlp_apply(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    act = act_fn(cfg.mlp_act)
    gate = act(jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype)))
    if cfg.mlp_act == "gelu_mlp":  # plain 2-layer MLP (whisper)
        h = gate
    else:
        h = gate * jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    h = constrain_hidden(h)  # ffn dim on "model": Megatron column-parallel
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# mixture of experts
# ---------------------------------------------------------------------------
def moe_init(cfg: ModelConfig, key) -> Dict:
    m = cfg.moe
    dt = cfg.param_jdtype()
    ks = jax.random.split(key, 5)
    D, E, F = cfg.d_model, m.held, m.d_expert
    p = {
        "router": dense_init(ks[0], D, (m.num_experts,), jnp.float32),
        "wi": dense_init(ks[1], D, (E, F), dt).transpose(1, 0, 2),  # (E,D,F)
        "wg": dense_init(ks[2], D, (E, F), dt).transpose(1, 0, 2),
        "wo": dense_init(ks[3], F, (E, D), dt).transpose(1, 0, 2),  # (E,F,D)
    }
    if m.num_shared:
        sk = jax.random.split(ks[4], 3)
        Fs = m.d_expert * m.num_shared
        p["shared"] = {
            "wi": dense_init(sk[0], D, (Fs,), dt),
            "wg": dense_init(sk[1], D, (Fs,), dt),
            "wo": dense_init(sk[2], Fs, (D,), dt),
        }
    return p


def _route(m: MoEConfig, p: Dict, x: jax.Array):
    """Router over all ``num_experts``: (gates (T,K), expert ids (T,K),
    one-hot of the ids (T,K,E), Switch loss)."""
    E, K = m.num_experts, m.top_k
    logits = jnp.einsum("td,de->te", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                  # (T,K)
    if m.norm_topk:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    if m.routed_scale != 1.0:
        gate_w = gate_w * m.routed_scale

    # load-balancing auxiliary loss (Switch): E * sum_e f_e * P_e
    me = probs.mean(0)                                        # (E,)
    onehot_k = jax.nn.one_hot(gate_i, E, dtype=jnp.float32)   # (T,K,E)
    ce = onehot_k.sum(1).mean(0)                              # fraction per expert
    aux = (me * ce).sum() * E * m.aux_loss_weight
    return gate_w, gate_i, onehot_k, aux


def _held(m: MoEConfig, onehot_k: jax.Array) -> jax.Array:
    """The one-hot's columns of the experts held here: rows of
    assignments to experts held elsewhere are all zero."""
    if m.held == m.num_experts:
        return onehot_k
    return onehot_k[..., m.expert_offset:m.expert_offset + m.held]


def _moe_group(cfg: ModelConfig, p: Dict, x: jax.Array,
               cf: Optional[float] = None):
    """MoE over one token group.  x: (T, D) -> (y (T, D), aux scalar,
    counts (2,): (token, k) assignments to the experts held here, and
    those kept under capacity)."""
    m = cfg.moe
    T, D = x.shape
    K = m.top_k
    cf = m.capacity_factor if cf is None else cf
    C = max(1, int(T * K * cf / m.num_experts))
    act = act_fn(cfg.mlp_act)

    gate_w, _, onehot_k, aux = _route(m, p, x)
    onehot_k = _held(m, onehot_k)                             # (T,K,E held)

    # position of each (t, k) assignment inside its expert buffer
    flat = onehot_k.reshape(T * K, -1)                        # row-major: t-major, k-minor
    pos = (jnp.cumsum(flat, axis=0) - flat)                   # (T*K, E) exclusive
    pos = (pos * flat).sum(-1).reshape(T, K)                  # (T,K)
    keep = pos < C
    gate_w = gate_w * keep
    here = onehot_k.sum(-1)                                   # (T,K) 1: held here
    counts = jnp.stack([here.sum(), (here * keep).sum()]).astype(jnp.int32)

    # dispatch one-hot: (T, K, E, C) -> einsum'd, never stored past fusion
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=x.dtype)  # (T,K,C)
    disp = jnp.einsum("tke,tkc->tec", onehot_k.astype(x.dtype), pos_oh)
    expert_in = jnp.einsum("tec,td->ecd", disp, x)            # (E,C,D)
    expert_in = constrain_dims(expert_in, {0: "model"})       # EP over "model"

    h = act(jnp.einsum("ecd,edf->ecf", expert_in, p["wi"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["wg"].astype(x.dtype))
    h = constrain_dims(h, {0: "model", 2: "model"})           # EP, else TP-in-expert
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))  # (E,C,D)
    expert_out = constrain_dims(expert_out, {0: "model"})

    comb = jnp.einsum("tke,tkc,tk->tec", onehot_k.astype(x.dtype), pos_oh,
                      gate_w.astype(x.dtype))
    y = jnp.einsum("tec,ecd->td", comb, expert_out)
    return y, aux, counts


def _moe_group_dropless(cfg: ModelConfig, p: Dict, x: jax.Array):
    """Dropless megablocks-style dispatch: sort (token, k) assignments by
    expert and run grouped matmuls with ``jax.lax.ragged_dot``.  Exact —
    no capacity, no drops — hence also the serving path.  It holds every
    routed expert."""
    m = cfg.moe
    if m.held != m.num_experts:
        raise NotImplementedError("the dropless path holds every expert")
    T, D = x.shape
    K = m.top_k
    act = act_fn(cfg.mlp_act)

    gate_w, gate_i, _, aux = _route(m, p, x)
    flat_e = gate_i.reshape(-1)                    # (T*K,)
    order = jnp.argsort(flat_e)                    # stable sort by expert
    tok = order // K
    xs = x[tok]                                    # (T*K, D)
    group_sizes = jnp.bincount(flat_e, length=m.num_experts).astype(jnp.int32)
    h = act(jax.lax.ragged_dot(xs, p["wi"].astype(x.dtype), group_sizes))
    h = h * jax.lax.ragged_dot(xs, p["wg"].astype(x.dtype), group_sizes)
    h = constrain_dims(h, {1: "model"})
    out = jax.lax.ragged_dot(h, p["wo"].astype(x.dtype), group_sizes)  # (T*K, D)
    w_sorted = gate_w.reshape(-1)[order].astype(x.dtype)
    y = jnp.zeros_like(x).at[tok].add(out * w_sorted[:, None])
    n = jnp.asarray(T * K, jnp.int32)
    return y, aux, jnp.stack([n, n])


def _shared(cfg: ModelConfig, sp: Dict, x: jax.Array) -> jax.Array:
    """The always-active shared experts, as one gated MLP."""
    act = act_fn(cfg.mlp_act)
    g = act(jnp.einsum("bsd,df->bsf", x, sp["wi"].astype(x.dtype)))
    h = g * jnp.einsum("bsd,df->bsf", x, sp["wg"].astype(x.dtype))
    h = constrain_hidden(h)
    return jnp.einsum("bsf,fd->bsd", h, sp["wo"].astype(x.dtype))


def moe_apply(cfg: ModelConfig, p: Dict, x: jax.Array,
              serve: bool = False) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B,S,D) -> (y, stats): ``aux`` (the load-balancing loss),
    ``moe_assigned`` ((token, k) assignments to the experts held here) and
    ``moe_kept`` (those kept under capacity).

    Capacity dispatch over fixed-size token groups (``group_tokens``),
    vmapped per group and lax.map'd over chunks of groups so the one-hot
    dispatch temporaries stay bounded.  ``serve=True`` uses the larger
    no-drop capacity margin; configs with ``dropless=True`` (smoke/tests)
    take the exact sort+ragged_dot path instead.  Only the routed experts
    this chip holds (``MoEConfig.experts_held``) add to ``y``.
    """
    m = cfg.moe
    B, S, D = x.shape
    cf = m.serve_capacity_factor if serve else m.capacity_factor

    if m.dropless:
        y, aux, counts = _moe_group_dropless(cfg, p, x.reshape(B * S, D))
        y = y.reshape(B, S, D)
    else:
        T = B * S
        gt = min(m.group_tokens, T)
        if T % gt:
            gt = math.gcd(T, gt)
        groups = T // gt
        xg = x.reshape(groups, gt, D)

        def do_group(g):
            return _moe_group(cfg, p, g, cf)

        mc = m.map_chunk_groups
        if groups > mc and groups % mc == 0:
            ys, auxs, counts = jax.lax.map(lambda ch: jax.vmap(do_group)(ch),
                                           xg.reshape(groups // mc, mc, gt, D))
        else:
            ys, auxs, counts = jax.vmap(do_group)(xg)
        y = ys.reshape(B, S, D)
        aux = auxs.mean()
        counts = counts.reshape(-1, 2).sum(0)

    if m.num_shared:
        y = y + _shared(cfg, p["shared"], x)
    return y, {"aux": aux, "moe_assigned": counts[0], "moe_kept": counts[1]}


def moe_apply_dense_oracle(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    """All-held-experts dense evaluation with top-k gating — the
    correctness oracle for tests (O(E) flops; tiny shapes only).  No
    capacity drops."""
    m = cfg.moe
    B, S, D = x.shape
    act = act_fn(cfg.mlp_act)
    xf = x.reshape(B * S, D)
    gate_w, gate_i, _, _ = _route(m, p, xf.astype(jnp.float32))
    w_full = jnp.zeros((B * S, m.num_experts), jnp.float32)
    w_full = jax.vmap(lambda w, gw, gi: w.at[gi].set(gw))(w_full, gate_w, gate_i)
    w_held = w_full[:, m.expert_offset:m.expert_offset + m.held]
    h = act(jnp.einsum("td,edf->etf", xf, p["wi"].astype(x.dtype)))
    h = h * jnp.einsum("td,edf->etf", xf, p["wg"].astype(x.dtype))
    out = jnp.einsum("etf,efd->etd", h, p["wo"].astype(x.dtype))
    y = jnp.einsum("te,etd->td", w_held.astype(x.dtype), out).reshape(B, S, D)
    if m.num_shared:
        y = y + _shared(cfg, p["shared"], x)
    return y
