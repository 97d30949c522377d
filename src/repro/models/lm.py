"""Decoder-only LM assembled from a per-layer block pattern.

Covers 9 of the 10 assigned architectures (whisper is enc-dec, see
:mod:`repro.models.whisper`): dense GQA/MQA transformers, MoE, MLA,
Mamba2 hybrids with a shared attention block (Zamba2), and RWKV6.

Layers are grouped into runs of identical (block kind, ffn kind) and each
run's parameters are *stacked* with a leading layer axis; the forward pass
``lax.scan``s over the stack (MaxText-style).  This keeps the HLO size —
and therefore SPMD-partitioning time at 512 devices — independent of
depth, and gives remat a natural per-layer boundary.

API (all pure):
  init(cfg, rng) -> params
  loss(cfg, params, batch) -> scalar           (train)
  prefill(cfg, params, batch, max_len) -> (last_logits, cache)
  decode_step(cfg, params, cache, token, pos) -> (logits, cache)
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mlp as mlpm
from . import ssm
from .common import (apply_norm, chunked_softmax_xent, constrain_batch,
                     dense_init, embed_tokens, embedding_init,
                     lm_head_logits, merge_visual, norm_init, positions_for)
from .config import ModelConfig


# ---------------------------------------------------------------------------
# layer grouping
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerGroup:
    kind: str      # attn | mla | mamba2 | rwkv6 | shared_attn
    ffn: str       # moe | mlp | dense | none
    start: int     # absolute index of first layer in the group
    count: int


def _ffn_kind(cfg: ModelConfig, layer_idx: int) -> str:
    kind = cfg.blocks[layer_idx]
    if kind in ("mamba2", "rwkv6"):
        return "none"
    m = cfg.moe
    if m is None:
        return "mlp"
    if layer_idx >= m.first_dense_layers:
        return "moe"
    return "dense"


def layer_groups(cfg: ModelConfig) -> List[LayerGroup]:
    groups: List[LayerGroup] = []
    for i, kind in enumerate(cfg.blocks):
        sig = (kind, _ffn_kind(cfg, i))
        if groups and kind != "shared_attn" \
                and (groups[-1].kind, groups[-1].ffn) == sig:
            g = groups[-1]
            groups[-1] = LayerGroup(g.kind, g.ffn, g.start, g.count + 1)
        else:
            groups.append(LayerGroup(kind, sig[1], i, 1))
    return groups


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------
def _ffn_init(cfg: ModelConfig, key, ffn: str) -> Dict:
    if ffn == "moe":
        return mlpm.moe_init(cfg, key)
    if ffn == "dense":
        return mlpm.mlp_init(cfg, key, d_ff=cfg.moe.dense_d_ff)
    return mlpm.mlp_init(cfg, key)


def _block_init(cfg: ModelConfig, g: LayerGroup, key) -> Dict:
    ks = jax.random.split(key, 4)
    if g.kind in ("attn", "mla"):
        p = {
            "ln1": norm_init(cfg),
            "attn": attn.attn_init(cfg, ks[0]) if g.kind == "attn"
            else attn.mla_init(cfg, ks[0]),
            "ffn": _ffn_init(cfg, ks[1], g.ffn),
        }
        if not cfg.parallel_block:
            p["ln2"] = norm_init(cfg)
        return p
    if g.kind == "mamba2":
        return {"ln1": norm_init(cfg), "mixer": ssm.mamba2_init(cfg, ks[0])}
    if g.kind == "rwkv6":
        return {"ln1": norm_init(cfg), "tm": ssm.rwkv6_init(cfg, ks[0]),
                "ln2": norm_init(cfg)}
    raise ValueError(g.kind)


def _stack(trees: List[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init(cfg: ModelConfig, rng) -> Dict:
    groups = layer_groups(cfg)
    keys = jax.random.split(rng, cfg.n_layers + 4)
    layers = []
    for g in groups:
        if g.kind == "shared_attn":
            layers.append({})
            continue
        per = [_block_init(cfg, g, keys[g.start + i]) for i in range(g.count)]
        layers.append(_stack(per))
    params: Dict[str, Any] = {
        "embed": embedding_init(cfg, keys[cfg.n_layers]),
        "final_norm": norm_init(cfg),
        "layers": layers,
    }
    if any(k == "shared_attn" for k in cfg.blocks):
        params["shared_block"] = _block_init(
            cfg, LayerGroup("attn", "mlp", 0, 1), keys[cfg.n_layers + 1])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[cfg.n_layers + 2], cfg.d_model,
                                       (cfg.padded_vocab,), cfg.param_jdtype()).T
    if cfg.rwkv is not None:
        params["ln0"] = norm_init(cfg)
    return params


# ---------------------------------------------------------------------------
# forward (training)
# ---------------------------------------------------------------------------
def _no_stats() -> Dict[str, jax.Array]:
    """A layer's stats where it routes nothing: the auxiliary loss and the
    MoE assignment counts (``mlp.moe_apply``), all zero."""
    return {"aux": jnp.zeros((), jnp.float32),
            "moe_assigned": jnp.zeros((), jnp.int32),
            "moe_kept": jnp.zeros((), jnp.int32)}


def _apply_ffn(cfg: ModelConfig, ffn_kind: str, fp: Dict, h, serve=False):
    if ffn_kind == "moe":
        return mlpm.moe_apply(cfg, fp, h, serve=serve)
    return mlpm.mlp_apply(cfg, fp, h), _no_stats()


def _apply_attn_layer(cfg: ModelConfig, kind: str, ffn_kind: str, lp: Dict,
                      x, positions, serve=False):
    attn_fn = attn.attn_apply if kind in ("attn", "shared_attn") else attn.mla_apply
    h = apply_norm(cfg, lp["ln1"], x)
    a = attn_fn(cfg, lp["attn"], h, positions)
    if cfg.parallel_block:
        f, stats = _apply_ffn(cfg, ffn_kind, lp["ffn"], h, serve)
        return x + a + f, stats
    # pin the residual to batch-only sharding at the psum point: without
    # this GSPMD keeps x d_model-sharded and re-gathers it (in f32) for
    # every consumer — ~3 redundant (B,S,D) all-gathers per layer.
    x = constrain_batch(x + a)
    h = apply_norm(cfg, lp["ln2"], x)
    f, stats = _apply_ffn(cfg, ffn_kind, lp["ffn"], h, serve)
    return x + f, stats


def _apply_layer(cfg: ModelConfig, g: LayerGroup, lp: Dict, x, positions,
                 shared: Optional[Dict] = None):
    if g.kind in ("attn", "mla"):
        return _apply_attn_layer(cfg, g.kind, g.ffn, lp, x, positions)
    if g.kind == "shared_attn":
        return _apply_attn_layer(cfg, "attn", "mlp", shared, x, positions)
    if g.kind == "mamba2":
        h = apply_norm(cfg, lp["ln1"], x)
        return x + ssm.mamba2_apply(cfg, lp["mixer"], h), _no_stats()
    if g.kind == "rwkv6":
        h = apply_norm(cfg, lp["ln1"], x)
        tm, _ = ssm.rwkv6_time_mix(cfg, lp["tm"], h)
        x = x + tm
        h = apply_norm(cfg, lp["ln2"], x)
        cm, _ = ssm.rwkv6_channel_mix(cfg, lp["tm"], h)
        return x + cm, _no_stats()
    raise ValueError(g.kind)


def backbone(cfg: ModelConfig, params: Dict, batch: Dict
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens -> final hidden states (B,S,D) + the layers' stats summed
    (``aux``: the total auxiliary loss; the MoE assignment counts)."""
    x = embed_tokens(cfg, params["embed"], batch["tokens"])
    x = merge_visual(cfg, x, batch)
    x = constrain_batch(x)
    if cfg.rwkv is not None:
        x = apply_norm(cfg, params["ln0"], x)
    positions = positions_for(cfg, batch)
    total = _no_stats()

    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    for gi, g in enumerate(layer_groups(cfg)):
        gp = params["layers"][gi]
        if g.kind == "shared_attn":
            def shared_body(x):
                return _apply_layer(cfg, g, {}, x, positions,
                                    shared=params["shared_block"])
            for _ in range(g.count):
                x, stats = (jax.checkpoint(shared_body)(x) if cfg.remat
                            else shared_body(x))
                total = add(total, stats)
            continue

        def body(x, lp):
            y, stats = _apply_layer(cfg, g, lp, x, positions)
            return constrain_batch(y), stats

        if cfg.remat:
            pol = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                   if cfg.remat_policy == "dots"
                   else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=pol)
        x, stats = jax.lax.scan(body, x, gp)
        total = add(total, jax.tree.map(lambda v: v.sum(0), stats))
    x = apply_norm(cfg, params["final_norm"], x)
    return x, total


def loss_and_stats(cfg: ModelConfig, params: Dict, batch: Dict
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The training loss, and the step's MoE counts over all layers
    (``moe_assigned``, ``moe_kept``; empty without MoE layers)."""
    h, stats = backbone(cfg, params, batch)
    xent = chunked_softmax_xent(cfg, params["embed"], params.get("lm_head"),
                                h, batch["labels"], batch.get("loss_mask"))
    counts = {k: stats[k] for k in ("moe_assigned", "moe_kept")} \
        if cfg.moe is not None else {}
    return xent + stats["aux"], counts


def loss(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    return loss_and_stats(cfg, params, batch)[0]


def logits_fn(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    """Full logits — tiny shapes/tests only."""
    h, _ = backbone(cfg, params, batch)
    return lm_head_logits(cfg, params["embed"], params.get("lm_head"), h)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------
def _cache_one(cfg: ModelConfig, kind: str, batch: int, max_len: int, dt) -> Dict:
    if kind in ("attn", "shared_attn"):
        return attn.attn_init_cache(cfg, batch, max_len, dt)
    if kind == "mla":
        return attn.mla_init_cache(cfg, batch, max_len, dt)
    if kind == "mamba2":
        return ssm.mamba2_init_state(cfg, batch, dt)
    if kind == "rwkv6":
        return ssm.rwkv6_init_state(cfg, batch, dt)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> List[Any]:
    """One stacked cache tree per layer group."""
    dt = cfg.compute_jdtype()
    out = []
    for g in layer_groups(cfg):
        one = _cache_one(cfg, g.kind, batch, max_len, dt)
        out.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (g.count,) + x.shape), one))
    return out


def _prefill_layer(cfg: ModelConfig, g: LayerGroup, lp: Dict, x, positions,
                   cache: Dict, shared: Optional[Dict] = None):
    kind = g.kind
    bp = shared if kind == "shared_attn" else lp
    if kind in ("attn", "shared_attn", "mla"):
        h = apply_norm(cfg, bp["ln1"], x)
        pf = attn.mla_prefill if kind == "mla" else attn.attn_prefill
        a, c = pf(cfg, bp["attn"], h, positions, cache)
        if cfg.parallel_block:
            f, _ = _apply_ffn(cfg, "mlp" if kind == "shared_attn" else g.ffn,
                              bp["ffn"], h, serve=True)
            return x + a + f, c
        x = x + a
        h = apply_norm(cfg, bp["ln2"], x)
        f, _ = _apply_ffn(cfg, "mlp" if kind == "shared_attn" else g.ffn,
                          bp["ffn"], h, serve=True)
        return x + f, c
    if kind == "mamba2":
        h = apply_norm(cfg, lp["ln1"], x)
        out = ssm.mamba2_apply(cfg, lp["mixer"], h)
        c = ssm.mamba2_prefill_state(cfg, lp["mixer"], h, cache)
        return x + out, c
    if kind == "rwkv6":
        h = apply_norm(cfg, lp["ln1"], x)
        tm, (last_x, s) = ssm.rwkv6_time_mix(cfg, lp["tm"], h)
        x = x + tm
        h2 = apply_norm(cfg, lp["ln2"], x)
        cm, cm_last = ssm.rwkv6_channel_mix(cfg, lp["tm"], h2)
        return x + cm, {"tm_x": last_x, "wkv": s, "cm_x": cm_last}
    raise ValueError(kind)


def prefill(cfg: ModelConfig, params: Dict, batch: Dict,
            max_len: int) -> Tuple[jax.Array, List[Any]]:
    """Process a prompt of S tokens; return last-position logits and the
    primed cache (max_len slots)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    x = merge_visual(cfg, x, batch)
    if cfg.rwkv is not None:
        x = apply_norm(cfg, params["ln0"], x)
    positions = positions_for(cfg, batch)
    cache0 = init_cache(cfg, B, max_len)
    new_cache: List[Any] = []
    for gi, g in enumerate(layer_groups(cfg)):
        gp = params["layers"][gi]
        cg = cache0[gi]
        if g.kind == "shared_attn":
            cs = []
            for j in range(g.count):
                cj = jax.tree.map(lambda t: t[j], cg)
                x, c = _prefill_layer(cfg, g, {}, x, positions, cj,
                                      shared=params["shared_block"])
                cs.append(c)
            new_cache.append(_stack(cs))
            continue

        def body(x, inp):
            lp, c = inp
            y, c2 = _prefill_layer(cfg, g, lp, x, positions, c)
            return y, c2

        x, cg2 = jax.lax.scan(body, x, (gp, cg))
        new_cache.append(cg2)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head_logits(cfg, params["embed"], params.get("lm_head"), x[:, -1])
    return logits, new_cache


def _decode_layer(cfg: ModelConfig, g: LayerGroup, lp: Dict, x, pos,
                  cache: Dict, shared: Optional[Dict] = None):
    kind = g.kind
    bp = shared if kind == "shared_attn" else lp
    if kind in ("attn", "shared_attn", "mla"):
        h = apply_norm(cfg, bp["ln1"], x)
        dec = attn.mla_decode if kind == "mla" else attn.attn_decode
        a, c = dec(cfg, bp["attn"], h, pos, cache)
        if cfg.parallel_block:
            f, _ = _apply_ffn(cfg, "mlp" if kind == "shared_attn" else g.ffn,
                              bp["ffn"], h, serve=True)
            return x + a + f, c
        x = x + a
        h = apply_norm(cfg, bp["ln2"], x)
        f, _ = _apply_ffn(cfg, "mlp" if kind == "shared_attn" else g.ffn,
                          bp["ffn"], h, serve=True)
        return x + f, c
    if kind == "mamba2":
        h = apply_norm(cfg, lp["ln1"], x)
        a, c = ssm.mamba2_decode(cfg, lp["mixer"], h, cache)
        return x + a, c
    if kind == "rwkv6":
        h = apply_norm(cfg, lp["ln1"], x)
        tm, c = ssm.rwkv6_decode(cfg, lp["tm"], h, cache)
        x = x + tm
        h2 = apply_norm(cfg, lp["ln2"], x)
        cm, c = ssm.rwkv6_channel_decode(cfg, lp["tm"], h2, c)
        return x + cm, c
    raise ValueError(kind)


def decode_step(cfg: ModelConfig, params: Dict, cache: List[Any],
                token: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, List[Any]]:
    """One decode step.  token: (B,), pos: (B,) -> logits (B, V)."""
    x = embed_tokens(cfg, params["embed"], token[:, None])
    if cfg.rwkv is not None:
        x = apply_norm(cfg, params["ln0"], x)
    new_cache: List[Any] = []
    for gi, g in enumerate(layer_groups(cfg)):
        gp = params["layers"][gi]
        cg = cache[gi]
        if g.kind == "shared_attn":
            cs = []
            for j in range(g.count):
                cj = jax.tree.map(lambda t: t[j], cg)
                x, c = _decode_layer(cfg, g, {}, x, pos, cj,
                                     shared=params["shared_block"])
                cs.append(c)
            new_cache.append(_stack(cs))
            continue

        def body(x, inp):
            lp, c = inp
            y, c2 = _decode_layer(cfg, g, lp, x, pos, c)
            return y, c2

        x, cg2 = jax.lax.scan(body, x, (gp, cg))
        new_cache.append(cg2)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head_logits(cfg, params["embed"], params.get("lm_head"), x[:, 0])
    return logits, new_cache
