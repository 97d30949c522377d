"""Attention blocks: GQA/MQA (with RoPE / M-RoPE) and DeepSeek-V2 MLA.

Each block provides ``init``, ``apply`` (full-sequence, causal) and
``decode`` (one token against a mutable KV cache).  Caches are plain dicts
of arrays; sharding is attached externally.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .common import (apply_mrope, apply_norm, apply_rope, constrain_dims,
                     dense_init, norm_init)
from .config import ModelConfig, yarn_mscale


# ---------------------------------------------------------------------------
# standard GQA attention
# ---------------------------------------------------------------------------
def attn_init(cfg: ModelConfig, key) -> Dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.param_jdtype()
    p = {
        "wq": dense_init(ks[0], D, (H, hd), dt),
        "wk": dense_init(ks[1], D, (KV, hd), dt),
        "wv": dense_init(ks[2], D, (KV, hd), dt),
        "wo": dense_init(ks[3], H * hd, (D,), dt).reshape(H, hd, D),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
    return p


def _qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions) -> Tuple:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.rope_type == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_type == "standard":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    # heads on "model"; if the head count does not divide (28-head qwen,
    # MQA), q falls back to SEQUENCE sharding (context parallelism) and
    # k/v stay replicated over model.  Never shard head_dim: it is the
    # attention contraction dim, and sharding it makes GSPMD psum
    # (B,H,S,block) logits per kv block — ~6 TiB/device for qwen at a
    # 32k prefill on a 16x16 mesh.
    q = constrain_dims(q, {0: "dp", 2: "model", 1: "model"})
    k = constrain_dims(k, {0: "dp", 2: "model"})
    v = constrain_dims(v, {0: "dp", 2: "model"})
    return q, k, v


def attn_apply(cfg: ModelConfig, p: Dict, x: jax.Array, positions,
               causal: bool = True) -> jax.Array:
    """x: (B,S,D) -> (B,S,D), full-sequence causal attention."""
    q, k, v = _qkv(cfg, p, x, positions)
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal,
                      impl=cfg.attn_impl)
    o = o.transpose(0, 2, 1, 3)  # (B,S,H,hd)
    o = constrain_dims(o, {0: "dp", 2: "model", 1: "model"})
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def attn_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype) -> Dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, KV, hd), dtype),
        "v": jnp.zeros((batch, max_len, KV, hd), dtype),
    }


def attn_prefill(cfg: ModelConfig, p: Dict, x: jax.Array, positions,
                 cache: Dict) -> Tuple[jax.Array, Dict]:
    q, k, v = _qkv(cfg, p, x, positions)
    S = x.shape[1]
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1),
    }
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True, impl=cfg.attn_impl)
    o = o.transpose(0, 2, 1, 3)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype)), cache


def attn_decode(cfg: ModelConfig, p: Dict, x: jax.Array, pos: jax.Array,
                cache: Dict) -> Tuple[jax.Array, Dict]:
    """x: (B,1,D); pos: (B,) current position; in-cache attention."""
    B = x.shape[0]
    if cfg.rope_type == "mrope":
        positions = jnp.broadcast_to(pos[None, :, None], (3, B, 1))
    else:
        positions = pos[:, None]
    q, k, v = _qkv(cfg, p, x, positions)
    ck = jax.vmap(lambda c, upd, i: jax.lax.dynamic_update_slice_in_dim(c, upd, i, 0))(
        cache["k"], k.astype(cache["k"].dtype), pos)
    cv = jax.vmap(lambda c, upd, i: jax.lax.dynamic_update_slice_in_dim(c, upd, i, 0))(
        cache["v"], v.astype(cache["v"].dtype), pos)
    o = ops.decode_attention(q[:, 0], ck.transpose(0, 2, 1, 3),
                             cv.transpose(0, 2, 1, 3), pos + 1,
                             impl=cfg.attn_impl if cfg.attn_impl != "ref" else "ref")
    o = o[:, None]  # (B,1,H,hd)
    return (jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype)),
            {"k": ck, "v": cv})


# ---------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention
# ---------------------------------------------------------------------------
def mla_init(cfg: ModelConfig, key) -> Dict:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    dt = cfg.param_jdtype()
    ks = jax.random.split(key, 8)
    qk_head = m.qk_nope + m.qk_rope
    if m.q_lora:
        q = {"q_down": dense_init(ks[0], D, (m.q_lora,), dt),
             "q_norm": norm_init(cfg, m.q_lora),
             "q_up": dense_init(ks[1], m.q_lora, (H, qk_head), dt)}
    else:
        q = {"wq": dense_init(ks[0], D, (H, qk_head), dt)}
    return {
        **q,
        "kv_down": dense_init(ks[2], D, (m.kv_lora + m.qk_rope,), dt),
        "kv_norm": norm_init(cfg, m.kv_lora),
        "k_up": dense_init(ks[3], m.kv_lora, (H, m.qk_nope), dt),
        "v_up": dense_init(ks[4], m.kv_lora, (H, m.v_head), dt),
        "wo": dense_init(ks[5], H * m.v_head, (D,), dt).reshape(H, m.v_head, D),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: (qk head size)^-1/2, times mscale(factor,
    mscale_all_dim)^2 under YaRN."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions):
    m = cfg.mla
    if m.q_lora:
        cq = jnp.einsum("bsd,dl->bsl", x, p["q_down"].astype(x.dtype))
        cq = apply_norm(cfg, p["q_norm"], cq)
        q = jnp.einsum("bsl,lhk->bshk", cq, p["q_up"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = q[..., : m.qk_nope], q[..., m.qk_nope:]
    ckv_full = jnp.einsum("bsd,dl->bsl", x, p["kv_down"].astype(x.dtype))
    ckv, k_pe = ckv_full[..., : m.kv_lora], ckv_full[..., m.kv_lora:]
    ckv = apply_norm(cfg, p["kv_norm"], ckv)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]
    q_nope = constrain_dims(q_nope, {0: "dp", 2: "model"})
    q_pe = constrain_dims(q_pe, {0: "dp", 2: "model"})
    return q_nope, q_pe, ckv, k_pe


def mla_apply(cfg: ModelConfig, p: Dict, x: jax.Array, positions,
              causal: bool = True) -> jax.Array:
    m = cfg.mla
    q_nope, q_pe, ckv, k_pe = _mla_qkv(cfg, p, x, positions)
    k_nope = jnp.einsum("bsl,lhk->bshk", ckv, p["k_up"].astype(x.dtype))
    v = jnp.einsum("bsl,lhk->bshk", ckv, p["v_up"].astype(x.dtype))
    k_nope = constrain_dims(k_nope, {0: "dp", 2: "model"})
    v = constrain_dims(v, {0: "dp", 2: "model"})
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_pe[:, :, None, :],
                                          k_nope.shape[:3] + (m.qk_rope,))], -1)
    # v at its own head size (v_head, under the qk head size)
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=causal,
                      scale=mla_scale(cfg), impl=cfg.attn_impl)
    o = o.transpose(0, 2, 1, 3)
    o = constrain_dims(o, {0: "dp", 2: "model"})
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    m = cfg.mla
    # the MLA trick: cache ONLY the compressed latent + shared rope key —
    # (kv_lora + qk_rope) per token instead of 2*H*hd.
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora), dtype),
            "kpe": jnp.zeros((batch, max_len, m.qk_rope), dtype)}


def mla_prefill(cfg: ModelConfig, p: Dict, x: jax.Array, positions,
                cache: Dict) -> Tuple[jax.Array, Dict]:
    q_nope, q_pe, ckv, k_pe = _mla_qkv(cfg, p, x, positions)
    cache = {
        "ckv": jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), 0, 1),
        "kpe": jax.lax.dynamic_update_slice_in_dim(
            cache["kpe"], k_pe.astype(cache["kpe"].dtype), 0, 1),
    }
    out = mla_apply(cfg, p, x, positions)  # recompute path for prefill
    return out, cache


def mla_decode(cfg: ModelConfig, p: Dict, x: jax.Array, pos: jax.Array,
               cache: Dict) -> Tuple[jax.Array, Dict]:
    """Latent-space decode: queries are projected INTO the compressed space
    (absorbed k_up) so attention runs against the (kv_lora+rope) cache
    directly — the MLA serving trick."""
    B = x.shape[0]
    positions = pos[:, None]
    q_nope, q_pe, ckv_new, kpe_new = _mla_qkv(cfg, p, x, positions)
    ckv_c = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos)
    kpe_c = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))(
        cache["kpe"], kpe_new.astype(cache["kpe"].dtype), pos)
    # absorb k_up into q:   q_lat = q_nope @ k_up^T  -> (B,1,H,kv_lora)
    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["k_up"].astype(x.dtype))
    scale = mla_scale(cfg)
    T = ckv_c.shape[1]
    logits = (jnp.einsum("bhl,btl->bht", q_lat[:, 0], ckv_c)
              + jnp.einsum("bhk,btk->bht", q_pe[:, 0], kpe_c)) * scale
    mask = jnp.arange(T)[None, None, :] <= pos[:, None, None]
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bht,btl->bhl", w, ckv_c)          # (B,H,kv_lora)
    o = jnp.einsum("bhl,lhk->bhk", ctx, p["v_up"].astype(x.dtype))
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x.dtype))[:, None]
    return out, {"ckv": ckv_c, "kpe": kpe_c}
