"""Whisper-style encoder-decoder (audio backbone; conv frontend stubbed).

The batch carries precomputed frame embeddings, ``frames`` of shape
(B, n_audio_ctx, d_model) — the mel-spectrogram conv stem is the modality
frontend and out of scope per the assignment.  Everything downstream is
real: sinusoidal encoder positions, bidirectional encoder self-attention,
causal decoder self-attention + cross-attention, tied LM head.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import attention as attn
from . import mlp as mlpm
from .common import (apply_norm, chunked_softmax_xent, dense_init,
                     embed_tokens, embedding_init, lm_head_logits, norm_init)
from .config import ModelConfig


def sinusoids(length: int, channels: int) -> jax.Array:
    log_timescale = math.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2))
    ang = jnp.arange(length)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


def _xattn_init(cfg: ModelConfig, key) -> Dict:
    return attn.attn_init(cfg, key)


def init(cfg: ModelConfig, rng) -> Dict:
    ed = cfg.enc_dec
    keys = jax.random.split(rng, 2 * cfg.n_layers + ed.n_enc_layers * 2 + 8)
    ki = iter(range(len(keys)))
    p: Dict = {
        "embed": embedding_init(cfg, keys[next(ki)]),
        # learned decoder positions, sized for the largest assigned decoder
        # context (prefill_32k); whisper-tiny's published 448 is a subset.
        "pos_dec": (jax.random.normal(keys[next(ki)], (32768 + 8, cfg.d_model))
                    * 0.01).astype(cfg.param_jdtype()),
        "enc_layers": [], "dec_layers": [],
        "enc_norm": norm_init(cfg), "dec_norm": norm_init(cfg),
    }
    for _ in range(ed.n_enc_layers):
        p["enc_layers"].append({
            "ln1": norm_init(cfg),
            "attn": attn.attn_init(cfg, keys[next(ki)]),
            "ln2": norm_init(cfg),
            "mlp": mlpm.mlp_init(cfg, keys[next(ki)]),
        })
    for _ in range(cfg.n_layers):
        p["dec_layers"].append({
            "ln1": norm_init(cfg),
            "attn": attn.attn_init(cfg, keys[next(ki)]),
            "lnx": norm_init(cfg),
            "xattn": _xattn_init(cfg, keys[next(ki)]),
            "ln2": norm_init(cfg),
            "mlp": mlpm.mlp_init(cfg, keys[next(ki)]),
        })
    return p


def _self_attn(cfg, bp, x, positions, causal):
    h = apply_norm(cfg, bp["ln1"], x)
    return x + attn.attn_apply(cfg, bp["attn"], h, positions, causal=causal)


def _cross_attn(cfg, bp, x, mem_k, mem_v):
    """Pre-projected encoder memory keys/values."""
    h = apply_norm(cfg, bp["lnx"], x)
    q = jnp.einsum("bsd,dhk->bshk", h, bp["xattn"]["wq"].astype(h.dtype))
    if cfg.qkv_bias:
        q = q + bp["xattn"]["bq"].astype(h.dtype)
    o = ops.attention(q.transpose(0, 2, 1, 3), mem_k, mem_v, causal=False,
                      impl=cfg.attn_impl)
    o = o.transpose(0, 2, 1, 3)
    return x + jnp.einsum("bshk,hkd->bsd", o, bp["xattn"]["wo"].astype(h.dtype))


def _mlp(cfg, bp, x):
    h = apply_norm(cfg, bp["ln2"], x)
    return x + mlpm.mlp_apply(cfg, bp["mlp"], h)


def _mem_kv(cfg, bp, mem):
    k = jnp.einsum("btd,dhk->bthk", mem, bp["xattn"]["wk"].astype(mem.dtype))
    v = jnp.einsum("btd,dhk->bthk", mem, bp["xattn"]["wv"].astype(mem.dtype))
    if cfg.qkv_bias:
        k = k + bp["xattn"]["bk"].astype(mem.dtype)
        v = v + bp["xattn"]["bv"].astype(mem.dtype)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def encode(cfg: ModelConfig, params: Dict, frames: jax.Array) -> jax.Array:
    """frames: (B, T_audio, D) precomputed embeddings (conv stub)."""
    B, T, D = frames.shape
    x = frames.astype(cfg.compute_jdtype()) + sinusoids(T, D).astype(cfg.compute_jdtype())[None]
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    for bp in params["enc_layers"]:
        x = _self_attn(cfg, bp, x, positions, causal=False)
        x = _mlp(cfg, bp, x)
    return apply_norm(cfg, params["enc_norm"], x)


def _decoder_embed(cfg, params, tokens, pos0: int = 0):
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    x = x + params["pos_dec"].astype(x.dtype)[pos0 : pos0 + S][None]
    return x


def loss(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    """batch: frames (B,T,D), tokens (B,S), labels (B,S)."""
    mem = encode(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _decoder_embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for bp in params["dec_layers"]:
        def run(x):
            mk, mv = _mem_kv(cfg, bp, mem)
            h = _self_attn(cfg, bp, x, positions, causal=True)
            h = _cross_attn(cfg, bp, h, mk, mv)
            return _mlp(cfg, bp, h)
        x = jax.checkpoint(run)(x) if cfg.remat else run(x)
    x = apply_norm(cfg, params["dec_norm"], x)
    return chunked_softmax_xent(cfg, params["embed"], None, x, batch["labels"],
                                batch.get("loss_mask"))


# -- serving ------------------------------------------------------------------
def init_cache(cfg: ModelConfig, params: Dict, mem: jax.Array,
               max_len: int) -> Dict:
    """Self-attn caches + precomputed cross K/V per decoder layer."""
    B = mem.shape[0]
    dt = cfg.compute_jdtype()
    layers = []
    for bp in params["dec_layers"]:
        mk, mv = _mem_kv(cfg, bp, mem)
        layers.append({
            "self": attn.attn_init_cache(cfg, B, max_len, dt),
            "mem_k": mk, "mem_v": mv,
        })
    return {"layers": layers}


def prefill(cfg: ModelConfig, params: Dict, batch: Dict,
            max_len: int) -> Tuple[jax.Array, Dict]:
    mem = encode(cfg, params, batch["frames"])
    cache = init_cache(cfg, params, mem, max_len)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _decoder_embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for bp, lc in zip(params["dec_layers"], cache["layers"]):
        h = apply_norm(cfg, bp["ln1"], x)
        a, lc["self"] = attn.attn_prefill(cfg, bp["attn"], h, positions, lc["self"])
        x = x + a
        x = _cross_attn(cfg, bp, x, lc["mem_k"], lc["mem_v"])
        x = _mlp(cfg, bp, x)
    x = apply_norm(cfg, params["dec_norm"], x)
    logits = lm_head_logits(cfg, params["embed"], None, x[:, -1])
    return logits, cache


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict, token: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, Dict]:
    B = token.shape[0]
    x = embed_tokens(cfg, params["embed"], token[:, None])
    x = x + jnp.take(params["pos_dec"].astype(x.dtype), pos, axis=0)[:, None]
    new_layers = []
    for bp, lc in zip(params["dec_layers"], cache["layers"]):
        h = apply_norm(cfg, bp["ln1"], x)
        a, self_c = attn.attn_decode(cfg, bp["attn"], h, pos, lc["self"])
        x = x + a
        h = apply_norm(cfg, bp["lnx"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, bp["xattn"]["wq"].astype(h.dtype))
        if cfg.qkv_bias:
            q = q + bp["xattn"]["bq"].astype(h.dtype)
        T = lc["mem_k"].shape[2]
        o = ops.decode_attention(q[:, 0], lc["mem_k"], lc["mem_v"],
                                 jnp.full((B,), T, jnp.int32), impl="ref")
        x = x + jnp.einsum("bhk,hkd->bd", o, bp["xattn"]["wo"].astype(h.dtype))[:, None]
        x = _mlp(cfg, bp, x)
        new_layers.append({"self": self_c, "mem_k": lc["mem_k"], "mem_v": lc["mem_v"]})
    x = apply_norm(cfg, params["dec_norm"], x)
    logits = lm_head_logits(cfg, params["embed"], None, x[:, 0])
    return logits, {"layers": new_layers}
