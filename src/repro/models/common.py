"""Shared layers: norms, RoPE / M-RoPE, embeddings, chunked LM loss."""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig, YarnScaling, yarn_mscale


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, out_shape, dtype) -> jax.Array:
    """Truncated-normal fan-in init for a (in_dim, *out) weight."""
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, *out_shape))
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def norm_init(cfg: ModelConfig, d: Optional[int] = None) -> Dict:
    d = d if d is not None else cfg.d_model
    p = {"scale": jnp.zeros(d, cfg.param_jdtype()) if cfg.norm_unit_offset
         else jnp.ones(d, cfg.param_jdtype())}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(d, cfg.param_jdtype())
    return p


def apply_norm(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + cfg.norm_eps)
        scale = p["scale"].astype(jnp.float32)
        if cfg.norm_unit_offset:
            scale = scale + 1.0
        y = y * scale
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(dim: int, theta: float,
               scaling: Optional[YarnScaling] = None) -> jax.Array:
    """Inverse frequencies of a ``dim``-wide rotary part; with YaRN, the
    slow ones (past the correction range) are interpolated (divided by
    ``factor``), the fast ones (before it) kept, and a linear ramp blends
    the two across it."""
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return extra
    y = scaling

    def corr_dim(rotations: float) -> float:
        return (dim * math.log(y.original_max_position_embeddings
                                / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def rope_mscale(scaling: Optional[YarnScaling]) -> float:
    """The factor YaRN puts on cos and sin (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[YarnScaling] = None) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) int."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta, scaling)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    ms = rope_mscale(scaling)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if ms != 1.0:
        cos, sin = cos * ms, sin * ms
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    ``positions``: (3, B, S) — temporal / height / width position ids (all
    equal for text tokens).  The rotary dim is split into three sections
    (in half-dim units), each rotated by its own position stream.
    """
    D = x.shape[-1]
    half = D // 2
    assert sum(sections) == half, "mrope sections must sum to head_dim/2"
    freqs = rope_freqs(D, theta)  # (half,)
    # pick the position stream per frequency-section
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=half)
    pos3 = positions.astype(jnp.float32)  # (3,B,S)
    # gather: for each frequency index f, use positions[sec_id[f]]
    ang = pos3[sec_id.astype(jnp.int32), :, :]  # (half, B, S) -- advanced index on axis 0
    ang = jnp.moveaxis(ang, 0, -1) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def positions_for(cfg: ModelConfig, batch: Dict) -> jax.Array:
    """Standard (B,S) or M-RoPE (3,B,S) position ids from the batch."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.rope_type == "mrope":
        if "positions" in batch:
            return batch["positions"]
        p = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return jnp.broadcast_to(p[None], (3, B, S))
    if "positions" in batch:
        return batch["positions"]
    return jnp.broadcast_to(jnp.arange(S)[None], (B, S))


# ---------------------------------------------------------------------------
# embeddings + chunked LM loss
# ---------------------------------------------------------------------------
def embedding_init(cfg: ModelConfig, key) -> Dict:
    p = {"tok": embed_init(key, cfg.padded_vocab, cfg.d_model, cfg.param_jdtype())}
    return p


def embed_tokens(cfg: ModelConfig, emb: Dict, tokens: jax.Array) -> jax.Array:
    x = emb["tok"].astype(cfg.compute_jdtype())[tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def merge_visual(cfg: ModelConfig, x: jax.Array, batch: Dict) -> jax.Array:
    """Qwen2-VL stub: splice precomputed patch embeddings over the first
    ``n_img`` token slots (the modality frontend is out of scope)."""
    if not cfg.visual_stub or "visual_embeds" not in batch:
        return x
    ve = batch["visual_embeds"].astype(x.dtype)  # (B, n_img, D)
    n = ve.shape[1]
    return jnp.concatenate([ve, x[:, n:]], axis=1)


def lm_head_logits(cfg: ModelConfig, emb: Dict, out_w: Optional[jax.Array],
                   h: jax.Array) -> jax.Array:
    w = emb["tok"] if cfg.tie_embeddings or out_w is None else out_w
    logits = jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                        w.astype(jnp.float32))
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    # mask padded vocab rows
    if cfg.padded_vocab != cfg.vocab_size:
        neg = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e30, jnp.float32)
        logits = logits.at[..., cfg.vocab_size:].set(neg)
    return logits


def chunked_softmax_xent(cfg: ModelConfig, emb: Dict, out_w: Optional[jax.Array],
                         h: jax.Array, labels: jax.Array,
                         mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token loss without materializing (B,S,V) logits.

    Scans over sequence chunks; each chunk computes (B,C,V) logits, its
    log-sum-exp and the label logit, then discards them.  With V up to
    256 k this is the difference between fitting and not fitting.

    Differentiated, the same scan also computes the gradient: the loss is
    the step's terminal scalar, so a chunk's ``d loss / d logits`` is known
    once its log-sum-exp is.  The VJP keeps only ``d loss / d h`` (B,S,D,
    in h's dtype) and ``d loss / d w`` (V,D, float32), never the chunks'
    logits, and the backward scales them by the incoming cotangent.
    """
    B, S, D = h.shape
    C = min(cfg.loss_chunk, S)
    if S % C:
        raise ValueError("seq len must divide loss_chunk")
    w = emb["tok"] if cfg.tie_embeddings or out_w is None else out_w
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    spec = _XentSpec(C, cfg.vocab_size, cfg.padded_vocab,
                     float(cfg.logit_softcap))
    return _xent(spec, h, w, labels, mask)


class _XentSpec(NamedTuple):
    chunk: int
    vocab: int
    padded_vocab: int
    softcap: float


def _by_chunk(spec: _XentSpec, x: jax.Array) -> jax.Array:
    """(B,S,...) -> (B,nchunks,C,...); the scans index axis 1 in place."""
    B, S = x.shape[:2]
    return x.reshape(B, S // spec.chunk, spec.chunk, *x.shape[2:])


def _chunk_logits(spec: _XentSpec, hh: jax.Array, wf: jax.Array):
    """(B,C,V) float32 logits of one chunk, softcapped and with padded
    vocab rows at -1e30; and the softcap's tanh (None without one)."""
    logits = jnp.einsum("bcd,vd->bcv", hh.astype(jnp.float32), wf)
    logits = constrain_dims(logits, {0: "dp", 2: "model"})
    t = None
    if spec.softcap > 0:
        t = jnp.tanh(logits / spec.softcap)
        logits = t * spec.softcap
    if spec.padded_vocab != spec.vocab:
        pad_mask = jnp.arange(spec.padded_vocab) < spec.vocab
        logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
    return logits, t


def _chunk_nll(logits: jax.Array, lab: jax.Array, m: jax.Array):
    """Masked per-token NLL (B,C) and the log-sum-exp it used."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab_logit = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    return (lse - lab_logit) * m, lse


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _xent(spec: _XentSpec, h, w, labels, mask):
    wf = w.astype(jnp.float32)
    hc, lc, mc = (_by_chunk(spec, x) for x in (h, labels, mask))

    def chunk_loss(total, i):
        logits, _ = _chunk_logits(spec, hc[:, i], wf)
        nll, _ = _chunk_nll(logits, lc[:, i], mc[:, i])
        return total + nll.sum(), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                            jnp.arange(hc.shape[1]))
    return total / jnp.maximum(mask.sum(), 1.0)


def _xent_fwd(spec: _XentSpec, h, w, labels, mask):
    wf = w.astype(jnp.float32)
    hc, lc, mc = (_by_chunk(spec, x) for x in (h, labels, mask))
    denom = jnp.maximum(mask.sum(), 1.0)

    def chunk_loss_grad(carry, i):
        total, dh, dw = carry
        hh, lab, m = hc[:, i], lc[:, i], mc[:, i]
        logits, t = _chunk_logits(spec, hh, wf)
        nll, lse = _chunk_nll(logits, lab, m)
        onehot = jnp.arange(spec.padded_vocab) == lab[..., None]
        dlogits = ((jnp.exp(logits - lse[..., None]) - onehot)
                   * (m / denom)[..., None])
        if t is not None:
            dlogits = dlogits * (1.0 - t * t)
        dlogits = constrain_dims(dlogits, {0: "dp", 2: "model"})
        dh = dh.at[:, i].set(
            jnp.einsum("bcv,vd->bcd", dlogits, wf).astype(h.dtype))
        dw = dw + jnp.einsum("bcv,bcd->vd", dlogits, hh.astype(jnp.float32))
        return (total + nll.sum(), dh, dw), None

    (total, dh, dw), _ = jax.lax.scan(
        chunk_loss_grad,
        (jnp.zeros((), jnp.float32), jnp.zeros_like(hc),
         jnp.zeros(w.shape, jnp.float32)),
        jnp.arange(hc.shape[1]))
    return total / denom, (dh.reshape(h.shape), dw, w)


def _xent_bwd(spec: _XentSpec, res, g):
    dh, dw, w = res
    return ((g * dh.astype(jnp.float32)).astype(dh.dtype),
            (g * dw).astype(w.dtype), None, None)


_xent.defvjp(_xent_fwd, _xent_bwd)


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain_dims(x: jax.Array, assignments: Dict[int, str]) -> jax.Array:
    """Pin activation dims to mesh axes (no-op outside a mesh context).

    ``assignments`` maps dim -> role, role in {"dp", "model"}.  "dp" is all
    data axes (("pod","data") on a multi-pod mesh); "model" shards hidden
    activation dims Megatron-style.  A dim whose size does not divide the
    axis is silently skipped, so the same model code works for MQA (kv=1),
    28-head attention, 40-expert MoE, etc.

    Without these anchors GSPMD tends to resolve ambiguous einsum
    shardings by replicating the tensor-parallel dim, which multiplies the
    per-device FLOPs by the model axis's size.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    from jax.sharding import PartitionSpec

    spec = [None] * x.ndim
    used = set()
    for dim, role in assignments.items():
        d = dim % x.ndim
        if role == "dp":
            ax = _dp_axes(mesh)
            # fallback chain: all data axes, then progressively fewer
            candidates = [ax[:k] for k in range(len(ax), 0, -1)]
        else:
            if role not in mesh.axis_names:
                continue
            candidates = [(role,)]
        for names in candidates:
            if not names or any(a in used for a in names):
                continue
            size = 1
            for a in names:
                size *= mesh.shape[a]
            if size > 1 and x.shape[d] % size == 0:
                spec[d] = names if len(names) > 1 else names[0]
                used.update(names)
                break
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Pin the leading batch dim to the data axes (block-boundary anchor)."""
    return constrain_dims(x, {0: "dp"})


def constrain_hidden(x: jax.Array, model_dim: int = -1) -> jax.Array:
    """Batch on data axes + a hidden (ffn/heads/vocab) dim on "model"."""
    return constrain_dims(x, {0: "dp", model_dim: "model"})


def act_fn(name: str):
    if name in ("silu", "swiglu"):
        return jax.nn.silu
    if name in ("gelu", "geglu", "gelu_mlp"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)
