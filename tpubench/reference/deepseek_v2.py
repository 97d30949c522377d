"""Plain float32 reference of a DeepSeek-V2 decoder (multi-head latent
attention with YaRN rotary, a leading dense layer, then layers of routed
and shared experts) for one expert-parallel rank's share, and of AdamW.

It follows the configuration file's published keys, its ``published``
block (the router's width) and its ``assumed`` block (which experts this
rank holds, capacity, optimizer), and holds parameters in the layout of
the system under test (layer groups stacked, ``[D, heads, head_dim]``
projections), so that the same seeded weights can be handed to both.  It
imports nothing of the system.  Every matrix product runs at
``Precision.HIGHEST``; ``quant="fp8"`` (the control that has to come out
as not correct) and ``quant="bf16"`` (a witness of the configuration's own
precision) round every product's operands as ``decoder_lm`` does.

Semantics (arXiv:2405.04434 and the published modelling code), with the
departures the configuration file lists:

* pre-norm blocks, RMSNorm in float32;
* attention without query compression (``q_lora_rank`` null): q = x Wq
  split into ``qk_nope`` and ``qk_rope`` parts; one latent of
  ``kv_lora_rank`` (RMS-normed) and one shared rotary key come from
  ``x Wkv_down``; keys' ``nope`` part and values come up from the latent;
  scores over q·k of ``qk_nope + qk_rope`` at softmax scale
  (qk head)^-1/2 times mscale(factor, mscale_all_dim)^2; values at their
  own head size;
* YaRN rotary (on the two halves) over the ``qk_rope`` part: inverse
  frequencies blended between interpolated (over ``factor``) and
  extrapolated by a linear ramp across the correction range of
  ``beta_fast``/``beta_slow`` at ``original_max_position_embeddings``;
  cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim);
* routed experts: softmax router over all the published experts, greedy
  top-k, gates raw (``norm_topk_prob`` false) times
  ``routed_scaling_factor``; capacity ``int(group * k * capacity_factor /
  router width)`` per group of ``group_tokens`` tokens in row-major order,
  positions token by token (k inner), assignments past capacity dropped.
  Only the experts this rank holds add to the output; assignments to the
  others add nothing.  The Switch loss is taken over the whole router,
  per group, averaged, summed over layers;
* shared experts: one SwiGLU of ``n_shared_experts * moe_intermediate_size``;
* mean next-token cross-entropy over the (sliced) vocabulary, with an
  untied output head of ``[vocab, D]``.

Attention runs in blocks of queries, and every block, layer and expert
group is recomputed in the backward pass, so that the 8k-token gradients
fit one chip once the system's state is freed.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.decoder_lm import _is_spec, _mm, _rms, _xent, adamw, leaf_norms


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


class Dims:
    """The sizes the reference needs, from a configuration file."""

    def __init__(self, cfg: Dict[str, Any]):
        a = cfg.get("assumed", {})
        if cfg.get("q_lora_rank"):
            raise ValueError("this reference models no query compression")
        self.D = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.kv_lora = int(cfg["kv_lora_rank"])
        self.nope = int(cfg["qk_nope_head_dim"])
        self.rope = int(cfg["qk_rope_head_dim"])
        self.vd = int(cfg["v_head_dim"])
        self.L = int(cfg["num_hidden_layers"])
        self.dense = int(cfg["first_k_dense_replace"])
        self.F = int(cfg["intermediate_size"])
        self.Fe = int(cfg["moe_intermediate_size"])
        self.Fs = self.Fe * int(cfg["n_shared_experts"])
        self.E = int(cfg["published"]["n_routed_experts"])   # router width
        self.Eh = int(cfg["n_routed_experts"])                # held here
        self.e0 = int(a.get("expert_offset", 0))
        self.K = int(cfg["num_experts_per_tok"])
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.routed_scale = float(cfg["routed_scaling_factor"])
        self.V = int(cfg["vocab_size"])
        r = int(a.get("vocab_round", 256))
        self.Vp = (self.V + r - 1) // r * r
        self.eps = float(cfg.get("rms_norm_eps", 1e-6))
        self.theta = float(cfg.get("rope_theta", 10000.0))
        self.yarn = cfg.get("rope_scaling")
        self.cf = float(a.get("capacity_factor", 1.0))
        self.group = int(a.get("group_tokens", 1024))
        self.aux_w = float(a.get("aux_loss_weight", 0.0))
        self.param_dtype = jnp.dtype(a.get("param_dtype", "bfloat16"))
        self.opt = dict(a.get("optimizer", {}))
        self.scale = (self.nope + self.rope) ** -0.5
        if self.yarn and self.yarn.get("mscale_all_dim"):
            self.scale *= _mscale(float(self.yarn["factor"]),
                                  float(self.yarn["mscale_all_dim"])) ** 2


# -- weights -----------------------------------------------------------------
def _param_specs(d: Dims) -> Dict[str, Any]:
    """(shape, std or 'ones', dtype) per leaf, in the system's layout: one
    stacked group for the dense layers, one for the MoE layers."""
    D, H, pd = d.D, d.H, d.param_dtype

    def group(n: int, moe: bool) -> Dict[str, Any]:
        g: Dict[str, Any] = {
            "ln1": {"scale": ((n, D), "ones", pd)},
            "ln2": {"scale": ((n, D), "ones", pd)},
            "attn": {
                "wq": ((n, D, H, d.nope + d.rope), 1 / math.sqrt(D), pd),
                "kv_down": ((n, D, d.kv_lora + d.rope), 1 / math.sqrt(D), pd),
                "kv_norm": {"scale": ((n, d.kv_lora), "ones", pd)},
                "k_up": ((n, d.kv_lora, H, d.nope), 1 / math.sqrt(d.kv_lora), pd),
                "v_up": ((n, d.kv_lora, H, d.vd), 1 / math.sqrt(d.kv_lora), pd),
                "wo": ((n, H, d.vd, D), 1 / math.sqrt(H * d.vd), pd),
            },
        }
        if moe:
            g["ffn"] = {
                "router": ((n, D, d.E), 1 / math.sqrt(D), jnp.dtype(jnp.float32)),
                "wi": ((n, d.Eh, D, d.Fe), 1 / math.sqrt(D), pd),
                "wg": ((n, d.Eh, D, d.Fe), 1 / math.sqrt(D), pd),
                "wo": ((n, d.Eh, d.Fe, D), 1 / math.sqrt(d.Fe), pd),
                "shared": {
                    "wi": ((n, D, d.Fs), 1 / math.sqrt(D), pd),
                    "wg": ((n, D, d.Fs), 1 / math.sqrt(D), pd),
                    "wo": ((n, d.Fs, D), 1 / math.sqrt(d.Fs), pd),
                },
            }
        else:
            g["ffn"] = {
                "wi": ((n, D, d.F), 1 / math.sqrt(D), pd),
                "wg": ((n, D, d.F), 1 / math.sqrt(D), pd),
                "wo": ((n, d.F, D), 1 / math.sqrt(d.F), pd),
            }
        return g

    layers = []
    if d.dense:
        layers.append(group(d.dense, False))
    if d.L > d.dense:
        layers.append(group(d.L - d.dense, True))
    return {
        "embed": {"tok": ((d.Vp, D), 0.02, pd)},
        "final_norm": {"scale": ((D,), "ones", pd)},
        "layers": layers,
        "lm_head": ((d.Vp, D), 1 / math.sqrt(D), pd),
    }


def init_params(cfg: Dict[str, Any], rng) -> Any:
    """Seeded weights in the parameter dtype (jittable; one key per leaf)."""
    specs = _param_specs(Dims(cfg))
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    out = []
    for i, (shape, std, dt) in enumerate(leaves):
        if std == "ones":
            out.append(jnp.ones(shape, dt))
        else:
            k = jax.random.fold_in(rng, i)
            out.append((jax.random.normal(k, shape, jnp.float32) * std).astype(dt))
    return jax.tree_util.tree_unflatten(treedef, out)


# -- attention -----------------------------------------------------------------
def _inv_freq(d: Dims) -> jax.Array:
    dim = d.rope
    extra = 1.0 / (d.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    y = d.yarn
    if not y:
        return extra
    base, orig = d.theta, float(y["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(y["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(y["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inter = extra / float(y["factor"])
    extra_mask = 1.0 - ramp
    return inter * (1.0 - extra_mask) + extra * extra_mask


def _rope(d: Dims, x):
    """x: (S, ..., rope) at positions 0..S-1, rotation on the two halves."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * _inv_freq(d)  # (S, r/2)
    ms = 1.0
    if d.yarn:
        f = float(d.yarn["factor"])
        ms = _mscale(f, float(d.yarn.get("mscale", 1.0))) \
            / _mscale(f, float(d.yarn.get("mscale_all_dim", 0.0)))
    shape = (S,) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * ms).reshape(shape)
    sin = (jnp.sin(ang) * ms).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_row(d: Dims, p: Dict, h, quant, block: int = 1024):
    """One sequence: h (S, D) -> (S, D), queries in blocks of ``block``."""
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, p["wq"], quant)
    q_nope, q_pe = q[..., : d.nope], _rope(d, q[..., d.nope:])
    ckv = _mm("sd,dl->sl", h, p["kv_down"], quant)
    lat = _rms(ckv[:, : d.kv_lora], p["kv_norm"]["scale"], d.eps)
    k_pe = _rope(d, ckv[:, d.kv_lora:])                            # (S, rope)
    k_nope = _mm("sl,lhk->shk", lat, p["k_up"], quant)
    v = _mm("sl,lhk->shk", lat, p["v_up"], quant)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (S, d.H, d.rope))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    bq = math.gcd(S, block)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(qf, i * bq, bq, 0)        # (bq, H, k)
        s = _mm("qhk,thk->hqt", qb, k, quant) * d.scale
        qpos = i * bq + jnp.arange(bq)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return _mm("hqt,thk->qhk", pr, v, quant)                    # (bq, H, vd)

    o = jax.lax.map(jax.checkpoint(one), jnp.arange(S // bq))
    o = o.reshape(S, d.H, d.vd)
    return _mm("shk,hkd->sd", o, p["wo"], quant)


# -- feed-forward --------------------------------------------------------------
def _swiglu(p: Dict, h, quant, spec_in: str, spec_out: str):
    g = jax.nn.silu(_mm(spec_in, h, p["wi"], quant))
    return _mm(spec_out, g * _mm(spec_in, h, p["wg"], quant), p["wo"], quant)


def _moe_group(d: Dims, p: Dict, x, quant):
    """One group of tokens: x (T, D) -> (y (T, D), aux)."""
    T = x.shape[0]
    E, K, Eh = d.E, d.K, d.Eh
    C = max(1, int(T * K * d.cf / E))
    logits = _mm("td,de->te", x, p["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                       # (T, K)
    if d.norm_topk:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    gate_w = gate_w * d.routed_scale
    onehot = jax.nn.one_hot(gate_i, E, dtype=jnp.float32)         # (T, K, E)
    aux = jnp.sum(probs.mean(0) * onehot.sum(1).mean(0)) * E * d.aux_w
    local = gate_i - d.e0
    here = (local >= 0) & (local < Eh)                             # held here
    flat = jax.nn.one_hot(jnp.where(here, local, Eh), Eh,
                          dtype=jnp.float32).reshape(T * K, Eh)
    pos = ((jnp.cumsum(flat, 0) - flat) * flat).sum(-1).reshape(T, K)
    keep = here & (pos < C)
    slot = jnp.where(keep, pos.astype(jnp.int32), C)               # C: none
    e = jnp.where(here, local, 0)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K))
    expert_in = jnp.zeros((Eh, C + 1, x.shape[1]), x.dtype)
    expert_in = expert_in.at[e, slot].set(x[tok])[:, :C]           # (Eh, C, D)
    out = _swiglu(p, expert_in, quant, "ecd,edf->ecf", "ecf,efd->ecd")
    out = jnp.concatenate([out, jnp.zeros_like(out[:, :1])], 1)   # slot C = 0
    picked = out[e, slot]                                          # (T, K, D)
    y = jnp.sum(picked * (gate_w * keep)[..., None], axis=1)
    return y, aux


def _moe(d: Dims, p: Dict, h, quant):
    """h (B, S, D) -> (y, aux): this rank's routed experts plus the shared."""
    B, S, D = h.shape
    T = B * S
    gt = min(d.group, T)
    if T % gt:
        gt = math.gcd(T, gt)
    ys, auxs = jax.lax.map(jax.checkpoint(lambda xx: _moe_group(d, p, xx, quant)),
                           h.reshape(T // gt, gt, D))
    y = ys.reshape(B, S, D) + _swiglu(p["shared"], h, quant,
                                      "bsd,df->bsf", "bsf,fd->bsd")
    return y, auxs.mean()


# -- model ---------------------------------------------------------------------
def loss(cfg: Dict[str, Any], params: Any, tokens, labels,
         quant: Optional[str] = None):
    """Training loss of ``params`` (float32 tree) on one batch."""
    d = Dims(cfg)
    x = params["embed"]["tok"][tokens]
    aux_total = jnp.zeros((), jnp.float32)

    def layer(moe):
        def f(x, p):
            h = _rms(x, p["ln1"]["scale"], d.eps)
            x = x + jax.lax.map(jax.checkpoint(
                lambda hh: _attention_row(d, p["attn"], hh, quant)), h)
            h = _rms(x, p["ln2"]["scale"], d.eps)
            if moe:
                y, aux = _moe(d, p["ffn"], h, quant)
            else:
                y = _swiglu(p["ffn"], h, quant, "bsd,df->bsf", "bsf,fd->bsd")
                aux = jnp.zeros((), jnp.float32)
            return x + y, aux
        return jax.checkpoint(f)

    groups = ([False] if d.dense else []) + ([True] if d.L > d.dense else [])
    for moe, gp in zip(groups, params["layers"]):
        x, auxs = jax.lax.scan(layer(moe), x, gp)
        aux_total = aux_total + auxs.sum()
    x = _rms(x, params["final_norm"]["scale"], d.eps)
    return _xent(d, params["lm_head"], x, labels, quant) + aux_total


def train_readings(cfg: Dict[str, Any], seed_key, batches: List[Tuple[Any, Any]],
                   quant: Optional[str] = None,
                   grad1_of: Optional[List[np.ndarray]] = None,
                   keep_grad1: bool = False) -> Dict[str, Any]:
    """Three AdamW steps from the seeded weights on ``batches``, read as
    ``decoder_lm.train_readings`` reads them (each step's loss, per-leaf
    norms of the first gradient and of the change after three steps, and
    of the first gradient's difference from ``grad1_of``).  The starting
    weights are rebuilt from the seed for the change, not kept."""
    d = Dims(cfg)
    o = d.opt
    f32 = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                         init_params(cfg, k)))
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(cfg, p, t, l, quant)))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw(o, p, g, m, v, t),
                      donate_argnums=(0, 2, 3))
    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    p = f32(seed_key)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    out: Dict[str, Any] = {}
    losses = []
    for i, (tok, lab) in enumerate(batches):
        lval, g = grad_fn(p, tok, lab)
        losses.append(float(lval))
        if i == 0:
            out["grad1"] = np.asarray(leaf_norms(g))
            if grad1_of is not None:      # leaf by leaf, to hold one at a time
                out["grad1_diff"] = np.asarray([
                    float(diff_norm(x, jnp.asarray(y, jnp.float32)))
                    for x, y in zip(jax.tree.leaves(g), grad1_of)])
            if keep_grad1:
                out["grad1_leaves"] = [np.asarray(x) for x in jax.tree.leaves(g)]
        p, m, v = step_fn(p, g, m, v, jnp.asarray(i + 1, jnp.int32))
        del g
    del m, v
    out["delta3"] = np.asarray(jax.jit(
        lambda a, k: leaf_norms(jax.tree.map(jnp.subtract, a, f32(k))))(
            p, seed_key))
    out["losses"] = np.asarray(losses)
    return out

