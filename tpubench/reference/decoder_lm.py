"""Plain float32 reference of a decoder-only LM with grouped-query attention
and a dense or mixture-of-experts feed-forward, and of AdamW.

It follows the configuration file's published keys and its ``assumed``
block, and holds parameters in the layout the system under test uses
(stacked layers, ``[D, heads, head_dim]`` projections), so the same
seeded weights can be handed to both.  It imports nothing of the system.
Every matrix product runs at ``Precision.HIGHEST``.  ``quant="fp8"`` is the
control that has to come out as not correct: every product computes in
fp8 as fp8 training does, its operands rounded to float8_e4m3 and, in the
backward pass, its incoming gradient rounded to float8_e5m2, each tensor
with a scale of its own.  ``quant="bf16"`` rounds the same to bfloat16,
the precision the configuration states: a witness of what that rounding
alone does to the readings.

Semantics, as the system under test defines them:

* pre-norm blocks (RMSNorm in float32), rotary embedding on halves,
  causal softmax attention at ``1/sqrt(head_dim)``;
* experts: softmax router, top-k, gates renormalised over the k, capacity
  ``int(group * k * capacity_factor / experts)`` per group of
  ``group_tokens`` tokens in row-major order, positions assigned token by
  token (k inner), assignments past capacity dropped without
  renormalising; Switch load-balancing loss per group, averaged over
  groups, summed over layers;
* mean next-token cross-entropy over the real vocabulary (padding rows
  of the tied embedding masked).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn          # operands
FP8_GRAD = jnp.float8_e5m2       # gradients flowing into a product


class Dims:
    """The sizes the reference needs, from a configuration file."""

    def __init__(self, cfg: Dict[str, Any]):
        a = cfg.get("assumed", {})
        self.D = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.KV = int(cfg["num_key_value_heads"])
        self.hd = int(a.get("head_dim") or self.D // self.H)
        self.L = int(cfg["num_hidden_layers"])
        self.V = int(cfg["vocab_size"])
        r = int(a.get("vocab_round", 256))
        self.Vp = (self.V + r - 1) // r * r
        self.tied = bool(cfg.get("tie_word_embeddings", False))
        self.eps = float(cfg.get("rms_norm_eps", 1e-6))
        self.theta = float(cfg.get("rope_theta", 10000.0))
        self.E = int(cfg.get("num_local_experts", 0) or 0)
        self.K = int(cfg.get("num_experts_per_tok", 0) or 0)
        self.F = int(cfg["intermediate_size"])
        self.cf = float(a.get("capacity_factor", 1.0))
        self.group = int(a.get("group_tokens", 1024))
        self.aux_w = float(a.get("aux_loss_weight", 0.0))
        self.param_dtype = jnp.dtype(a.get("param_dtype", "bfloat16"))
        self.opt = dict(a.get("optimizer", {}))

    @property
    def moe(self) -> bool:
        return self.E > 0


# -- weights -----------------------------------------------------------------
def _param_specs(d: Dims) -> Dict[str, Any]:
    """(shape, std or 'ones', dtype) per leaf, in the system's layout."""
    L, D, H, KV, hd = d.L, d.D, d.H, d.KV, d.hd
    pd = d.param_dtype
    layer: Dict[str, Any] = {
        "ln1": {"scale": ((L, D), "ones", pd)},
        "ln2": {"scale": ((L, D), "ones", pd)},
        "attn": {
            "wq": ((L, D, H, hd), 1 / math.sqrt(D), pd),
            "wk": ((L, D, KV, hd), 1 / math.sqrt(D), pd),
            "wv": ((L, D, KV, hd), 1 / math.sqrt(D), pd),
            "wo": ((L, H, hd, D), 1 / math.sqrt(H * hd), pd),
        },
    }
    if d.moe:
        E, F = d.E, d.F
        layer["ffn"] = {
            "router": ((L, D, E), 1 / math.sqrt(D), jnp.dtype(jnp.float32)),
            "wi": ((L, E, D, F), 1 / math.sqrt(D), pd),
            "wg": ((L, E, D, F), 1 / math.sqrt(D), pd),
            "wo": ((L, E, F, D), 1 / math.sqrt(F), pd),
        }
    else:
        F = d.F
        layer["ffn"] = {
            "wi": ((L, D, F), 1 / math.sqrt(D), pd),
            "wg": ((L, D, F), 1 / math.sqrt(D), pd),
            "wo": ((L, F, D), 1 / math.sqrt(F), pd),
        }
    specs: Dict[str, Any] = {
        "embed": {"tok": ((d.Vp, D), 0.02, pd)},
        "final_norm": {"scale": ((D,), "ones", pd)},
        "layers": [layer],
    }
    if not d.tied:
        specs["lm_head"] = ((D, d.Vp), 1 / math.sqrt(D), pd)
    return specs


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def init_params(cfg: Dict[str, Any], rng) -> Any:
    """Seeded weights in the parameter dtype (jittable; one key per leaf)."""
    d = Dims(cfg)
    specs = _param_specs(d)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    out = []
    for i, (shape, std, dt) in enumerate(leaves):
        if std == "ones":
            out.append(jnp.ones(shape, dt))
        else:
            k = jax.random.fold_in(rng, i)
            out.append((jax.random.normal(k, shape, jnp.float32) * std).astype(dt))
    return jax.tree_util.tree_unflatten(treedef, out)


# -- products ------------------------------------------------------------------
#: lower-precision products: (operand format, incoming-gradient format,
#: whether each tensor is scaled to the format's range first)
FORMATS = {"fp8": (FP8, FP8_GRAD, True),
           "bf16": (jnp.bfloat16, jnp.bfloat16, False)}


def _round(x, dtype, scaled: bool):
    """``x`` rounded to ``dtype``; scaled so that its largest entry lands on
    the format's largest value, where ``scaled``."""
    if not scaled:
        return x.astype(dtype).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mm_low(quant: str, spec: str, a, b):
    op, _, scaled = FORMATS[quant]
    return _einsum(spec, _round(a, op, scaled), _round(b, op, scaled))


def _mm_low_fwd(quant, spec, a, b):
    op, _, scaled = FORMATS[quant]
    qa, qb = _round(a, op, scaled), _round(b, op, scaled)
    return _einsum(spec, qa, qb), (qa, qb)


def _mm_low_bwd(quant, spec, res, ct):
    _, grad, scaled = FORMATS[quant]
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_round(ct, grad, scaled))


_mm_low.defvjp(_mm_low_fwd, _mm_low_bwd)


def _mm(spec: str, a, b, quant: Optional[str]):
    if quant is None:
        return _einsum(spec, a, b)
    return _mm_low(quant, spec, a, b)


# -- forward -------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, H, hd) at positions 0..S-1, rotation on the two halves."""
    S, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_row(d: Dims, p: Dict, h, quant):
    """One sequence: h (S, D) -> (S, D)."""
    S = h.shape[0]
    q = _rope(_mm("sd,dhk->shk", h, p["wq"], quant), d.theta)
    k = _rope(_mm("sd,dhk->shk", h, p["wk"], quant), d.theta)
    v = _mm("sd,dhk->shk", h, p["wv"], quant)
    g = d.H // d.KV
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = _mm("shk,thk->hst", q, k, quant) / math.sqrt(d.hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = _mm("hst,thk->shk", pr, v, quant)
    return _mm("shk,hkd->sd", o, p["wo"], quant)


def _moe_group(d: Dims, p: Dict, x, quant):
    """One group of tokens: x (T, D) -> (y (T, D), aux)."""
    T = x.shape[0]
    E, K = d.E, d.K
    C = max(1, int(T * K * d.cf / E))
    logits = _mm("td,de->te", x, p["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                       # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(gate_i, E, dtype=jnp.float32)         # (T, K, E)
    aux = jnp.sum(probs.mean(0) * onehot.sum(1).mean(0)) * E * d.aux_w
    flat = onehot.reshape(T * K, E)
    pos = ((jnp.cumsum(flat, 0) - flat) * flat).sum(-1).reshape(T, K)
    pos = pos.astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, pos, C)                                 # C: dropped
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K))
    expert_in = jnp.zeros((E, C + 1, x.shape[1]), x.dtype)
    expert_in = expert_in.at[gate_i, slot].set(x[tok])[:, :C]      # (E, C, D)
    hdn = jax.nn.silu(_mm("ecd,edf->ecf", expert_in, p["wi"], quant))
    hdn = hdn * _mm("ecd,edf->ecf", expert_in, p["wg"], quant)
    out = _mm("ecf,efd->ecd", hdn, p["wo"], quant)                # (E, C, D)
    out = jnp.concatenate([out, jnp.zeros_like(out[:, :1])], 1)   # slot C = 0
    picked = out[gate_i, slot]                                     # (T, K, D)
    y = jnp.sum(picked * (gate_w * keep)[..., None], axis=1)
    return y, aux


def _ffn(d: Dims, p: Dict, h, quant):
    """h (B, S, D) -> (y, aux)."""
    B, S, D = h.shape
    if not d.moe:
        g = jax.nn.silu(_mm("bsd,df->bsf", h, p["wi"], quant))
        return (_mm("bsf,fd->bsd", g * _mm("bsd,df->bsf", h, p["wg"], quant),
                    p["wo"], quant), jnp.zeros((), jnp.float32))
    T = B * S
    gt = min(d.group, T)
    if T % gt:
        gt = math.gcd(T, gt)
    xg = h.reshape(T // gt, gt, D)
    ys, auxs = jax.lax.map(
        jax.checkpoint(lambda xx: _moe_group(d, p, xx, quant)), xg)
    return ys.reshape(B, S, D), auxs.mean()


def _xent(d: Dims, w, h, labels, quant, chunk: int = 1024):
    """Mean next-token loss over (B, S) in sequence chunks."""
    B, S, D = h.shape
    C = min(chunk, S)
    n = S // C
    hc = h.reshape(B, n, C, D).swapaxes(0, 1)
    lc = labels.reshape(B, n, C).swapaxes(0, 1)
    real = jnp.arange(d.Vp) < d.V

    def one(args):
        hh, ll = args
        logits = _mm("bcd,vd->bcv", hh, w, quant)
        logits = jnp.where(real, logits, -1e30)
        lse = jax.nn.logsumexp(logits, -1)
        lab = jnp.take_along_axis(logits, ll[..., None], -1)[..., 0]
        return jnp.sum(lse - lab)

    tot = jax.lax.map(jax.checkpoint(one), (hc, lc))
    return jnp.sum(tot) / (B * S)


def loss(cfg: Dict[str, Any], params: Any, tokens, labels,
         quant: Optional[str] = None):
    """Training loss of ``params`` (float32 tree) on one batch."""
    d = Dims(cfg)
    emb = params["embed"]["tok"]
    x = emb[tokens]
    lp = params["layers"][0]
    aux_total = jnp.zeros((), jnp.float32)

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], d.eps)
        a = jax.lax.map(jax.checkpoint(
            lambda hh: _attention_row(d, p["attn"], hh, quant)), h)
        x = x + a
        h = _rms(x, p["ln2"]["scale"], d.eps)
        f, aux = _ffn(d, p["ffn"], h, quant)
        return x + f, aux

    x, auxs = jax.lax.scan(jax.checkpoint(layer), x, lp)
    aux_total = aux_total + auxs.sum()
    x = _rms(x, params["final_norm"]["scale"], d.eps)
    w = emb if d.tied else params["lm_head"].T
    return _xent(d, w, x, labels, quant) + aux_total


# -- optimizer -----------------------------------------------------------------
def _lr(o: Dict[str, Any], t):
    t = t.astype(jnp.float32)
    warm = jnp.minimum(t / max(o["warmup_steps"], 1), 1.0)
    span = max(o["total_steps"] - o["warmup_steps"], 1)
    tt = jnp.clip((t - o["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * tt))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw(o: Dict[str, Any], params, grads, m, v, t):
    """One AdamW step at 1-based step ``t``; returns (params, m, v)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * clip, grads)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    lr = _lr(o, t)
    b1c = 1 - o["b1"] ** t.astype(jnp.float32)
    b2c = 1 - o["b2"] ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * ((mm / b1c) / (jnp.sqrt(vv / b2c) + o["eps"])
                                    + o["weight_decay"] * p), params, m, v)
    return params, m, v


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train_readings(cfg: Dict[str, Any], seed_key, batches: List[Tuple[Any, Any]],
                   quant: Optional[str] = None,
                   grad1_of: Optional[List[np.ndarray]] = None,
                   keep_grad1: bool = False) -> Dict[str, Any]:
    """Three AdamW steps from the seeded weights on ``batches``.

    Returns each step's loss, the per-leaf norm of the first gradient as
    the loss gives it (before the optimizer's clip), and the per-leaf norm
    of the parameters' change after the three steps, all as host arrays.
    Given another first gradient (``grad1_of``, host leaves in this
    tree's order), also the per-leaf norm of its difference from this
    one (``grad1_diff``); ``keep_grad1`` returns this one's leaves.
    """
    d = Dims(cfg)
    o = d.opt
    p0 = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                        init_params(cfg, k)))(seed_key)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(cfg, p, t, l, quant)))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw(o, p, g, m, v, t))
    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    p = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
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
    out["delta3"] = np.asarray(jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))(p, p0))
    out["losses"] = np.asarray(losses)
    return out


def train_state(cfg: Dict[str, Any], rng) -> Dict[str, Any]:
    """A whole train state in the system's layout, as after some steps:
    seeded weights, their float32 master, moments of plausible size, and
    the step counter (jittable)."""
    params = init_params(cfg, rng)
    k1, k2 = jax.random.split(jax.random.fold_in(rng, 1 << 20))
    leaves, td = jax.tree_util.tree_flatten(params)

    def moment(k, scale, square):
        out = [jax.random.normal(jax.random.fold_in(k, i), x.shape) * scale
               for i, x in enumerate(leaves)]
        if square:
            out = [o * o for o in out]
        return jax.tree_util.tree_unflatten(td, out)

    return {"params": params,
            "opt": {"m": moment(k1, 1e-3, False), "v": moment(k2, 1e-3, True),
                    "step": jnp.asarray(1000, jnp.int32),
                    "master": jax.tree.map(lambda x: x.astype(jnp.float32),
                                           params)}}
