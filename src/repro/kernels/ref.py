"""Pure-jnp oracles for every Pallas kernel.

Two flavors where it matters:

* ``*_naive`` — the simplest possible semantics (materializes S x S scores,
  steps the recurrence token by token).  These define correctness.
* ``attention_blockwise`` / chunked scans — memory-efficient pure-XLA
  implementations used by the model plane on CPU (numerically equal to
  the naive versions up to float assoc.).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention_naive(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, T, D)
    v: jax.Array,  # (B, KV, T, Dv)
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Full softmax attention with GQA head-group broadcast."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    assert H % KV == 0
    scale = D ** -0.5 if scale is None else scale
    kr = jnp.repeat(k, H // KV, axis=1)
    vr = jnp.repeat(v, H // KV, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", q * scale, kr).astype(jnp.float32)
    if causal:
        # allow query i (at absolute position offset + i) to see keys <= it;
        # when S != T the queries are the *last* S positions of T.
        offs = T - S
        qpos = jnp.arange(S)[:, None] + offs
        kpos = jnp.arange(T)[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", p, vr)


def attention_blockwise(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, T, D)
    v: jax.Array,  # (B, KV, T, Dv)
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
) -> jax.Array:
    """Online-softmax attention in pure jnp (never materializes S x T).

    This is the 'flash-in-XLA' path the model plane uses for long
    sequences on the CPU backend; the Pallas kernel in
    :mod:`repro.kernels.flash_attention` is the TPU fast path with the
    same math.
    """
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    scale_ = D ** -0.5 if scale is None else scale

    def _pick(n, target):  # largest divisor of n that is <= target
        d = min(target, n)
        while n % d:
            d -= 1
        return d

    block_q = _pick(S, block_q)
    block_k = _pick(T, block_k)
    nq = S // block_q
    nk = T // block_k
    offs = T - S

    # (nk, B, KV, bk, D): KV blocks as the scan's inputs, so that the VJP
    # stacks one cotangent slice per block (indexing a closed-over array
    # instead builds a whole-size cotangent at every step)
    kb = jnp.moveaxis(k.reshape(B, KV, nk, block_k, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, KV, nk, block_k, Dv), 2, 0)

    def q_block(qi, qchunk):  # qchunk: (B, H, bq, D)
        def kv_step(carry, blk):
            acc, m, l = carry
            kk, vv, ki = blk
            kk = jnp.repeat(kk, G, axis=1)  # (B, H, bk, D)
            vv = jnp.repeat(vv, G, axis=1)
            s = jnp.einsum("bhqd,bhkd->bhqk", qchunk * scale_, kk).astype(jnp.float32)
            if causal:
                qpos = qi * block_q + jnp.arange(block_q)[:, None] + offs
                kpos = ki * block_k + jnp.arange(block_k)[None, :]
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vv.dtype), vv
            ).astype(jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, H, block_q, Dv), jnp.float32)
        m0 = jnp.full((B, H, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        # causal: only the kv blocks this q block sees (qi is static)
        hi = min(max(((qi + 1) * block_q + offs + block_k - 1) // block_k, 0),
                 nk) if causal else nk
        if hi == 0:
            return jnp.zeros((B, H, block_q, Dv), q.dtype)
        # checkpointed step: the VJP keeps only the (acc, m, l) carry per
        # kv block and recomputes the (bq, bk) scores, as flash attention's
        # backward does; storing them holds all S x T probabilities in f32
        # at once, which alone overflows a 16 GB chip at seq 2048, batch 8
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0),
            (kb[:hi], vb[:hi], jnp.arange(hi)))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    qb = q.reshape(B, H, nq, block_q, D)
    outs = [q_block(qi, qb[:, :, qi]) for qi in range(nq)]
    return jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]


def decode_attention_naive(
    q: jax.Array,  # (B, H, D) single-token query
    k: jax.Array,  # (B, KV, T, D) cache
    v: jax.Array,  # (B, KV, T, D)
    length: jax.Array,  # (B,) valid cache lengths
    scale: Optional[float] = None,
) -> jax.Array:
    B, H, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    scale_ = D ** -0.5 if scale is None else scale
    kr = jnp.repeat(k, H // KV, axis=1)
    vr = jnp.repeat(v, H // KV, axis=1)
    logits = jnp.einsum("bhd,bhtd->bht", q * scale_, kr).astype(jnp.float32)
    mask = jnp.arange(T)[None, None, :] < length[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bht,bhtd->bhd", p, vr)


# ---------------------------------------------------------------------------
# Mamba2 (SSD) scan
# ---------------------------------------------------------------------------
def mamba2_scan_naive(
    x: jax.Array,   # (B, S, H, P)  inputs per head
    dt: jax.Array,  # (B, S, H)     softplus'd step sizes (>0)
    A: jax.Array,   # (H,)          negative decay rates (A < 0)
    Bm: jax.Array,  # (B, S, G, N)  input projections (G groups)
    Cm: jax.Array,  # (B, S, G, N)  output projections
    h0: Optional[jax.Array] = None,  # (B, H, P, N) initial state
):
    """Token-by-token SSD recurrence:
        h_t = exp(dt_t A) h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t
    Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)  # (B,S,H,N)
    Ch = jnp.repeat(Cm, rep, axis=2)
    h = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        decay = jnp.exp(dt[:, t] * A[None, :])  # (B,H)
        upd = (dt[:, t] * 1.0)[..., None, None] * (
            x[:, t][..., :, None] * Bh[:, t][..., None, :]
        )  # (B,H,P,N)
        h = h * decay[..., None, None] + upd.astype(jnp.float32)
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t].astype(jnp.float32))
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)  # (B,S,H,P)
    return y, h


def mamba2_scan_chunked(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array,
    h0: Optional[jax.Array] = None, chunk: int = 128,
):
    """Chunked SSD: dense intra-chunk matmuls + inter-chunk state carry.
    Mathematically identical to the naive recurrence (fp32 accumulation).
    This is the pure-XLA twin of the Pallas kernel."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    if S % chunk:
        raise ValueError("S must divide chunk")
    nc = S // chunk
    Bh = jnp.repeat(Bm, rep, axis=2).reshape(B, nc, chunk, H, N)
    Ch = jnp.repeat(Cm, rep, axis=2).reshape(B, nc, chunk, H, N)
    xc = x.reshape(B, nc, chunk, H, P)
    dtc = dt.reshape(B, nc, chunk, H)

    # per-chunk cumulative log-decay: a_t = dt_t * A  (<= 0)
    ac = dtc * A[None, None, None, :]  # (B,nc,L,H)
    cum = jnp.cumsum(ac, axis=2)  # inclusive cumsum over L

    def chunk_step(h, i):
        a = ac[:, i]          # (B,L,H)
        cs = cum[:, i]        # (B,L,H) inclusive
        xb = xc[:, i]         # (B,L,H,P)
        bb = Bh[:, i]         # (B,L,H,N)
        cb = Ch[:, i]         # (B,L,H,N)
        dtb = dtc[:, i]       # (B,L,H)
        total = cs[:, -1]     # (B,H) full-chunk log decay
        # intra-chunk: y_intra[t] = sum_{s<=t} exp(cs_t - cs_s) dt_s (C_t.B_s) x_s
        # NB: mask the exponent (not the exp) so gradients of masked entries
        # are exactly zero instead of inf * 0 = NaN.
        lmask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]  # (1,t,s,1)
        expo = jnp.where(lmask, cs[:, :, None, :] - cs[:, None, :, :], -1e30)
        L = jnp.exp(expo)
        cb_dot_bb = jnp.einsum("blhn,bmhn->blmh", cb, bb)  # (B,t,s,H)
        w = L * cb_dot_bb * dtb[:, None, :, :]  # (B,t,s,H)
        y_intra = jnp.einsum("blmh,bmhp->blhp", w, xb)
        # contribution of carried-in state: y_state[t] = C_t . (exp(cs_t) h)
        decay_t = jnp.exp(cs)  # (B,L,H)
        y_state = jnp.einsum("blhn,bhpn->blhp", cb, h) * decay_t[..., None]
        # new state: h' = exp(total) h + sum_s exp(total - cs_s) dt_s B_s x_s^T
        wst = jnp.exp(total[:, None, :] - cs) * dtb  # (B,L,H)
        h_new = h * jnp.exp(total)[..., None, None] + jnp.einsum(
            "blh,blhp,blhn->bhpn", wst, xb.astype(jnp.float32), bb.astype(jnp.float32))
        return h_new, (y_intra + y_state).astype(x.dtype)

    h = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    h, ys = jax.lax.scan(chunk_step, h, jnp.arange(nc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P)
    return y, h


# ---------------------------------------------------------------------------
# RWKV6 (Finch) scan
# ---------------------------------------------------------------------------
def rwkv6_scan_naive(
    r: jax.Array,  # (B, S, H, K)
    k: jax.Array,  # (B, S, H, K)
    v: jax.Array,  # (B, S, H, V)
    w: jax.Array,  # (B, S, H, K)  per-step log-decay (<0): state *= exp(w)
    u: jax.Array,  # (H, K)        bonus for the current token
    s0: Optional[jax.Array] = None,  # (B, H, K, V)
):
    """Token-by-token WKV6:
        y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
        S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T
    Returns (y (B,S,H,V), S_final)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    s = jnp.zeros((B, H, K, V), jnp.float32) if s0 is None else s0.astype(jnp.float32)

    def step(s, t):
        kv = k[:, t][..., :, None] * v[:, t][..., None, :]  # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv",
                       r[:, t].astype(jnp.float32),
                       s + u[None, :, :, None] * kv.astype(jnp.float32))
        s = jnp.exp(w[:, t].astype(jnp.float32))[..., None] * s + kv.astype(jnp.float32)
        return s, y

    s, ys = jax.lax.scan(step, s, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1).astype(v.dtype), s


def rwkv6_scan_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    s0: Optional[jax.Array] = None, chunk: int = 64,
):
    """Chunked WKV6 with per-channel data-dependent decay.

    Within a chunk, define inclusive log-decay prefix W_t = sum_{s<=t} w_s.
    y_t = r_t [ exp(W_{t-1} ... ) ... ]  — implemented with dense (t,s)
    matrices per chunk; inter-chunk state carried exactly.
    """
    B, S, H, K = r.shape
    V = v.shape[-1]
    if S % chunk:
        raise ValueError("S must divide chunk")
    nc = S // chunk
    rc = r.reshape(B, nc, chunk, H, K)
    kc = k.reshape(B, nc, chunk, H, K)
    vc = v.reshape(B, nc, chunk, H, V)
    wc = w.reshape(B, nc, chunk, H, K).astype(jnp.float32)

    def chunk_step(s, i):
        rb, kb, vb, wb = rc[:, i], kc[:, i], vc[:, i], wc[:, i]
        cw = jnp.cumsum(wb, axis=1)  # inclusive (B,L,H,K)
        # state contribution: y_state[t] = (r_t * exp(cw_{t-1})) @ S
        # exclusive prefix: cw_excl[t] = cw[t] - w[t]
        cw_excl = cw - wb
        rs = rb.astype(jnp.float32) * jnp.exp(cw_excl)
        y_state = jnp.einsum("blhk,bhkv->blhv", rs, s)
        # intra-chunk: pairs s < t contribute exp(cw_excl_t - cw_s) r_t.k_s
        # diag (s == t) contributes via bonus u instead of decay.
        # Mask the exponent (not the product) so masked entries carry zero
        # gradient instead of inf * 0 = NaN.
        mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)[None, :, :, None, None]
        expo = jnp.where(mask, cw_excl[:, :, None] - cw[:, None, :], -1e30)
        qk = jnp.einsum("blhk,bmhk,blmhk->blmh",
                        rb.astype(jnp.float32),
                        kb.astype(jnp.float32),
                        jnp.exp(expo))
        y_intra = jnp.einsum("blmh,bmhv->blhv", qk, vb.astype(jnp.float32))
        diag = jnp.einsum("blhk,hk,blhk->blh", rb.astype(jnp.float32),
                          u, kb.astype(jnp.float32))
        y_diag = diag[..., None] * vb.astype(jnp.float32)
        # new state: S' = diag(exp(cw_L)) S + sum_s exp(cw_L - cw_s) k_s v_s^T
        total = cw[:, -1]  # (B,H,K)
        dec = jnp.exp(total[:, None] - cw)  # (B,L,H,K)
        s_new = jnp.exp(total)[..., None] * s + jnp.einsum(
            "blhk,blhv->bhkv", kb.astype(jnp.float32) * dec, vb.astype(jnp.float32))
        return s_new, (y_state + y_intra + y_diag).astype(v.dtype)

    s = jnp.zeros((B, H, K, V), jnp.float32) if s0 is None else s0.astype(jnp.float32)
    s, ys = jax.lax.scan(chunk_step, s, jnp.arange(nc))
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, H, V), s


def rwkv6_decode_step(r, k, v, w, u, s):
    """Single-token WKV6 update for serving: shapes (B,H,K) / (B,H,V)."""
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", r.astype(jnp.float32),
                   s + u[None, :, :, None] * kv.astype(jnp.float32))
    s = jnp.exp(w.astype(jnp.float32))[..., None] * s + kv.astype(jnp.float32)
    return y.astype(v.dtype), s


def mamba2_decode_step(x, dt, A, Bm, Cm, h):
    """Single-token SSD update: x (B,H,P), dt (B,H), Bm/Cm (B,G,N)."""
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)
    Ch = jnp.repeat(Cm, rep, axis=1)
    decay = jnp.exp(dt * A[None, :])
    upd = dt[..., None, None] * (x[..., :, None] * Bh[..., None, :])
    h = h * decay[..., None, None] + upd.astype(jnp.float32)
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch.astype(jnp.float32))
    return y.astype(x.dtype), h
