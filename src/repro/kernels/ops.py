"""Jitted front-door for every kernel, with implementation selection.

``impl``:
* ``"naive"``      — simplest oracle (tests, tiny shapes)
* ``"ref"``        — memory-efficient pure-XLA twin (blockwise / chunked);
                     differentiable; the default on CPU
* ``"pallas"``     — the TPU kernel (compiled via Mosaic)
* ``"interpret"``  — the TPU kernel executed in interpret mode (CPU CI)
* ``"auto"``       — pallas on TPU, ref elsewhere

Pallas forwards are wrapped in ``jax.custom_vjp`` with the reference
implementation's VJP as the backward (recompute-style), so training code
can use kernels without a hand-written backward kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ref
from .decode_attention import flash_decode
from .flash_attention import flash_attention_fwd
from .mamba2_scan import mamba2_scan
from .rwkv6_scan import rwkv6_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def _per_shard(kernel, q, k, v, *per_row):
    """Run an attention kernel once per shard of the context mesh.

    XLA cannot partition a Mosaic kernel, so under a mesh of more than one
    device the call goes through ``shard_map``: batch over the non-"model"
    axes and heads over "model", each only where the size divides (query
    and kv heads alike, so every shard keeps whole GQA groups).  ``q`` is
    (B, H, ...), ``k``/``v`` are (B, KV, ...), ``per_row`` arrays are (B,).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel(q, k, v, *per_row)
    B, H, KV = q.shape[0], q.shape[1], k.shape[1]
    batch, n = [], 1
    for a in mesh.axis_names:
        if a != "model" and B % (n * mesh.shape[a]) == 0:
            batch.append(a)
            n *= mesh.shape[a]
    b = tuple(batch) or None
    m = mesh.shape.get("model", 1)
    h = "model" if m > 1 and H % m == 0 and KV % m == 0 else None
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(b, h), P(b, h), P(b, h)) + (P(b),) * len(per_row),
        out_specs=P(b, h), check_vma=False)(q, k, v, *per_row)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention_pallas(q, k, v, causal, scale, interpret):
    return _per_shard(
        functools.partial(flash_attention_fwd, causal=causal, scale=scale,
                          interpret=interpret), q, k, v)


def _attention_pallas_fwd(q, k, v, causal, scale, interpret):
    return _attention_pallas(q, k, v, causal, scale, interpret), (q, k, v)


def _attention_pallas_bwd(causal, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: ref.attention_blockwise(q, k, v, causal, scale),
                     q, k, v)
    return vjp(g)


_attention_pallas.defvjp(_attention_pallas_fwd, _attention_pallas_bwd)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
              scale: Optional[float] = None, impl: str = "auto",
              block_q: int = 512, block_k: int = 1024) -> jax.Array:
    """(B,H,S,D) x (B,KV,T,D) x (B,KV,T,Dv) -> (B,H,S,Dv); GQA via head
    groups; v may have a head size of its own (MLA).

    ``block_q`` / ``block_k`` reach only ``impl="ref"``, the blockwise XLA
    twin.  The Pallas kernel (``"pallas"``, ``"interpret"``) plans its own
    blocks from the shapes (``flash_attention.plan_blocks``), and
    ``"naive"`` has none.
    """
    impl = _resolve(impl)
    if impl == "naive":
        return ref.attention_naive(q, k, v, causal, scale)
    if impl == "ref":
        bq = min(block_q, q.shape[2])
        bk = min(block_k, k.shape[2])
        return ref.attention_blockwise(q, k, v, causal, scale,
                                       block_q=bq, block_k=bk)
    if impl == "pallas":
        return _attention_pallas(q, k, v, causal, scale, False)
    if impl == "interpret":
        return _attention_pallas(q, k, v, causal, scale, True)
    raise ValueError(f"unknown impl {impl!r}")


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, scale: Optional[float] = None,
                     impl: str = "auto", block_k: int = 512) -> jax.Array:
    """(B,H,D) query vs (B,KV,T,D) cache with per-batch valid lengths."""
    impl = _resolve(impl)
    if impl in ("naive", "ref"):
        return ref.decode_attention_naive(q, k, v, length, scale)
    if impl in ("pallas", "interpret"):
        kernel = functools.partial(flash_decode, scale=scale, block_k=block_k,
                                   interpret=impl == "interpret")
        return _per_shard(kernel, q, k, v, length)
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# Mamba2 / SSD
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _mamba2_pallas(x, dt, A, B, C, h0, chunk, interpret):
    return mamba2_scan(x, dt, A, B, C, h0, chunk=chunk, interpret=interpret)


def _mamba2_pallas_fwd(x, dt, A, B, C, h0, chunk, interpret):
    return _mamba2_pallas(x, dt, A, B, C, h0, chunk, interpret), (x, dt, A, B, C, h0)


def _mamba2_pallas_bwd(chunk, interpret, res, g):
    x, dt, A, B, C, h0 = res
    _, vjp = jax.vjp(
        lambda x, dt, A, B, C, h0: ref.mamba2_scan_chunked(x, dt, A, B, C, h0, chunk=chunk),
        x, dt, A, B, C, h0)
    return vjp(g)


_mamba2_pallas.defvjp(_mamba2_pallas_fwd, _mamba2_pallas_bwd)


def mamba2(x, dt, A, B, C, h0=None, impl: str = "auto", chunk: int = 128):
    """Chunked SSD scan -> (y, h_final)."""
    impl = _resolve(impl)
    if impl == "naive":
        return ref.mamba2_scan_naive(x, dt, A, B, C, h0)
    if impl == "ref":
        return ref.mamba2_scan_chunked(x, dt, A, B, C, h0, chunk=min(chunk, x.shape[1]))
    if h0 is None:
        Bsz, _, H, P = x.shape
        N = B.shape[-1]
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    if impl == "pallas":
        return _mamba2_pallas(x, dt, A, B, C, h0, min(chunk, x.shape[1]), False)
    if impl == "interpret":
        return _mamba2_pallas(x, dt, A, B, C, h0, min(chunk, x.shape[1]), True)
    raise ValueError(f"unknown impl {impl!r}")


def mamba2_decode(x, dt, A, B, C, h):
    """Single-token SSD step (serving)."""
    return ref.mamba2_decode_step(x, dt, A, B, C, h)


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rwkv6_pallas(r, k, v, w, u, s0, chunk, interpret):
    return rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=interpret)


def _rwkv6_pallas_fwd(r, k, v, w, u, s0, chunk, interpret):
    return _rwkv6_pallas(r, k, v, w, u, s0, chunk, interpret), (r, k, v, w, u, s0)


def _rwkv6_pallas_bwd(chunk, interpret, res, g):
    r, k, v, w, u, s0 = res
    _, vjp = jax.vjp(
        lambda r, k, v, w, u, s0: ref.rwkv6_scan_chunked(r, k, v, w, u, s0, chunk=chunk),
        r, k, v, w, u, s0)
    return vjp(g)


_rwkv6_pallas.defvjp(_rwkv6_pallas_fwd, _rwkv6_pallas_bwd)


def rwkv6(r, k, v, w, u, s0=None, impl: str = "auto", chunk: int = 64):
    """Chunked WKV6 scan -> (y, s_final)."""
    impl = _resolve(impl)
    if impl == "naive":
        return ref.rwkv6_scan_naive(r, k, v, w, u, s0)
    if impl == "ref":
        return ref.rwkv6_scan_chunked(r, k, v, w, u, s0, chunk=min(chunk, r.shape[1]))
    if s0 is None:
        B, _, H, K = r.shape
        V = v.shape[-1]
        s0 = jnp.zeros((B, H, K, V), jnp.float32)
    if impl == "pallas":
        return _rwkv6_pallas(r, k, v, w, u, s0, min(chunk, r.shape[1]), False)
    if impl == "interpret":
        return _rwkv6_pallas(r, k, v, w, u, s0, min(chunk, r.shape[1]), True)
    raise ValueError(f"unknown impl {impl!r}")


def rwkv6_decode(r, k, v, w, u, s):
    """Single-token WKV6 step (serving)."""
    return ref.rwkv6_decode_step(r, k, v, w, u, s)
