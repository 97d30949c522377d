"""Flash-decode kernel: one query token against a long KV cache.

serve_step's hot spot at decode_32k / long_500k shapes.  Grid
``(B, H, num_kv_blocks)``: KV blocks stream through VMEM innermost with a
running (m, l, acc) in scratch; invalid cache positions (>= length[b]) are
masked with an iota comparison against a scalar-prefetched length.

The query head -> KV head mapping is again done in the index maps
(GQA/MQA without materialized repeats).  For a 1-token query the matmul is
a (1, D) x (D, block_k) contraction — small for the MXU, which is exactly
why decode is memory-bound: the kernel's job is to stream K/V through VMEM
at full HBM bandwidth, not to saturate the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import pick_block

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_k: int, num_k_blocks: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, D)
        v = v_ref[0, 0]                          # (bk, D)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (1, bk)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1) + ki * block_k
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,       # (B, H, D)
    k: jax.Array,       # (B, KV, T, D)
    v: jax.Array,       # (B, KV, T, D)
    length: jax.Array,  # (B,) int32 valid lengths
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError("query heads must be a multiple of kv heads")
    group = H // KV
    scale_ = D ** -0.5 if scale is None else scale
    block_k = pick_block(T, block_k)
    nk = T // block_k

    kernel = functools.partial(_decode_kernel, scale=scale_, block_k=block_k,
                               num_k_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # length lands in SMEM before the grid runs
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, lens: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, lens: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), q[:, :, None, :], k, v)
    return out[:, :, 0, :]
