"""Blockwise (flash) attention forward kernel for TPU.

Grid ``(B, H, num_q_blocks, num_kv_blocks)`` with the KV dimension
innermost — TPU grids iterate sequentially over the last axis, so the
online-softmax running state (m, l, acc) lives in VMEM scratch that
persists across KV steps and the output block is written once on the last
step.  GQA/MQA is handled in the BlockSpec index maps: the KV block for
query head ``h`` is head ``h // (H // KV)`` — no materialized repeat.

Causal masking skips fully-masked KV blocks via ``pl.when`` (no MXU work
issued for them) and applies an iota mask on the diagonal blocks.

Block shapes are (128, head_dim)-aligned by default, matching the MXU's
128-lane systolic tiles; head_dim 64/128/256 are all lane-aligned.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, offs: int, block_q: int,
                block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0]                      # (bk, D)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + qi * block_q + offs
            kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
                + ki * block_k
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip KV blocks strictly above the diagonal for this q block:
        # the last query position of the block sees keys <= qpos_max.
        qpos_max = (qi + 1) * block_q - 1 + offs
        pl.when(ki * block_k <= qpos_max)(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def pick_block(n: int, target: int) -> int:
    """Largest multiple of 8 that divides ``n`` and is at most ``target``;
    ``n`` itself when there is none (a block spanning the whole dim always
    meets the TPU's tiling rule)."""
    for b in range(min(target, n) // 8 * 8, 0, -8):
        if n % b == 0:
            return b
    return n


def flash_attention_fwd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, T, D)
    v: jax.Array,  # (B, KV, T, D)
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError("query heads must be a multiple of kv heads")
    group = H // KV
    scale_ = D ** -0.5 if scale is None else scale
    block_q = pick_block(S, block_q)
    block_k = pick_block(T, block_k)
    nq, nk = S // block_q, T // block_k
    offs = T - S

    kernel = functools.partial(
        _fwd_kernel, scale=scale_, causal=causal, offs=offs,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)

    # causal block skipping happens inside the kernel via pl.when; here we
    # still express it through the (python-bool) short circuit above.
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
