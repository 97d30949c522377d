"""Blockwise (flash) attention forward kernel for TPU.

Grid ``(B, H, num_q_blocks, num_kv_blocks)`` with the KV dimension
innermost — TPU grids iterate sequentially over the last axis, so the
online-softmax running state (m, l, acc) lives in VMEM scratch that
persists across KV steps and the output block is written once on the last
step.  Within a step the kernel walks its KV block in chunks of
``block_c`` keys, one online-softmax update each.  GQA/MQA is handled in
the BlockSpec index maps: the KV block for query head ``h`` is head
``h // (H // KV)`` — no materialized repeat.

QKᵀ takes q and k in their input dtype with float32 accumulation; the
scale is applied to the float32 scores.  m, l and acc are float32 (m and
l replicated across the 128 lanes), and p is cast to v's dtype for the PV
product.  v may have a head size ``Dv`` of its own (MLA: q and k of 192,
v of 128); ``acc`` and the output take it.

Causal masking skips chunks wholly above the diagonal via ``pl.when`` (no
MXU work), applies an iota mask only on chunks that straddle it, and
clamps the K/V index maps to the last block a q block can see, so grid
steps past it fetch nothing new.

Block sizes come from :func:`plan_blocks`, which picks them from the call's
shapes; explicit ``block_q`` / ``block_k`` override it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: VMEM for one bf16 K or V block and its second buffer, lanes padded to 128
_KV_BLOCK_BYTES = 1 << 20


class BlockPlan(NamedTuple):
    block_q: int
    block_k: int
    block_c: int        # keys per online-softmax update; divides block_k
    live_share: float   # share of (q block, kv chunk) pairs that do work


def pick_block(n: int, target: int) -> int:
    """Largest multiple of 8 that divides ``n`` and is at most ``target``;
    ``n`` itself when there is none (a block spanning the whole dim always
    meets the TPU's tiling rule)."""
    for b in range(min(target, n) // 8 * 8, 0, -8):
        if n % b == 0:
            return b
    return n


def plan_blocks(S: int, T: int, D: int, causal: bool,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                Dv: Optional[int] = None) -> BlockPlan:
    """Blocks for one call from its shapes: q length ``S``, kv length
    ``T``, head size ``D`` and v's head size ``Dv`` (default ``D``).

    q blocks of up to 512 rows; KV blocks as long as ``_KV_BLOCK_BYTES``
    allows for the wider of K and V (the whole of a 2048-key sequence at
    head sizes up to 128), so that a head's K and V are fetched once for
    all its q blocks; chunks of up to 512 keys.  ``block_q`` / ``block_k``
    override the targets.
    """
    lanes = -(-max(D, Dv or D) // 128) * 128
    bq = pick_block(S, block_q or 512)
    bk = pick_block(T, block_k or _KV_BLOCK_BYTES // (2 * 2 * lanes))
    bc = pick_block(bk, 512)
    nq, nc = S // bq, T // bc
    live = nq * nc
    if causal:  # live: the chunk's first key is visible to the block's last query
        live = sum(c * bc <= (qi + 1) * bq - 1 + T - S
                   for qi in range(nq) for c in range(nc))
    return BlockPlan(bq, bk, bc, live / (nq * nc))


def _lanes(x, n: int):
    """A (rows, 128) lane-replicated column widened or cut to ``n`` lanes."""
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return x[:, :n] if n < 128 else x[:, :1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, offs: int, block_q: int,
                block_k: int, block_c: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    Dv = v_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(c: int, masked: bool):
        q = q_ref[0, 0]                                 # (bq, D)
        k = k_ref[0, 0, c * block_c:(c + 1) * block_c]  # (bc, D)
        v = v_ref[0, 0, c * block_c:(c + 1) * block_c]  # (bc, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bc)
        if masked:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_c), 0) \
                + qi * block_q + offs
            kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_c), 1) \
                + ki * block_k + c * block_c
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_c))
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lanes(corr, Dv) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    for c in range(block_k // block_c):
        if causal:
            # a chunk wholly at or below the diagonal needs no mask; one
            # wholly above it is skipped; one that straddles it is masked.
            q_first = qi * block_q + offs
            k_first = ki * block_k + c * block_c
            k_last = k_first + block_c - 1
            pl.when(k_last <= q_first)(functools.partial(_compute, c, False))
            pl.when((k_last > q_first) & (k_first <= q_first + block_q - 1))(
                functools.partial(_compute, c, True))
        else:
            _compute(c, False)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, Dv)).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, T, D)
    v: jax.Array,  # (B, KV, T, Dv)
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    if H % KV:
        raise ValueError("query heads must be a multiple of kv heads")
    group = H // KV
    scale_ = D ** -0.5 if scale is None else scale
    plan = plan_blocks(S, T, D, causal, block_q, block_k, Dv)
    bq, bk = plan.block_q, plan.block_k
    nq, nk = S // bq, T // bk
    offs = T - S

    def kv_index(b, h, qi, ki):
        if causal:  # the last KV block this q block sees: no fetch past it
            ki = jnp.minimum(ki, jnp.maximum((qi + 1) * bq - 1 + offs, 0) // bk)
        return (b, h // group, ki, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale_, causal=causal, offs=offs, block_q=bq,
        block_k=bk, block_c=plan.block_c, num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_index),
            pl.BlockSpec((1, 1, bk, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
