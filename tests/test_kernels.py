"""Pallas kernel sweeps: every kernel vs its pure-jnp oracle, in
interpret mode (the kernel body executes in Python on CPU), across
shapes and dtypes; plus custom-vjp gradient checks on the ops wrappers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import (flash_attention_fwd, pick_block,
                                           plan_blocks)
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

RNG = np.random.default_rng(0)


def rnd(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# -- flash attention ------------------------------------------------------------
def _case(B, H, KV, S, T, D, causal, block=64):
    """A sweep case at explicit ``block`` x ``block`` blocks, or at the
    kernel's own plan where ``block`` is None (id suffix ``plan``)."""
    name = f"{B}-{H}-{KV}-{S}-{T}-{D}-{causal}" + ("" if block else "-plan")
    return pytest.param(B, H, KV, S, T, D, causal, block, id=name)


@pytest.mark.parametrize("B,H,KV,S,T,D,causal,block", [
    _case(1, 4, 4, 128, 128, 64, True),
    _case(2, 8, 2, 128, 256, 64, True),     # GQA + cross lengths
    _case(1, 2, 1, 256, 256, 128, False),   # MQA, non-causal
    _case(1, 4, 2, 128, 128, 256, True),    # gemma-size head_dim
    _case(1, 6, 2, 1024, 1024, 64, True, None),  # GQA group 3, two q blocks
    _case(1, 8, 1, 1024, 1024, 64, True, None),  # MQA, group 8
    _case(1, 6, 2, 256, 1024, 64, True, None),   # S < T: offset diagonal
    _case(1, 4, 2, 200, 200, 64, True, None),    # no multiple-of-128 divisor
    _case(2, 6, 2, 256, 512, 64, False, None),   # non-causal
    _case(1, 2, 1, 4096, 4096, 64, True, None),  # two KV blocks: the clamp
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, T, D, causal, block, dtype):
    q, k, v = rnd((B, H, S, D), dtype), rnd((B, KV, T, D), dtype), rnd((B, KV, T, D), dtype)
    o_ref = ref.attention_naive(q, k, v, causal)
    o_ker = flash_attention_fwd(q, k, v, causal, block_q=block, block_k=block,
                                interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_ker, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,S,T,Dqk,Dv,causal,block", [
    pytest.param(1, 2, 128, 128, 192, 128, True, 64, id="mla-64"),
    pytest.param(2, 2, 1024, 1024, 192, 128, True, None, id="mla-plan"),
    pytest.param(1, 2, 256, 1024, 192, 128, True, None, id="mla-offset"),
    pytest.param(1, 2, 128, 256, 32, 8, False, None, id="narrow-v"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_v_head_of_its_own(B, H, S, T, Dqk, Dv, causal, block,
                                          dtype):
    """MLA's shapes: v (and the output) at a head size under q's and k's."""
    q, k = rnd((B, H, S, Dqk), dtype), rnd((B, H, T, Dqk), dtype)
    v = rnd((B, H, T, Dv), dtype)
    scale = 1.59 * Dqk ** -0.5
    # the oracle in float32 on the same (rounded) inputs: bf16 rounding
    # inside the naive oracle itself would add to the kernel's
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    o_ref = ref.attention_naive(*f32, causal, scale)
    o_ker = flash_attention_fwd(q, k, v, causal, scale, block_q=block,
                                block_k=block, interpret=True)
    assert o_ker.shape == (B, H, S, Dv)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_ker, np.float32), atol=tol, rtol=tol)
    o_blk = ref.attention_blockwise(q, k, v, causal, scale, block_q=128,
                                    block_k=128)
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_blk, np.float32), atol=tol, rtol=tol)


def test_plan_blocks_with_a_v_head_of_its_own():
    """The KV block is sized for the wider of K and V; a v head size at or
    under q's leaves the plan of q's head size alone (Granite, D = 64)."""
    assert plan_blocks(2048, 2048, 64, True, Dv=64) == \
        plan_blocks(2048, 2048, 64, True)
    mla = plan_blocks(8192, 8192, 192, True, Dv=128)
    assert (mla.block_q, mla.block_k, mla.block_c) == (512, 1024, 512)
    assert plan_blocks(8192, 8192, 64, True, Dv=256).block_k == \
        plan_blocks(8192, 8192, 256, True).block_k


def _live_share(S, T, bq, bc, causal):
    """Share of (q block, kv chunk) pairs holding a score the mask admits,
    counted position by position."""
    qpos = np.arange(S) + (T - S)
    kpos = np.arange(T)
    admit = kpos[None, :] <= qpos[:, None] if causal else np.ones((S, T), bool)
    return admit.reshape(S // bq, bq, T // bc, bc).any(axis=(1, 3)).mean()


@pytest.mark.parametrize("S,T,D,causal", [
    (2048, 2048, 64, True),     # Granite-3.0 MoE and TinyLlama-1.1B training
    (512, 2048, 128, True),
    (4096, 4096, 256, True),
    (1500, 1500, 64, False),    # Whisper's encoder length
    (200, 200, 64, True),
])
def test_plan_blocks(S, T, D, causal):
    plan = plan_blocks(S, T, D, causal)
    assert S % plan.block_q == 0 and T % plan.block_k == 0
    assert plan.block_k % plan.block_c == 0
    assert plan.live_share == pytest.approx(
        _live_share(S, T, plan.block_q, plan.block_c, causal), abs=1e-12)
    # explicit blocks override the plan (through pick_block's fallback)
    over = plan_blocks(S, T, D, causal, block_q=8, block_k=8)
    assert (over.block_q, over.block_k) == (pick_block(S, 8), pick_block(T, 8))


def test_plan_blocks_at_2048():
    """Large blocks at S = T = 2048, D = 64, and a causal live share below
    one: the dead chunks are the rest."""
    plan = plan_blocks(2048, 2048, 64, True)
    assert plan.block_q >= 512 and plan.block_k >= 512
    assert plan.live_share == _live_share(2048, 2048, plan.block_q, plan.block_c, True)
    assert plan.live_share < 1.0
    over = plan_blocks(2048, 2048, 64, True, block_q=256, block_k=128)
    assert (over.block_q, over.block_k, over.block_c) == (256, 128, 128)


def test_blockwise_ref_matches_naive_ragged_lengths():
    q, k, v = rnd((2, 4, 300, 64)), rnd((2, 2, 300, 64)), rnd((2, 2, 300, 64))
    o1 = ref.attention_naive(q, k, v, True)
    o2 = ref.attention_blockwise(q, k, v, True, block_q=128, block_k=128)
    np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)


# -- decode attention -------------------------------------------------------------
@pytest.mark.parametrize("B,H,KV,T,D", [
    (2, 8, 2, 512, 64), (1, 4, 1, 1024, 128), (3, 6, 6, 512, 64)])
def test_flash_decode_sweep(B, H, KV, T, D):
    q = rnd((B, H, D))
    k, v = rnd((B, KV, T, D)), rnd((B, KV, T, D))
    length = jnp.asarray(RNG.integers(1, T + 1, B), jnp.int32)
    o_ref = ref.decode_attention_naive(q, k, v, length)
    o_ker = flash_decode(q, k, v, length, block_k=128, interpret=True)
    np.testing.assert_allclose(o_ref, o_ker, atol=2e-5, rtol=2e-5)


# -- mamba2 -------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,G,N,chunk,hb", [
    (2, 128, 8, 16, 2, 8, 32, 4),
    (1, 256, 4, 32, 1, 16, 64, 4),   # single group (zamba2 style)
    (2, 64, 8, 64, 8, 32, 32, 8),    # per-head groups
])
def test_mamba2_kernel_sweep(B, S, H, P, G, N, chunk, hb):
    x = rnd((B, S, H, P))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, H), jnp.float32)
    Bm, Cm = rnd((B, S, G, N)), rnd((B, S, G, N))
    h0 = rnd((B, H, P, N))
    y1, h1 = ref.mamba2_scan_naive(x, dt, A, Bm, Cm, h0)
    y2, h2 = mamba2_scan(x, dt, A, Bm, Cm, h0, chunk=chunk, head_block=hb,
                         interpret=True)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-4)


# -- rwkv6 ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,K,V,chunk,sub", [
    (2, 128, 4, 16, 16, 32, 16),
    (1, 256, 2, 64, 64, 64, 32),
    (2, 64, 8, 32, 32, 64, 32),
])
def test_rwkv6_kernel_sweep(B, S, H, K, V, chunk, sub):
    r, k, v = rnd((B, S, H, K)), rnd((B, S, H, K)), rnd((B, S, H, V))
    w = jnp.asarray(-RNG.uniform(0.01, 3.0, (B, S, H, K)), jnp.float32)
    u = rnd((H, K))
    s0 = rnd((B, H, K, V))
    yc, sc = ref.rwkv6_scan_chunked(r, k, v, w, u, s0, chunk=chunk)
    y2, s2 = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, sub=sub, interpret=True)
    np.testing.assert_allclose(yc, y2, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(sc, s2, atol=5e-5, rtol=5e-5)
    # and the chunked math equals the token recurrence
    yn, sn = ref.rwkv6_scan_naive(r, k, v, w, u, s0)
    np.testing.assert_allclose(yn, y2, atol=2e-3, rtol=2e-3)


# -- decode steps equal scan prefixes -------------------------------------------------
def test_mamba2_decode_equals_scan():
    B, S, H, P, G, N = 2, 16, 4, 8, 2, 8
    x = rnd((B, S, H, P))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, H), jnp.float32)
    Bm, Cm = rnd((B, S, G, N)), rnd((B, S, G, N))
    y, _ = ref.mamba2_scan_naive(x, dt, A, Bm, Cm)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    for t in range(S):
        yt, h = ops.mamba2_decode(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        np.testing.assert_allclose(yt, y[:, t], atol=3e-5, rtol=3e-5)


def test_rwkv6_decode_equals_scan():
    B, S, H, K = 2, 16, 4, 8
    r, k, v = rnd((B, S, H, K)), rnd((B, S, H, K)), rnd((B, S, H, K))
    w = jnp.asarray(-RNG.uniform(0.05, 1.0, (B, S, H, K)), jnp.float32)
    u = rnd((H, K))
    y, _ = ref.rwkv6_scan_naive(r, k, v, w, u)
    s = jnp.zeros((B, H, K, K), jnp.float32)
    for t in range(S):
        yt, s = ops.rwkv6_decode(r[:, t], k[:, t], v[:, t], w[:, t], u, s)
        np.testing.assert_allclose(yt, y[:, t], atol=3e-5, rtol=3e-5)


# -- custom vjp: pallas fwd + ref bwd == ref fwd+bwd ------------------------------------
def test_attention_custom_vjp_grads():
    q, k, v = rnd((1, 2, 64, 32)), rnd((1, 2, 64, 32)), rnd((1, 2, 64, 32))

    def f_ker(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="interpret").sum()

    def f_ref(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="ref").sum()

    g1 = jax.grad(f_ker, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("S,T,Dqk,Dv", [(64, 64, 32, 32), (64, 64, 48, 16),
                                       (32, 128, 48, 16)])
def test_blockwise_ref_grads_match_naive(S, T, Dqk, Dv):
    """The blockwise VJP (the Pallas forward's backward) against the naive
    one, at a v head of its own and with queries offset into the keys."""
    q, k, v = rnd((1, 4, S, Dqk)), rnd((1, 2, T, Dqk)), rnd((1, 2, T, Dv))
    w = rnd((1, 4, S, Dv))

    def f(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) * w).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    g1 = f(lambda q, k, v: ref.attention_naive(q, k, v, True, 0.3))
    g2 = f(lambda q, k, v: ref.attention_blockwise(q, k, v, True, 0.3,
                                                   block_q=16, block_k=32))
    g3 = f(lambda q, k, v: ops.attention(q, k, v, True, 0.3, impl="interpret"))
    for a, b, c in zip(g1, g2, g3):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(a, c, atol=2e-4, rtol=2e-4)


def test_mamba2_custom_vjp_grads():
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    x = rnd((B, S, H, P))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2, H), jnp.float32)
    Bm, Cm = rnd((B, S, G, N)), rnd((B, S, G, N))

    def f(impl):
        def g(x, Bm, Cm):
            y, _ = ops.mamba2(x, dt, A, Bm, Cm, impl=impl, chunk=32)
            return (y ** 2).sum()
        return g

    g1 = jax.grad(f("interpret"), argnums=(0, 1, 2))(x, Bm, Cm)
    g2 = jax.grad(f("ref"), argnums=(0, 1, 2))(x, Bm, Cm)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)
