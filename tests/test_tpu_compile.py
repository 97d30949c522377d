"""Ahead-of-time compiles of the training path's attention kernels for a
described TPU v5e, at TinyLlama-1.1B and Granite-3.0 MoE training widths.

The TPU compiler refuses what interpret mode accepts (misaligned blocks,
too much VMEM), so these compiles guard the kernels without a chip.  The
topology is described inside a fixture, never at import, because only one
process at a time may load the TPU library.  Nothing here runs a kernel.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention_fwd

B, H, KV, S, D = 8, 32, 4, 2048, 64  # TinyLlama-1.1B heads at seq 2048
GRANITE_Q, GRANITE_KV = (8, 24, 2048, 64), (8, 8, 2048, 64)  # batch 8 x 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_fwd_compiles(one_chip):
    q = _sds((B, H, S, D), jnp.bfloat16, one_chip)
    kv = _sds((B, KV, S, D), jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(flash_attention_fwd).lower(q, kv, kv).compile())


def test_flash_decode_compiles(one_chip):
    q = _sds((B, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, KV, S, D), jnp.bfloat16, one_chip)
    length = _sds((B,), jnp.int32, one_chip)
    _assert_kernel(jax.jit(flash_decode).lower(q, kv, kv, length).compile())


def test_attention_custom_vjp_compiles(one_chip):
    """Forward kernel plus the reference backward, as the train step runs
    them (value_and_grad keeps the forward's output alive)."""
    q = _sds((B, H, S, D), jnp.bfloat16, one_chip)
    kv = _sds((B, KV, S, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return ops.attention(q, k, v, impl="pallas").astype(jnp.float32).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    _assert_kernel(step.lower(q, kv, kv).compile())


def test_attention_at_whisper_audio_length_compiles(one_chip):
    """1500 encoder positions: no multiple of 8 divides it, so the kernels
    take the whole length as one block."""
    q = _sds((1, 6, 1500, 64), jnp.bfloat16, one_chip)
    kv = _sds((1, 6, 1500, 64), jnp.bfloat16, one_chip)
    fwd = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, causal=False))
    _assert_kernel(fwd.lower(q, kv, kv).compile())
    qd = _sds((1, 6, 64), jnp.bfloat16, one_chip)
    length = _sds((1,), jnp.int32, one_chip)
    _assert_kernel(jax.jit(flash_decode).lower(qd, kv, kv, length).compile())


def _assert_granite_call(compiled):
    """The kernel's HLO keeps q and o at bf16[8,24,2048,64] and k, v at
    bf16[8,8,2048,64]: the strings the benchmark's roofline reader
    (``kernel_matcher``) finds the kernel by."""
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert any(c.count("bf16[8,24,2048,64]") >= 2 and "bf16[8,8,2048,64]" in c
               for c in calls), calls


def test_flash_attention_fwd_compiles_at_granite_widths(one_chip):
    """The planned blocks fit the compiler's VMEM and tiling rules."""
    q = _sds(GRANITE_Q, jnp.bfloat16, one_chip)
    kv = _sds(GRANITE_KV, jnp.bfloat16, one_chip)
    _assert_granite_call(jax.jit(flash_attention_fwd).lower(q, kv, kv).compile())


def test_attention_custom_vjp_compiles_at_granite_widths(one_chip):
    q = _sds(GRANITE_Q, jnp.bfloat16, one_chip)
    kv = _sds(GRANITE_KV, jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return ops.attention(q, k, v, impl="pallas").astype(jnp.float32).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    _assert_granite_call(step.lower(q, kv, kv).compile())


MLA_QK, MLA_V = (2, 16, 8192, 192), (2, 16, 8192, 128)  # V2-Lite, batch 2 x 8k


def _assert_mla_call(compiled):
    """q and k at bf16[2,16,8192,192], v and o at bf16[2,16,8192,128]: the
    strings the benchmark's ``mla_attn_roofline`` reader finds it by."""
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert any(c.count("bf16[2,16,8192,192]") >= 2 and "bf16[2,16,8192,128]" in c
               for c in calls), calls


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "custom_vjp"])
def test_attention_compiles_at_deepseek_v2_lite_widths(one_chip, grad):
    """Latent attention's shapes: v at its own head size under q's and k's,
    the forward alone and with the reference backward."""
    q = _sds(MLA_QK, jnp.bfloat16, one_chip)
    v = _sds(MLA_V, jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return ops.attention(q, k, v, scale=0.115, impl="pallas")

    fn = jax.value_and_grad(lambda q, k, v: fwd(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2)) if grad else fwd
    _assert_mla_call(jax.jit(fn).lower(q, q, v).compile())
