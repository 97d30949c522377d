"""Int8 gradient compression codec with error feedback.

The distributed-optimization trick for cross-pod gradient reduction: before
the (slow, DCN-bound) pod-axis all-reduce, gradients are quantized to int8
with a per-tensor scale; the quantization residual is carried to the next
step (error feedback), which keeps SGD/Adam convergence intact in practice.

Under GSPMD the data/model-axis reductions are emitted by XLA, so this
codec is applied at the optimizer boundary (quantize -> dequantize + error
state).  The bandwidth saving needs the pod-axis reduction routed through
the codec inside a shard_map block, which no launcher does yet; the tests
validate the numerics (round-trip error, error-feedback accumulation).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array, key: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8 quantization; stochastic rounding if a
    PRNG key is given."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    y = xf / scale
    if key is not None:
        y = jnp.floor(y + jax.random.uniform(key, y.shape))
    else:
        y = jnp.round(y)
    return jnp.clip(y, -127, 127).astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def int8_codec_roundtrip(x: jax.Array, err: Optional[jax.Array] = None,
                         key: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Quantize->dequantize with error feedback: returns (x_hat, new_err)
    where x_hat + new_err == x + err (up to fp32)."""
    target = x.astype(jnp.float32) + (0.0 if err is None else err)
    q, s = quantize_int8(target, key)
    xhat = dequantize_int8(q, s)
    return xhat, target - xhat


def compress_grads(grads: Any, err_state: Optional[Any] = None) -> Tuple[Any, Any]:
    """Apply the codec leaf-wise across a gradient pytree."""
    if err_state is None:
        err_state = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
    out = jax.tree.map(lambda g, e: int8_codec_roundtrip(g, e), grads, err_state)
    xhat = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    err = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return xhat, err
