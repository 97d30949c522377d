"""Exact, order-sensitive digests of array bytes, computed alike on the
device (jitted) and on the host (numpy), so a leaf can be checked on
either side against the other.

Each leaf's elements are read as unsigned words of their own width and
folded into two sums modulo 2**32: one weighted by the odd number
``2i + 1`` of the position and one of the words xor-ed with a multiple of
the position.  Any single changed element changes the first; two swapped
ones change it too.  Leaves of 1-byte elements are not supported.
"""

from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

_GOLD = np.uint32(0x9E3779B1)


def _words_np(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a).reshape(-1)
    width = {2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
    return a.view(width).astype(np.uint32)


def leaf_digest_np(a: np.ndarray) -> np.ndarray:
    w = _words_np(a)
    i = np.arange(w.size, dtype=np.uint32)
    s1 = np.sum(w * (i * np.uint32(2) + np.uint32(1)), dtype=np.uint32)
    s2 = np.sum(w ^ (i * _GOLD), dtype=np.uint32)
    return np.array([s1, s2], np.uint32)


def _words_jnp(x: jax.Array) -> jax.Array:
    x = x.reshape(-1)
    width = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32)


def leaf_digest_jnp(x: jax.Array) -> jax.Array:
    w = _words_jnp(x)
    i = jnp.arange(w.size, dtype=jnp.uint32)
    s1 = jnp.sum(w * (i * jnp.uint32(2) + jnp.uint32(1)), dtype=jnp.uint32)
    s2 = jnp.sum(w ^ (i * jnp.uint32(int(_GOLD))), dtype=jnp.uint32)
    return jnp.stack([s1, s2])


@jax.jit
def tree_digest(tree: Any) -> jax.Array:
    """(leaves, 2) uint32 digests of a device tree, in tree-flatten order."""
    return jnp.stack([leaf_digest_jnp(x) for x in jax.tree.leaves(tree)])


def host_digests(arrays: List[np.ndarray]) -> np.ndarray:
    return np.stack([leaf_digest_np(a) for a in arrays])
