"""Mesh construction over ("data", "model"); a "pod" axis, where a mesh
has one, is an outer data axis, which is why batch specs shard over
("pod", "data") jointly.

Defined as functions so importing this module never touches jax device
state (device count is locked at first backend init).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_host_mesh(devices: Optional[Sequence] = None):
    """A data-parallel mesh over ``devices`` (default: every device this
    host has) — the trainer's mesh on one chip or on a four-chip host."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=_auto(2), devices=devices)


def batch_axes(mesh) -> Tuple[str, ...]:
    """The axes a global batch is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
