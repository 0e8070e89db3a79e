"""Mesh construction. Functions, not module constants — importing this module
never touches jax device state (dryrun.py must set XLA_FLAGS first)."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def _named_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (one 256-chip v5e pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _named_mesh(shape, axes)


def make_flat_mesh(name: str = "shards") -> Mesh:
    """All devices on one axis — the ANN shard-and-merge layout."""
    devs = np.array(jax.devices())
    return Mesh(devs, (name,))


def make_test_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """CPU-sized mesh with production axis names for unit tests."""
    return _named_mesh(shape, axes)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes carrying the batch dimension ('pod' + 'data' when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
