"""Production mesh construction (assignment-specified shapes).

Meshes are built with ``Auto`` axes: the repo's sharding rules are GSPMD
constraints and ``shard_map`` specs (see ``models.sharding.auto_mesh``)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for tests (requires enough host devices)."""
    if multi_pod:
        return _make((2, n_data, n_model), ("pod", "data", "model"))
    return _make((n_data, n_model), ("data", "model"))
