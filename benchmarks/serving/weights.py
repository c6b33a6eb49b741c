"""Seeded weights in the plain reference's layout, made on the device.

The layout, the init rule and the program's parameter tree built from these
weights belong to the configuration's architecture module (``archs/``).  The
reference (``reference.py``) and the program both receive these weights,
never anything the program made from them.  A configuration with
``serving.weights == "apack-int8"`` states int8 projection and FFN weights:
the architecture's init rounds each such matrix with ``round_per_channel``,
so the program's own int8 packing finds exactly these codes and the
reference multiplies exactly what the program serves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import spec


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def round_per_channel(w: jax.Array, levels: int) -> jax.Array:
    """Symmetric rounding of each layer's tensor to ``2*levels+1`` values,
    one scale per last-axis index (max over every other axis of the layer)."""
    axes = tuple(range(1, w.ndim - 1))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / levels
    return jnp.clip(jnp.round(w / scale), -levels, levels) * scale


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _make_jit(arch, model_items: tuple, int8: bool, key: jax.Array) -> dict:
    return arch.init(dict(model_items), int8, key)


def make(model: dict, seed: int, *, int8: bool) -> dict:
    """Reference-layout float32 weights, in one jitted call on the device."""
    arch = spec.arch_of(model)
    items = tuple(sorted((k, model[k]) for k in arch.WEIGHT_KEYS))
    return _make_jit(arch, items, int8, key_of(seed))
