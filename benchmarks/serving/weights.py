"""Seeded weights in the plain reference's layout, made on the device, and
the program's parameter tree built from them.

The reference (``reference.py``) and the program both receive these
weights, never anything the program made from them.  A configuration with
``serving.weights == "apack-int8"`` states int8 projection and FFN weights:
each such matrix is rounded here to int8 with one scale per last-axis index
of each layer's tensor, so the program's own int8 packing finds exactly
these codes and the reference multiplies exactly what the program serves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PROJECTIONS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")


def shapes(model: dict) -> dict:
    """Reference layout: layer-stacked tensors, attention weights split
    by head."""
    L, d = model["num_hidden_layers"], model["hidden_size"]
    h, kv, dh = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    f, v = model["intermediate_size"], model["vocab_size"]
    return {"embed": (v, d), "final_norm": (d,),
            "norm1": (L, d), "norm2": (L, d),
            "wq": (L, d, h, dh), "wk": (L, d, kv, dh), "wv": (L, d, kv, dh),
            "wo": (L, h, dh, d), "q_norm": (L, dh), "k_norm": (L, dh),
            "w_up": (L, d, f), "w_gate": (L, d, f), "w_down": (L, f, d)}


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


def _make(model: dict, int8: bool, key: jax.Array) -> dict:
    shp = shapes(model)
    keys = dict(zip(shp, jax.random.split(key, len(shp))))
    d, f = model["hidden_size"], model["intermediate_size"]
    out = {}
    for name, s in shp.items():
        z = jax.random.normal(keys[name], s, jnp.float32)
        if name.endswith("norm") or name in ("norm1", "norm2"):
            out[name] = 1.0 + 0.1 * z
        else:
            w = z * (f if name == "w_down" else d) ** -0.5
            out[name] = round_per_channel(w, 127) if (
                int8 and name in PROJECTIONS) else w
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_jit(model_items: tuple, int8: bool, key: jax.Array) -> dict:
    return _make(dict(model_items), int8, key)


def make(model: dict, seed: int, *, int8: bool) -> dict:
    """Reference-layout float32 weights, in one jitted call on the device."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if k in ("num_hidden_layers", "hidden_size",
                                  "num_attention_heads",
                                  "num_key_value_heads", "head_dim",
                                  "intermediate_size", "vocab_size")))
    return _make_jit(items, int8, key_of(seed))


def program_params(w: dict) -> dict:
    """The program's parameter tree (``repro.models.model.init_params``
    layout, one scanned block kind): norms there are ``1 + scale``."""
    one = jnp.float32(1.0)
    block = {"norm1": w["norm1"] - one, "norm2": w["norm2"] - one,
             "inner": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                       "wo": w["wo"], "q_norm": w["q_norm"] - one,
                       "k_norm": w["k_norm"] - one},
             "ffn": {"w_up": w["w_up"], "w_gate": w["w_gate"],
                     "w_down": w["w_down"]}}
    return {"embed": w["embed"], "final_norm": w["final_norm"] - one,
            "blocks": (block,)}
