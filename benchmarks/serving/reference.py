"""The plain reference: one sequence through the configuration's forward pass,
in float32 at the highest matmul precision, read position by position.

The forward pass and the LM head are the architecture module's (``archs/``),
written from the published architecture with no cache, no kernels and no
batching beyond one padded sequence, from the helpers here.  The reference
imports nothing of the program and reads only the weights of
``weights.make``.

``mode`` lowers one precision for the control of ``check.py``:

* ``int4_weights``: every projection and FFN matrix rounded to int4 with the
  per-channel rule the configuration states for its int8 weights (the
  architecture's forward pass names its matrices);
* ``fp8_projections``: the inputs of every projection, FFN matrix and the
  LM head (activations per row, weights per output column) rounded to
  float8 e4m3 (``mm``, ``fp8``), where the configuration states bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import spec

HI = jax.lax.Precision.HIGHEST
MODES = (None, "int4_weights", "fp8_projections")
FP8_MAX = 448.0                         # largest finite float8_e4m3fn


def fp8(x: jax.Array, axes: tuple) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                        1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(mode, eq, x, wt, w_axes):
    """A projection: ``einsum(eq, x, wt)`` at HIGHEST; in
    ``fp8_projections`` its inputs are first rounded to float8, ``x`` per
    row and ``wt`` over ``w_axes``."""
    if mode == "fp8_projections":
        x = fp8(x, (-1,))
        wt = fp8(wt, w_axes)
    return jnp.einsum(eq, x, wt, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: [S, H, dh]; rotate-half RoPE at positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


HEAD_BLOCK = 256                        # positions per LM-head block


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _read(arch, model_items: tuple, mode, w: dict, tokens: jax.Array,
          targets: jax.Array):
    """Per position of a padded sequence: the largest logit, the logits of
    ``targets`` ([S, k] token ids), and the token ranked first."""
    model = dict(model_items)
    x = arch.forward(model, mode, w, tokens)
    s = tokens.shape[0]
    xb = x.reshape(s // HEAD_BLOCK, HEAD_BLOCK, -1)
    tb = targets.reshape(s // HEAD_BLOCK, HEAD_BLOCK, -1)

    def one(args):
        xs, ts = args
        lg = arch.logits(model, mode, w, xs)
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, ts, -1),
                jnp.argmax(lg, -1).astype(jnp.int32))

    best, got, top = jax.lax.map(one, (xb, tb))
    return (best.reshape(s), got.reshape(s, -1), top.reshape(s))


def padded_len(n: int) -> int:
    return -(-n // HEAD_BLOCK) * HEAD_BLOCK


def read(model: dict, w: dict, tokens, targets, pad_to: int, mode=None):
    """``tokens`` [n] int; ``targets`` [n, k] int; both padded to
    ``padded_len(pad_to)`` positions, so one compiled program serves every
    sequence of a cell.  Returns numpy (best [n], target logits [n, k],
    top token [n])."""
    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}")
    n = len(tokens)
    s = padded_len(pad_to)
    if n > s:
        raise ValueError(f"{n} positions exceed the padded length {s}")
    tok = np.zeros(s, np.int32)
    tok[:n] = tokens
    tg = np.zeros((s, np.shape(targets)[1]), np.int32)
    tg[:n] = targets
    arch = spec.arch_of(model)
    items = tuple((k, model[k]) for k in arch.REFERENCE_KEYS)
    best, got, top = _read(arch, items, mode, w, jnp.asarray(tok),
                           jnp.asarray(tg))
    return (np.asarray(best)[:n], np.asarray(got)[:n], np.asarray(top)[:n])
