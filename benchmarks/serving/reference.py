"""Plain Qwen3 forward pass in float32 at the highest matmul precision.

Written from the published architecture (pre-norm RMSNorm blocks; GQA
attention with a per-head RMSNorm on q and k before rotate-half RoPE; SwiGLU
FFN; tied embedding and LM head), with no cache, no kernels and no batching
beyond one padded sequence.  It imports nothing of the program and reads
only the weights of ``weights.make``.

``mode`` lowers one precision for the control of ``check.py``:

* ``int4_weights``: every projection and FFN matrix rounded to int4 with the
  per-channel rule the configuration states for its int8 weights;
* ``fp8_projections``: the inputs of every projection, FFN matrix and the
  LM head (activations per row, weights per output column) rounded to
  float8 e4m3, where the configuration states bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import round_per_channel

HI = jax.lax.Precision.HIGHEST
MODES = (None, "int4_weights", "fp8_projections")
FP8_MAX = 448.0                         # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axes: tuple) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                        1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [S, H, dh]; rotate-half RoPE at positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(model: dict, mode, w: dict, tokens: jax.Array) -> jax.Array:
    """Final hidden states [S, d] of one sequence."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model["head_dim"]
    s = tokens.shape[0]

    def mm(eq, x, wt, w_axes):
        if mode == "fp8_projections":
            x = _fp8(x, (-1,))
            wt = _fp8(wt, w_axes)
        return jnp.einsum(eq, x, wt, precision=HI)

    def layer(x, p):
        if mode == "int4_weights":
            p = {k: (round_per_channel(v[None], 7)[0]
                     if k in ("wq", "wk", "wv", "wo", "w_up", "w_gate",
                              "w_down") else v) for k, v in p.items()}
        a = _rms(x, p["norm1"], eps)
        q = mm("sd,dhk->shk", a, p["wq"], (0,))
        k = mm("sd,dhk->shk", a, p["wk"], (0,))
        v = mm("sd,dhk->shk", a, p["wv"], (0,))
        q = _rope(_rms(q, p["q_norm"], eps), theta)
        k = _rope(_rms(k, p["k_norm"], eps), theta)
        q = q.reshape(s, kv, h // kv, dh)
        scores = jnp.einsum("sgrk,tgk->grst", q, k, precision=HI) * dh ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        o = jnp.einsum("grst,tgk->sgrk", jax.nn.softmax(scores, -1), v,
                       precision=HI).reshape(s, h, dh)
        x = x + mm("shk,hkd->sd", o, p["wo"], (0, 1))
        b = _rms(x, p["norm2"], eps)
        up = mm("sd,df->sf", b, p["w_up"], (0,))
        gate = mm("sd,df->sf", b, p["w_gate"], (0,))
        x = x + mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"], (0,))
        return x, None

    stack = {k: w[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                               "q_norm", "k_norm", "w_up", "w_gate",
                               "w_down")}
    x, _ = jax.lax.scan(layer, w["embed"][tokens], stack)
    return _rms(x, w["final_norm"], eps)


def _logits(model: dict, mode, w: dict, x: jax.Array) -> jax.Array:
    """LM head (tied to the embedding): [n, d] -> [n, V]."""
    e = w["embed"]
    if mode == "fp8_projections":
        x, e = _fp8(x, (-1,)), _fp8(e, (-1,))
    return jnp.einsum("sd,vd->sv", x, e, precision=HI)


HEAD_BLOCK = 256                        # positions per LM-head block


@functools.partial(jax.jit, static_argnums=(0, 1))
def _read(model_items: tuple, mode, w: dict, tokens: jax.Array,
          targets: jax.Array):
    """Per position of a padded sequence: the largest logit, the logits of
    ``targets`` ([S, k] token ids), and the token ranked first."""
    model = dict(model_items)
    x = _forward(model, mode, w, tokens)
    s = tokens.shape[0]
    xb = x.reshape(s // HEAD_BLOCK, HEAD_BLOCK, -1)
    tb = targets.reshape(s // HEAD_BLOCK, HEAD_BLOCK, -1)

    def one(args):
        xs, ts = args
        lg = _logits(model, mode, w, xs)
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, ts, -1),
                jnp.argmax(lg, -1).astype(jnp.int32))

    best, got, top = jax.lax.map(one, (xb, tb))
    return (best.reshape(s), got.reshape(s, -1), top.reshape(s))


def model_items(model: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, model[k]) for k in keys)


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
    best, got, top = _read(model_items(model), mode, w, jnp.asarray(tok),
                           jnp.asarray(tg))
    return (np.asarray(best)[:n], np.asarray(got)[:n], np.asarray(top)[:n])
