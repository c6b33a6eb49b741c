"""Qwen3 (``"model_type": "qwen3"``): pre-norm RMSNorm blocks; GQA attention
with a per-head RMSNorm on q and k before rotate-half RoPE; SwiGLU FFN; tied
embedding and LM head.

Everything the serving benchmark knows of the architecture: the program's
``ModelConfig``, the reference's weight layout and its seeded init, the
program's parameter tree built from those weights, the plain forward pass
and LM head, the operations and bytes the readers count, and the tests'
tiny size.  The reference part imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import HI, fp8, mm, rms, rope
from weights import round_per_channel

PROJECTIONS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")
# the configuration keys that ``init`` and ``forward`` compile on
WEIGHT_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "intermediate_size",
               "vocab_size")
REFERENCE_KEYS = WEIGHT_KEYS + ("rms_norm_eps", "rope_theta")

# The CPU tests' size, and its limit, set from its readings on the CPU (seeds
# 11-14): sound runs read at most 0.0233, the controls at least 0.224 (fp8
# projections) and 1.10 (int4 weights).  The cells' own limits are set for
# their size.
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            vocab_size=512)
TINY_LIMIT = 0.1


def program_config(model: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="qwen3", family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], d_ff=model["intermediate_size"],
        vocab_size=model["vocab_size"], qk_norm=True, mlp_variant="swiglu",
        rope_theta=float(model["rope_theta"]),
        tie_embeddings=model["tie_word_embeddings"],
        norm_eps=float(model["rms_norm_eps"]), param_dtype="float32",
        kv_cache_dtype=model["serving"]["kv"])


# -- weights -----------------------------------------------------------------

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


def init(model: dict, int8: bool, key: jax.Array) -> dict:
    """Normal weights with std hidden_size**-0.5 (intermediate_size**-0.5
    for ``w_down``), norm gains 1 + 0.1 * normal; with ``int8`` every
    projection and FFN matrix is rounded to int8 per channel."""
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


# -- reference ---------------------------------------------------------------

def forward(model: dict, mode, w: dict, tokens: jax.Array) -> jax.Array:
    """Final hidden states [S, d] of one sequence.  ``int4_weights`` rounds
    every projection and FFN matrix to int4 with the per-channel rule the
    configuration states for its int8 weights."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model["head_dim"]
    s = tokens.shape[0]

    def layer(x, p):
        if mode == "int4_weights":
            p = {k: (round_per_channel(v[None], 7)[0]
                     if k in PROJECTIONS else v) for k, v in p.items()}
        a = rms(x, p["norm1"], eps)
        q = mm(mode, "sd,dhk->shk", a, p["wq"], (0,))
        k = mm(mode, "sd,dhk->shk", a, p["wk"], (0,))
        v = mm(mode, "sd,dhk->shk", a, p["wv"], (0,))
        q = rope(rms(q, p["q_norm"], eps), theta)
        k = rope(rms(k, p["k_norm"], eps), theta)
        q = q.reshape(s, kv, h // kv, dh)
        scores = jnp.einsum("sgrk,tgk->grst", q, k, precision=HI) * dh ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        o = jnp.einsum("grst,tgk->sgrk", jax.nn.softmax(scores, -1), v,
                       precision=HI).reshape(s, h, dh)
        x = x + mm(mode, "shk,hkd->sd", o, p["wo"], (0, 1))
        b = rms(x, p["norm2"], eps)
        up = mm(mode, "sd,df->sf", b, p["w_up"], (0,))
        gate = mm(mode, "sd,df->sf", b, p["w_gate"], (0,))
        x = x + mm(mode, "sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"],
                   (0,))
        return x, None

    stack = {k: w[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                               "q_norm", "k_norm", "w_up", "w_gate",
                               "w_down")}
    x, _ = jax.lax.scan(layer, w["embed"][tokens], stack)
    return rms(x, w["final_norm"], eps)


def logits(model: dict, mode, w: dict, x: jax.Array) -> jax.Array:
    """LM head (tied to the embedding): [n, d] -> [n, V]."""
    e = w["embed"]
    if mode == "fp8_projections":
        x, e = fp8(x, (-1,)), fp8(e, (-1,))
    return jnp.einsum("sd,vd->sv", x, e, precision=HI)


# -- operations and bytes ----------------------------------------------------

def projection_shapes(model: dict) -> list[tuple[int, int]]:
    """(K, N) of every projection and FFN matrix of one layer."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv, dh = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    return [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
            (d, f), (d, f), (f, d)]


def projection_params(model: dict) -> int:
    """Weights in the projection and FFN matrices of all layers."""
    return model["num_hidden_layers"] * sum(
        k * n for k, n in projection_shapes(model))


def attention_flops(model: dict, context: int) -> int:
    """QK^T and PV of one query token over ``context`` keys, all layers."""
    return (4 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"] * context)


def head_flops(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def decode_token_flops(model: dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context`` keys
    (itself included): projections, attention and the LM head."""
    return (2 * projection_params(model) + attention_flops(model, context)
            + head_flops(model))


def prefill_flops(model: dict, n: int) -> int:
    """Model FLOPs of a prefill of ``n`` tokens: projections of every
    token, causal attention (token t attends t+1 keys), and the LM head of
    the last token only, the one that is served."""
    return (2 * projection_params(model) * n
            + attention_flops(model, n * (n + 1) // 2) + head_flops(model))


def compressed_matmul_cost(model: dict, rows: int,
                           weight_bytes: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one forward's packed projections at ``rows``
    activation rows: 2*rows*K*N per matrix; the packed weights' payload
    (``weight_bytes``: coded bits and scales, for all layers) plus float32
    activations in and out."""
    shp = projection_shapes(model)
    layers = model["num_hidden_layers"]
    flops = 2 * rows * layers * sum(k * n for k, n in shp)
    act = 4 * rows * layers * sum(k + n for k, n in shp)
    return flops, weight_bytes + act


def page_attention_cost(model: dict, contexts: list[int],
                        kv_bytes: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step's paged attention over every
    layer: each active sequence's query attends its ``context`` cached keys
    (4*H*dh FLOPs per key per layer); bytes are the KV payload read
    (``kv_bytes``) plus the float32 query and output of every head."""
    flops = sum(attention_flops(model, c) for c in contexts)
    qo = (2 * 4 * model["num_hidden_layers"] * model["num_attention_heads"]
          * model["head_dim"] * len(contexts))
    return flops, kv_bytes + qo
