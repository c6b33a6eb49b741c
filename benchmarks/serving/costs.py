"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work, not what today's kernels happen to do: a roofline
share built on them can only pass 100% if a kernel does less than the
algorithm needs.  ``model`` is a configuration file's dict.
"""
from __future__ import annotations


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


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of compute and memory."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
