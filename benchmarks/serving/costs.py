"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count the work, not what today's kernels happen to do: a roofline
share built on them can only pass 100% if a kernel does less than the
algorithm needs.  ``model`` is a configuration file's dict; the counts are
its architecture module's (``archs/<model_type>.py``), under the same names.
"""
from __future__ import annotations

import spec


def projection_params(model: dict) -> int:
    return spec.arch_of(model).projection_params(model)


def decode_token_flops(model: dict, context: int) -> int:
    return spec.arch_of(model).decode_token_flops(model, context)


def prefill_flops(model: dict, n: int) -> int:
    return spec.arch_of(model).prefill_flops(model, n)


def compressed_matmul_cost(model: dict, rows: int,
                           weight_bytes: int) -> tuple[int, int]:
    return spec.arch_of(model).compressed_matmul_cost(model, rows,
                                                      weight_bytes)


def page_attention_cost(model: dict, contexts: list[int],
                        kv_bytes: int) -> tuple[int, int]:
    return spec.arch_of(model).page_attention_cost(model, contexts, kv_bytes)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of compute and memory."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
