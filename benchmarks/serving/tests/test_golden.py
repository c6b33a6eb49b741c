"""The qwen3 configuration reads what it read before its architecture moved
into ``archs/qwen3.py``: ``data/qwen3_golden.npz`` was recorded by the same
computation on the tree before the move, at the tiny size on the CPU, and
every number must match it exactly: digests of the seeded weights and of
the program's parameter tree, the reference's three arrays in each mode,
and the cost functions at the tiny and the configured size.

    JAX_PLATFORMS=cpu python3 benchmarks/serving/tests/test_golden.py

rewrites the file from the current code; do so only with a change that is
meant to move these readings.
"""
import hashlib
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import costs  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402

GOLDEN = HERE / "data" / "qwen3_golden.npz"
SEED = 2 ** 31 + 17
FULL = json.loads((spec.HERE / "configs" / "qwen3-1.7b-densew.json")
                  .read_text())
TINY = dict(FULL, **spec.load_arch("qwen3").TINY)


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n"
                 .encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def record_weights() -> dict:
    arch = spec.arch_of(TINY)
    rec = {}
    for int8 in (False, True):
        w = weights.make(TINY, SEED, int8=int8)
        rec[f"weights_int8_{int8}"] = np.array(digest(w))
        rec[f"program_params_int8_{int8}"] = np.array(
            digest(arch.program_params(w)))
    return rec


def record_reference(mode) -> dict:
    w = weights.make(TINY, SEED, int8=False)
    rng = np.random.default_rng(7)
    n = 70
    tokens = rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
    targets = rng.integers(0, TINY["vocab_size"], (n, 2)).astype(np.int32)
    best, got, top = reference.read(TINY, w, tokens, targets, 96, mode=mode)
    return {"read_tokens": tokens, "read_targets": targets,
            f"read_{mode}_best": best, f"read_{mode}_got": got,
            f"read_{mode}_top": top}


def record_costs() -> dict:
    rec = {}
    for tag, m in (("tiny", TINY), ("full", FULL)):
        rec[f"cost_{tag}_projection_params"] = np.array(
            costs.projection_params(m), np.int64)
        rec[f"cost_{tag}_decode_token_flops"] = np.array(
            [costs.decode_token_flops(m, c) for c in (1, 100, 1000, 4096)],
            np.int64)
        rec[f"cost_{tag}_prefill_flops"] = np.array(
            [costs.prefill_flops(m, c) for c in (1, 64, 100, 1024)], np.int64)
        rec[f"cost_{tag}_page_attention_cost"] = np.array(
            [costs.page_attention_cost(m, cs, b) for cs, b in
             (([100, 300], 5000), ([1], 0), ([128, 1024, 1279], 10 ** 7))],
            np.int64)
        rec[f"cost_{tag}_compressed_matmul_cost"] = np.array(
            [costs.compressed_matmul_cost(m, r, b) for r, b in
             ((8, 1000), (1, 0), (1024, 10 ** 8))], np.int64)
    return rec


def record() -> dict:
    rec = record_weights()
    for mode in reference.MODES:
        rec.update(record_reference(mode))
    rec.update(record_costs())
    return rec


@pytest.fixture(scope="module")
def golden():
    with np.load(GOLDEN) as f:
        return {k: f[k] for k in f.files}


def same(rec: dict, golden: dict) -> None:
    for k, v in rec.items():
        want = golden[k]
        assert v.dtype == want.dtype and v.shape == want.shape, k
        assert np.array_equal(v, want), k


def test_golden_covers_the_record(golden):
    keys = set(record_weights()) | set(record_costs())
    for mode in reference.MODES:
        keys |= {f"read_{mode}_{a}" for a in ("best", "got", "top")}
    assert keys | {"read_tokens", "read_targets"} == set(golden)


def test_weights_match_golden(golden):
    same(record_weights(), golden)


@pytest.mark.parametrize("mode", reference.MODES)
def test_reference_matches_golden(mode, golden):
    same(record_reference(mode), golden)


def test_costs_match_golden(golden):
    same(record_costs(), golden)


if __name__ == "__main__":
    np.savez(GOLDEN, **record())
