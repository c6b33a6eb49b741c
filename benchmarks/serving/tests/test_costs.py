"""The FLOP and byte functions of ``archs/qwen3.py`` against counts worked
out by hand for qwen3-1.7b (d 2048, 16 q / 8 kv heads of 128, d_ff 6144, 28
layers, vocab 151936), and ``costs.least_time``."""
import json

import costs
import spec

qwen3 = spec.load_arch("qwen3")

# the published depth, whatever depth the configuration files run
MODEL = dict(json.load(open(spec.HERE / "configs" / "qwen3-1.7b-densew.json")),
             num_hidden_layers=28)
# per layer: wq 2048x2048, wk and wv 2048x1024, wo 2048x2048, w_up and
# w_gate 2048x6144, w_down 6144x2048
PER_LAYER_KN = 4194304 + 2 * 2097152 + 4194304 + 3 * 12582912   # 50331648
PER_LAYER_K_PLUS_N = 4096 + 2 * 3072 + 4096 + 2 * 8192 + 8192   # 38912


def test_projection_params():
    assert sum(k * n for k, n in qwen3.projection_shapes(MODEL)) == PER_LAYER_KN
    assert qwen3.projection_params(MODEL) == 28 * PER_LAYER_KN == 1409286144


def test_decode_token_flops():
    # 2 * 1409286144 + 4 * 28 * 16 * 128 * 1000 + 2 * 2048 * 151936
    assert qwen3.decode_token_flops(MODEL, 1000) == (
        2818572288 + 229376000 + 622329856)


def test_prefill_flops():
    # 100 tokens: projections of each, attention over 1+2+...+100 = 5050
    # keys, the LM head once
    assert qwen3.prefill_flops(MODEL, 100) == (
        281857228800 + 4 * 28 * 16 * 128 * 5050 + 622329856)


def test_compressed_matmul_cost():
    flops, nbytes = qwen3.compressed_matmul_cost(MODEL, 8, 1000)
    assert flops == 2 * 8 * 1409286144 == 22548578304
    assert nbytes == 1000 + 4 * 8 * 28 * PER_LAYER_K_PLUS_N == 1000 + 34865152


def test_page_attention_cost():
    flops, nbytes = qwen3.page_attention_cost(MODEL, [100, 300], 5000)
    assert flops == 4 * 28 * 16 * 128 * 400 == 91750400
    # query and output, float32, of 16 heads of 128 in 28 layers, 2 tokens
    assert nbytes == 5000 + 2 * 4 * 28 * 16 * 128 * 2 == 5000 + 917504


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.least_time(197e12, 0, peaks) == 1.0
    assert costs.least_time(0, 819e9 * 2, peaks) == 2.0
    assert costs.least_time(197e12, 819e9 * 2, peaks) == 2.0
