"""The trace reduction, on a hand-made trace whose numbers are worked out
by hand, and on a small trace recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import xplane_reduce

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_hand_made_trace():
    host = plane("/host:CPU", python=[
        ev("bench.step", 100, 400), ev("bench.seal", 300, 150),
        ev("bench.step", 600, 400), ev("outside", 1000, 500)])
    # two overlapping ops count once; an op before the window is clipped
    ops = [ev("%fusion.1 = f32[8] fusion()", 50, 100),
           ev("%compressed_matmul.3 = f32[8] custom-call()", 150, 100),
           ev("%fusion.2 = f32[8] fusion(f32[8] %compressed_matmul.3)", 200, 100),
           ev("%fused_page_attention = f32[8] custom-call()", 700, 200),
           ev("%fusion.9 = f32[8] fusion()", 1100, 100)]
    dev = plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=[ev("jit_x", 0, 2000)])
    out = xplane_reduce.reduce([host, dev])
    # window [100, 1000): busy [100, 300) and [700, 900)
    assert out["window_s"] == pytest.approx(900e-9)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["kernels"] == {"compressed_matmul": 100,
                              "fused_page_attention": 200}
    assert dict(out["device_ops"]) == pytest.approx({
        "fusion": 150e-9, "compressed_matmul": 100e-9,
        "fused_page_attention": 200e-9})
    # idle [300, 700): middle 500 is in no step (the steps end at 500 and
    # start at 600); idle [900, 1000): inside the second step
    assert dict(out["idle_gaps"]) == pytest.approx({
        "no host event": 400e-9, "bench.step": 100e-9})


def test_no_step_no_reduction():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("fusion", 0, 10)])
    assert xplane_reduce.reduce([plane("/host:CPU", python=[]), dev]) is None


def test_recorded_tpu_trace():
    """Two steps of a one-layer engine at qwen3-1.7b width (packed weights,
    APack KV pages) recorded on one TPU v5e."""
    out = xplane_reduce.reduce_dir(DATA)
    assert out is not None
    assert out["window_s"] == pytest.approx(0.238129423)
    assert out["busy_s"] == pytest.approx(0.228825195)
    # 7 packed matrices x 2 steps; one fused attention call a step
    assert out["kernels"] == {"compressed_matmul": 226838861,
                              "fused_page_attention": 1819099}
    ops = dict(out["device_ops"])
    assert ops["compressed_matmul"] == pytest.approx(0.226838861)
    assert "while" not in ops and len(ops) <= xplane_reduce.TOP
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])
