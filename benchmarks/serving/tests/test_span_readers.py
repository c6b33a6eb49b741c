"""The readers of the program's spans, on a tiny run of the chat cell on
the CPU: each reads a positive number from a window that holds admissions
and decode steps, reads nothing from a window whose spans the ring has
dropped, and the program's admission spans agree with the harness's."""
import copy
import itertools
import time

import pytest

import harness
import spec
import traffic as traffic_gen
import weights as weight_gen
from test_run_cpu import TINY_MIX

CELL = "qwen3-1.7b-densew.chat"
READERS = ["admit.prefill_ms", "admit.seal_ms",
           "page_seal.encode_ms_per_page", "step.pre_dispatch_ms"]


@pytest.fixture(scope="module")
def ctx():
    """A ``MetricContext`` over a window of whole steps after the fill,
    with the harness's spans wrapped as a traced run wraps them, long
    enough to hold two admissions."""
    cell = spec.load_cell(CELL)
    model = dict(copy.deepcopy(cell.config), **cell.arch.TINY)
    mix = dict(copy.deepcopy(cell.traffic), **TINY_MIX)
    seed = 2 ** 31 + 17
    cfg = cell.arch.program_config(model)
    params = cell.arch.program_params(
        weight_gen.make(model, seed, int8=False))
    eng = harness.build_engine(cfg, params, model, mix)
    requests = traffic_gen.generate(mix, model["vocab_size"], seed)
    loop = harness.ClosedLoop(eng, requests)
    for s in itertools.islice(requests, int(mix["slots"])):
        loop.submit(s)
    loop.step()
    hs = harness.Spans()
    hs.wrap(eng, "_prefill_into_slot", "admit")
    hs.wrap(eng.kv, "_seal", "seal")
    hs.wrap(eng.kv, "_flush_device", "seal")
    kv0 = eng.kv_stats()
    t_w0 = time.perf_counter()
    step_ends = []
    while len(step_ends) < 80:
        step_ends.append(loop.step())
        if len(hs.within("admit", t_w0, step_ends[-1])) >= 2:
            break
    return harness.MetricContext(
        model=model, peaks=spec.load_peaks("TPU v5 lite"), trace=None,
        spans=hs, window=(t_w0, step_ends[-1]), step_ends=step_ends,
        served=[], kv0=kv0, kv1=eng.kv_stats(),
        weight_stats=eng.weight_stats())


def read(name, ctx):
    return spec.load_reader(name)(ctx)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_positive_number(name, ctx):
    v = read(name, ctx)
    assert v is not None and v > 0, ctx.notes


def test_admission_parts_within_the_harness_admission(ctx):
    admit_ms = read("admit_ms", ctx)
    assert len(ctx.spans.within("admit", *ctx.window)) >= 2
    assert read("admit.prefill_ms", ctx) + read("admit.seal_ms", ctx) \
        <= admit_ms


def test_program_admission_spans_match_the_harness(ctx):
    from repro.runtime import spans
    program = [(s.t0, s.t1) for s in spans.recorded(*ctx.window).spans
               if s.name == "engine.admit"]
    bench = [(a, b) for a, b in ctx.spans.times["admit"]
             if ctx.window[0] <= a < ctx.window[1]]
    assert len(program) == len(bench) >= 2
    for (p0, p1), (b0, b1) in zip(program, bench):
        assert b0 <= p0 and p1 <= b1
        assert abs((p1 - p0) - (b1 - b0)) < 1e-3


def test_readers_read_nothing_from_a_cut_ring(ctx):
    """Run last in this module: it pushes the window's spans out of the
    program's ring."""
    from repro.runtime import spans
    for _ in range(spans.CAPACITY):
        with spans.span("test.filler"):
            pass
    assert not spans.recorded(*ctx.window).complete
    for name in READERS:
        notes = len(ctx.notes)
        assert read(name, ctx) is None
        assert len(ctx.notes) == notes + 1
