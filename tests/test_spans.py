"""Program spans (``repro.runtime.spans``): nesting, request ids, the
bounded ring and its ``complete`` flag, per-name totals; and, on a tiny
fused ``ServeEngine`` run under a CPU profiler trace, that the engine's
spans land where the work happens, add no host<->device transfer, and
appear on the trace's host line with the recorder's counts."""
import dataclasses
import pathlib
import sys
import threading
import time
from collections import Counter

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.runtime import spans
from repro.serve import Request, ServeEngine


def test_nesting_parents_and_rid_inheritance():
    rec = spans.Recorder()
    t0 = time.perf_counter()
    with rec.span("a", rid=7):
        with rec.span("b"):
            with rec.span("c", rid=9):
                pass
            with rec.span("d"):
                pass
    with rec.span("e"):
        pass
    win = rec.recorded(t0, time.perf_counter())
    assert win.complete
    by = {s.name: s for s in win.spans}
    assert [s.name for s in win.spans] == ["a", "b", "c", "d", "e"]
    assert by["a"].parent is None and by["e"].parent is None
    assert by["b"].parent == by["a"].id
    assert by["c"].parent == by["b"].id and by["d"].parent == by["b"].id
    assert by["b"].rid == 7 and by["d"].rid == 7      # inherited
    assert by["c"].rid == 9                            # given
    assert by["e"].rid is None
    for s in win.spans:
        assert s.t0 <= s.t1
    assert by["a"].t0 <= by["b"].t0 <= by["c"].t0 <= by["c"].t1 \
        <= by["d"].t0 <= by["d"].t1 <= by["b"].t1 <= by["a"].t1


def test_span_closes_on_exception():
    rec = spans.Recorder()
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("boom")
    with rec.span("after"):
        pass
    win = rec.recorded(t0, time.perf_counter())
    assert [s.name for s in win.spans] == ["outer", "inner", "after"]
    assert win.spans[2].parent is None      # the stack unwound


def test_ring_bound_and_complete_flag():
    rec = spans.Recorder(capacity=4)
    t_a = time.perf_counter()
    for _ in range(3):
        with rec.span("x"):
            pass
    t_b = time.perf_counter()
    assert rec.recorded(t_a, t_b).complete
    assert rec.recorded(float("-inf"), float("inf")).complete
    assert len(rec.recorded(t_a, t_b).spans) == 3
    for _ in range(3):
        with rec.span("y"):
            pass
    t_c = time.perf_counter()
    # two of the first three were dropped: that window is cut
    win = rec.recorded(t_a, t_b)
    assert not win.complete and len(win.spans) == 1
    assert len(rec._ring) == 4
    # a window that starts after every dropped span is whole
    win = rec.recorded(t_b, t_c)
    assert win.complete and [s.name for s in win.spans] == ["y"] * 3
    assert not rec.recorded(t_a, t_c).complete


def test_totals_count_every_span_the_ring_dropped_too():
    rec = spans.Recorder(capacity=2)
    for _ in range(5):
        with rec.span("p"):
            with rec.span("q"):
                time.sleep(0.001)
    tot = rec.totals()
    assert set(tot) == {"p", "q"}
    assert tot["p"][0] == 5 and tot["q"][0] == 5
    assert tot["p"][1] >= tot["q"][1] >= 0.005


def test_threads_keep_their_own_nesting_and_lose_no_span():
    rec = spans.Recorder(capacity=1000)
    n_threads, n = 16, 2000
    errors = []

    def work(k):
        try:
            for _ in range(n // 2):
                with rec.span("outer", rid=k):
                    with rec.span("inner"):
                        pass
        except Exception as e:            # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    tot = rec.totals()
    assert tot["outer"][0] == tot["inner"][0] == n_threads * n // 2
    win = rec.recorded(float("-inf"), float("inf"))
    assert not win.complete and len(win.spans) == 1000
    by_id = {s.id: s for s in win.spans}
    for s in win.spans:
        if s.name == "inner" and s.parent in by_id:
            assert by_id[s.parent].name == "outer"
            assert by_id[s.parent].rid == s.rid


def test_module_recorder_is_always_on():
    t0 = time.perf_counter()
    before = spans.totals().get("test.module", (0, 0.0))[0]
    with spans.span("test.module"):
        pass
    assert spans.totals()["test.module"][0] == before + 1
    win = spans.recorded(t0, time.perf_counter())
    assert [s.name for s in win.spans if s.name == "test.module"] \
        == ["test.module"]


# ------------------------------------------------- a tiny fused engine
# The run below, on the tree before the spans were added, made exactly
# these transfers (kv_stats()["transfers"]); the spans add none.
PARENT_D2H_CALLS = 36
PARENT_H2D_CALLS = 204
PROMPT_LENS = [5, 11, 9]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Serve three requests through a fused paged engine, one ``step()``
    at a time, under a CPU profiler trace."""
    base = configs.get_smoke_config("qwen3-1.7b")
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_len=48, kv_page_size=4,
                      kv_calib_pages=2)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=8)
            for i, n in enumerate(PROMPT_LENS)]
    for r in reqs:
        eng.submit(r)
    tdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    packed0 = eng.kv_stats()["kv_pages_packed"]
    encodes0 = eng.kv_stats()["kv_seal_encode_calls"]
    tot0 = spans.totals()
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    t0 = time.perf_counter()
    calls = 0
    while eng.queue or any(r is not None for r in eng.active):
        eng.step()
        calls += 1
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    tot1 = spans.totals()
    counts = {k: n - tot0.get(k, (0, 0.0))[0] for k, (n, _) in tot1.items()}
    counts = {k: n for k, n in counts.items() if n}
    xplane = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))[-1]
    return dict(eng=eng, reqs=reqs, calls=calls,
                window=spans.recorded(t0, t1), counts=counts,
                packed=eng.kv_stats()["kv_pages_packed"] - packed0,
                encodes=eng.kv_stats()["kv_seal_encode_calls"] - encodes0,
                xplane=xplane)


def test_one_step_span_per_step(traced_run):
    win = traced_run["window"]
    assert win.complete
    steps = [s for s in win.spans if s.name == "engine.step"]
    assert len(steps) == traced_run["calls"] > 0
    assert all(s.parent is None for s in steps)


def test_one_admit_span_per_admission_with_its_rid(traced_run):
    win = traced_run["window"]
    by_id = {s.id: s for s in win.spans}
    admits = [s for s in win.spans if s.name == "engine.admit"]
    assert sorted(s.rid for s in admits) == [r.rid for r in
                                              traced_run["reqs"]]
    for a in admits:
        assert by_id[a.parent].name == "engine.step"
        under = [s for s in win.spans if s.parent == a.id]
        assert {s.name for s in under} >= {"engine.prefill", "kv.ingest"}
        assert all(s.rid == a.rid for s in under)
    pulls = [s for s in win.spans if s.name == "kv.ingest.pull"]
    assert len(pulls) == len(admits)
    assert all(by_id[by_id[p.parent].parent].name == "engine.admit"
               for p in pulls)


def test_decode_step_phases_nest_in_the_step(traced_run):
    win = traced_run["window"]
    by_id = {s.id: s for s in win.spans}
    for name in ("engine.decode_dispatch", "engine.token_pull",
                 "kv.step_meta", "kv.claim_append", "kv.note_appended"):
        found = [s for s in win.spans if s.name == name]
        assert len(found) == traced_run["eng"].stats["steps"], name
        assert all(by_id[s.parent].name == "engine.step" for s in found)
    for s in win.spans:
        if s.name == "kv.seal.pull":
            assert s.rid is not None
            assert by_id[s.parent].name == "kv.note_appended"


def test_one_encode_span_per_packed_page(traced_run):
    """Pages seal one by one (``kv.seal`` with one ``kv.seal.requantize``)
    and pack in flushes (``kv.seal`` holding the batched encode calls,
    one ``kv.seal.encode`` each, and one ``kv.seal.crc`` per page)."""
    win, counts = traced_run["window"], traced_run["counts"]
    children = {}
    for s in win.spans:
        children.setdefault(s.parent, []).append(s.name)
    seals = [s for s in win.spans if s.name == "kv.seal"]
    flushes = [s for s in seals if "kv.seal.encode" in children.get(s.id, [])]
    per_page = [s for s in seals if s not in flushes]
    assert counts["kv.seal.encode"] == traced_run["encodes"] > 0
    assert counts["kv.seal"] == counts["kv.seal.requantize"] + len(flushes)
    assert all(children[s.id] == ["kv.seal.requantize"] for s in per_page)
    pages = [children[s.id].count("kv.seal.crc") for s in flushes]
    assert sum(pages) == traced_run["packed"] > 0
    assert counts["kv.seal.requantize"] >= traced_run["packed"]
    for s, n in zip(flushes, pages):
        assert children[s.id].count("kv.seal.encode") \
            == len(M.seal_chunks(2 * n))
    # the batching is real: some flush packs pages of several layers
    assert max(pages) > 1


def test_spans_add_no_transfer(traced_run):
    tr = traced_run["eng"].kv_stats()["transfers"]
    assert tr["d2h_calls"] == PARENT_D2H_CALLS
    assert tr["h2d_calls"] == PARENT_H2D_CALLS
    assert traced_run["eng"].kv_stats()["kv_pages_packed"] == 22


def test_profiler_host_line_holds_every_span(traced_run):
    """The spans are on the trace's clock: every name the recorder
    closed during the trace is an event of the host line that ran the
    steps, as many times."""
    pd = jax.profiler.ProfileData.from_file(str(traced_run["xplane"]))
    want = traced_run["counts"]
    lines = [Counter(e.name for e in line.events)
             for plane in pd.planes if not plane.name.startswith("/device")
             for line in plane.lines]
    host = [c for c in lines if c["engine.step"]]
    assert len(host) == 1
    got = {k: host[0][k] for k in want}
    assert got == want
