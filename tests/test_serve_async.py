"""Async event-loop engine tests (ISSUE 7): lockstep sync-vs-async token
parity (including preempt/spill/resume mid-run), chunked-prefill
equivalence vs monolithic ingest, the pool over-commit regression, the
bucketed-prefill recompile-storm guard, monotonic latency clocks, and
fault injection on the overlapped host phase."""
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.serve import FaultInjector, Request, ServeEngine
from repro.serve import engine as serve_engine
from repro.serve.engine import prefill_bucket

KEY = jax.random.PRNGKey(0)


def apack_cfg(**kw):
    return dataclasses.replace(configs.get_smoke_config("qwen3-1.7b"),
                               kv_cache_dtype="apack-int8", **kw)


def hetero_cfg(**kw):
    return dataclasses.replace(configs.get_hetero_smoke_config(),
                               kv_cache_dtype="apack-int8", **kw)


@pytest.fixture(scope="module")
def qwen_params():
    return M.init_params(configs.get_smoke_config("qwen3-1.7b"), KEY)


@pytest.fixture(scope="module")
def hetero_params():
    return M.init_params(configs.get_hetero_smoke_config(), KEY)


# deliberately non-power-of-two lengths: every prompt exercises the
# padded+masked bucket path, not the exact-length fast path
PROMPT_LENS = [5, 11, 9, 20, 6]


def _mk_requests(cfg, lens, max_new, seed=3, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, L)
                    .astype(np.int32),
                    max_new_tokens=max_new, **kw)
            for i, L in enumerate(lens)]


def _run(cfg, params, scheduler, *, lens=PROMPT_LENS, max_new=10,
         max_batch=2, max_len=48, preempt_at=None, **ekw):
    """Serve one wave; optionally preempt-with-spill slot 0 after the
    ``preempt_at``-th decode step (mid-run spill -> readahead -> resume)."""
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                      kv_page_size=4, kv_calib_pages=2,
                      scheduler=scheduler, **ekw)
    reqs = _mk_requests(cfg, lens, max_new)
    for r in reqs:
        eng.submit(r)
    if preempt_at is not None:
        for _ in range(500):
            eng.step()
            if eng.stats["steps"] >= preempt_at:
                break
        assert eng.active[0] is not None
        eng.preempt(0, spill=True, requeue="tail")
    eng.run_until_drained(max_steps=2000)
    for r in reqs:
        assert r.done and not r.error, (r.rid, r.error)
    return eng, reqs


class TestAsyncSyncParity:
    def test_qwen3_with_preempt_spill_resume(self, qwen_params):
        """Greedy tokens bit-identical between the sync and async
        engines on varied-length traffic, including a mid-run
        preempt-with-spill + readahead resume in BOTH engines (the async
        one must drain its in-flight step before snapshotting)."""
        cfg = apack_cfg()
        es, rs = _run(cfg, qwen_params, "sync", preempt_at=3)
        ea, ra = _run(cfg, qwen_params, "async", preempt_at=3)
        assert es.stats["preempted"] >= 1 and ea.stats["preempted"] >= 1
        assert es.stats["spilled_requests"] >= 1
        for a, b in zip(rs, ra):
            assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        # the async run actually pumped chunked prefills
        assert ea.stats["prefill_chunks"] > 0

    def test_hetero_with_preempt_spill_resume(self, hetero_params):
        """Same lockstep parity on the heterogeneous smoke config
        (global + rolling + recurrent-kind layers): pad masking must
        freeze recurrent state and build the rolling ring correctly for
        every layer kind."""
        cfg = hetero_cfg()
        es, rs = _run(cfg, hetero_params, "sync", preempt_at=3)
        ea, ra = _run(cfg, hetero_params, "async", preempt_at=3)
        assert ea.stats["preempted"] >= 1
        for a, b in zip(rs, ra):
            assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)

    def test_chunked_prefill_equivalence(self, qwen_params):
        """A long prompt ingested in tiny chunks interleaved with decode
        steps produces the same pages — greedy tokens bit-identical to
        the sync engine's monolithic ``ingest_prefill``."""
        cfg = apack_cfg()
        lens = [20, 7, 23]
        es, rs = _run(cfg, qwen_params, "sync", lens=lens, max_new=6)
        ea, ra = _run(cfg, qwen_params, "async", lens=lens, max_new=6,
                      prefill_chunk_tokens=3)
        for a, b in zip(rs, ra):
            assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        # ceil(20/3) + ceil(7/3) + ceil(23/3) when fully paced; idle-time
        # draining can merge steps but each prompt takes >= 1 chunk
        assert ea.stats["prefill_chunks"] >= len(lens)

    def test_async_requires_fused_paged_kv(self, qwen_params):
        cfg = configs.get_smoke_config("qwen3-1.7b")   # dense KV
        with pytest.raises(ValueError, match="scheduler='async'"):
            ServeEngine(cfg, qwen_params, max_batch=2, max_len=32,
                        scheduler="async")
        with pytest.raises(ValueError, match="unknown scheduler"):
            ServeEngine(cfg, qwen_params, max_batch=2, max_len=32,
                        scheduler="overlapped")


class TestPaddedPrefill:
    def test_padded_forward_matches_exact(self, qwen_params):
        """Model-level masking check: a zero-padded prompt with
        ``true_len`` produces the same last-token logits as the exact
        unpadded forward (pads excluded from attention, logits sliced at
        the true position)."""
        cfg = configs.get_smoke_config("qwen3-1.7b")
        rng = np.random.default_rng(9)
        s, bucket = 11, 16
        toks = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
        exact, _, _ = M.forward(cfg, qwen_params,
                                {"tokens": jnp.asarray(toks[None])},
                                remat=False, collect_cache=True,
                                last_only=True)
        padded_toks = np.zeros((1, bucket), np.int32)
        padded_toks[0, :s] = toks
        padded, _, _ = M.forward(cfg, qwen_params,
                                 {"tokens": jnp.asarray(padded_toks)},
                                 remat=False, collect_cache=True,
                                 last_only=True,
                                 true_len=jnp.asarray(s, jnp.int32))
        np.testing.assert_allclose(np.asarray(exact), np.asarray(padded),
                                   rtol=2e-4, atol=2e-5)

    def test_prefill_bucket_values(self):
        assert prefill_bucket(5, 64) == 8
        assert prefill_bucket(8, 64) == 8           # exact power of two
        assert prefill_bucket(9, 64) == 16
        assert prefill_bucket(40, 48) == 48         # capped at max_len

    def test_recompile_storm_warns(self, monkeypatch, caplog):
        monkeypatch.setattr(serve_engine, "_seen_prefill_buckets", set())
        monkeypatch.setattr(serve_engine,
                            "PREFILL_BUCKET_WARN_THRESHOLD", 3)
        with caplog.at_level(logging.WARNING, logger="repro.serve"):
            for s in (1, 2, 4):
                prefill_bucket(s, 64)
            assert not caplog.records          # at threshold: quiet
            prefill_bucket(8, 64)              # 4th distinct size: warn
            assert len(caplog.records) == 1
            assert "recompile storm" in caplog.records[0].message
            prefill_bucket(8, 64)              # repeat size: no new warn
            assert len(caplog.records) == 1


class TestAdmissionAccounting:
    def test_head_never_its_own_pressure_victim(self, qwen_params):
        """Over-commit regression (pre-fix this FAILS): the queue head —
        preempted but still holding its reservation — must never be
        selected by ``_relieve_pressure``'s parked-victim scan.  Spilling
        the head releases the very reservation the caller's ``need=0``
        was computed against, so the head would resume unreserved and
        ``_reserved_total`` would under-count the pool forever after."""
        cfg = apack_cfg()
        eng = ServeEngine(cfg, qwen_params, max_batch=2, max_len=32,
                          kv_page_size=4, kv_calib_pages=2)
        reqs = _mk_requests(cfg, [8, 8], max_new=8)
        for r in reqs:
            eng.submit(r)
        for _ in range(20):
            if all(a is not None for a in eng.active):
                break
            eng.step()
        head = eng.active[1]
        eng.preempt(1, spill=False, requeue="head")
        assert head.rid in eng._preempted
        assert head.rid in eng._reserved        # reservation survives
        # the stale-need scenario: relief requested on the head's behalf
        relieved = eng._relieve_pressure(head, 0)
        assert not relieved, "head was spilled to relieve itself"
        assert head.rid in eng._reserved
        assert head.rid not in eng._spilled
        eng.run_until_drained(max_steps=500)
        assert all(r.done and not r.error for r in reqs)
        # reservation accounting drained back to zero — no over-commit
        assert eng._reserved_total == 0 and not eng._reserved

    def test_slo_priority_admission(self, qwen_params):
        """EDF-over-FIFO: with the pool sized for one request, a
        late-submitted request with a tight SLO is admitted before
        earlier FIFO traffic; SLO-free traffic stays pure FIFO."""
        cfg = apack_cfg()
        n_layers = cfg.n_cycles * len(cfg.cycle)
        eng = ServeEngine(cfg, qwen_params, max_batch=4, max_len=16,
                          kv_page_size=4, kv_calib_pages=2,
                          kv_pages=n_layers * 4)
        reqs = _mk_requests(cfg, [8, 8], max_new=4)
        urgent = _mk_requests(cfg, [8], max_new=4, slo_ms=1.0)[0]
        urgent.rid = 99
        for r in reqs:
            eng.submit(r)
        eng.submit(urgent)
        eng._retire()
        eng._admit()
        active_rids = [r.rid for r in eng.active if r is not None]
        assert active_rids == [99], active_rids
        eng.run_until_drained(max_steps=500)
        assert all(r.done for r in reqs) and urgent.done


class TestClocksAndFaults:
    def test_monotonic_latency_clocks(self, qwen_params, monkeypatch):
        """Request timing must not touch the wall clock: with
        ``time.time`` frozen (NTP-step stand-in), latencies stay
        positive and the percentile stats populate."""
        monkeypatch.setattr(time, "time", lambda: 1.0e9)
        cfg = configs.get_smoke_config("qwen3-1.7b")   # dense KV: fast
        eng = ServeEngine(cfg, qwen_params, max_batch=2, max_len=32)
        reqs = _mk_requests(cfg, [8, 8], max_new=4)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        for r in reqs:
            assert r.t_done > r.t_submit > 0.0
            assert r.t_admit >= r.t_submit
            assert r.t_done >= r.t_first >= r.t_admit
        lat = eng.latency_stats()
        assert lat["n"] == 2
        assert lat["e2e_p50"] > 0.0
        assert lat["queue_wait_p99"] >= 0.0
        assert lat["e2e_p99"] > 0.0
        assert 0.0 < lat["ttft_p50"] <= lat["e2e_p99"]

    def test_host_delay_fault_degrades_latency_not_tokens(self,
                                                          qwen_params):
        """``delay_host_work`` lands on the async engine's overlapped
        phase: the injected stalls are consumed there, the sync engine
        ignores them, and greedy tokens are unaffected."""
        cfg = apack_cfg()
        inj = FaultInjector()
        inj.delay_host_work(0.02, n=3)
        ea, ra = _run(cfg, qwen_params, "async", lens=[9, 6], max_new=5,
                      faults=inj)
        assert inj.stats["host_work_delayed"] == 3
        inj2 = FaultInjector()
        inj2.delay_host_work(0.02, n=3)
        es, rs = _run(cfg, qwen_params, "sync", lens=[9, 6], max_new=5,
                      faults=inj2)
        assert inj2.stats["host_work_delayed"] == 0   # no overlap phase
        for a, b in zip(ra, rs):
            assert a.tokens == b.tokens
