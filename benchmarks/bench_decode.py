"""Decode hot-path benchmark: serving steps/sec and per-step host<->device
transfer traffic of the paged APack KV engine — device-resident fused path
(on-device append + fused gather-decode attention) vs the legacy
materialize path (dense cache rebuilt from the pool every step).

One engine per mode serves identical request waves; the first wave warms
the jit caches, the next ``REPEAT`` waves are timed and the *minimum*
per-step time is reported (min-of-3).  Transfer bytes come from the
engine's own ``kv.transfers`` accounting (every KV-path byte crossing the
boundary goes through ``PagedKVCache._fetch``/``_put``), and
``steady_d2h_calls`` is the per-step minimum of ``device_get`` calls — the
fused path must report 0 (its only d2h is the amortized page-seal pull,
absent on non-boundary steps), which is the CI transfer guard.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

REPEAT = 3


def _build_engine(arch: str, fused: bool, *, max_batch: int, max_len: int):
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serve import ServeEngine

    base = configs.get_smoke_config(arch)
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))
    return cfg, ServeEngine(cfg, params, max_batch=max_batch,
                            max_len=max_len, kv_page_size=4,
                            kv_calib_pages=2, kv_fused=fused)


def _serve_wave(eng, cfg, seed: int, *, requests: int, prompt_len: int,
                max_new: int) -> dict:
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=seed * 1000 + i,
                    prompt=rng.integers(0, cfg.vocab_size, prompt_len)
                    .astype(np.int32), max_new_tokens=max_new)
            for i in range(requests)]
    for r in reqs:
        eng.submit(r)
    # admissions (prefill) happen in the first, untimed step — the row
    # measures the decode hot path, not prompt processing
    eng.step()
    steps0 = eng.stats["steps"]
    tr0 = dict(eng.kv.transfers)
    per_step_d2h = []
    t0 = time.perf_counter()
    for _ in range(500):                     # bounded: a stalled engine
        before = eng.kv.transfers["d2h_calls"]   # must fail, not hang CI
        n = eng.step()
        if n == 0 and not eng.queue:
            break
        per_step_d2h.append(eng.kv.transfers["d2h_calls"] - before)
    else:
        raise RuntimeError("engine failed to drain within 500 steps")
    wall = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    steps = max(eng.stats["steps"] - steps0, 1)
    moved = sum(eng.kv.transfers[k] - tr0[k]
                for k in ("h2d_bytes", "d2h_bytes"))
    return {"s_per_step": wall / steps,
            "bytes_per_step": moved / steps,
            "steady_d2h_calls": min(per_step_d2h) if per_step_d2h else 0,
            "steps": steps}


def decode_throughput(arch: str = "qwen3-1.7b", fused: bool = True, *,
                      requests: int = 2, prompt_len: int = 8,
                      max_new: int = 12, max_batch: int = 2,
                      max_len: int = 32) -> dict:
    """Min-of-``REPEAT`` per-step decode time for one engine mode."""
    cfg, eng = _build_engine(arch, fused, max_batch=max_batch,
                             max_len=max_len)
    kw = dict(requests=requests, prompt_len=prompt_len, max_new=max_new)
    _serve_wave(eng, cfg, 0, **kw)              # warmup: jit compiles
    waves = [_serve_wave(eng, cfg, 1 + i, **kw) for i in range(REPEAT)]
    best = min(waves, key=lambda w: w["s_per_step"])
    return {
        "mode": "fused" if fused else "materialize",
        "us_per_step": best["s_per_step"] * 1e6,
        "steps_per_s": 1.0 / best["s_per_step"],
        "bytes_per_step": best["bytes_per_step"],
        "steady_d2h_calls": min(w["steady_d2h_calls"] for w in waves),
        "kv_ratio": eng.kv_stats()["kv_ratio"],
    }


def drift_scenario(arch: str = "qwen3-1.7b", *, requests: int = 4,
                   prompt_len: int = 9, max_new: int = 24) -> dict:
    """Two-phase drifting workload: diverse prompts, then a repetitive hot
    prompt (serving traffic narrowing onto one workload).  Runs a
    refresh-enabled engine (adaptive table refresh + budgeted page
    re-pack) and a frozen-table control over identical requests and
    reports per-phase *windowed* KV read ratios — read + shipped-table
    bytes over raw bytes moved inside the phase, so the pre-refresh window
    is not averaged away by cumulative accounting — plus the re-pack
    overhead per decode step and the steady-state d2h-call floor with
    refresh active (must stay 0: sketches are fed at page-seal time and
    re-pack reads the host pool mirror)."""
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serve import Request, ServeEngine

    base = configs.get_smoke_config(arch)
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))

    def run(refresh: bool):
        rng = np.random.default_rng(7)
        eng = ServeEngine(cfg, params, max_batch=requests,
                          max_len=prompt_len + max_new + 8, kv_page_size=4,
                          kv_calib_pages=1, kv_refresh=refresh,
                          kv_refresh_every_pages=24, kv_refresh_min_pages=8,
                          kv_repack_budget=32)
        phases = ([rng.integers(0, cfg.vocab_size, prompt_len)
                   .astype(np.int32) for _ in range(requests)],
                  [np.full(prompt_len, 7, np.int32)
                   for _ in range(requests)])
        ratios, tokens, d2h_steps = [], [], []
        for p, prompts in enumerate(phases):
            t0 = dict(eng.kv.traffic)
            reqs = [Request(rid=100 * p + i, prompt=pr,
                            max_new_tokens=max_new)
                    for i, pr in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            for _ in range(500):
                before = eng.kv.transfers["d2h_calls"]
                n = eng.step()
                if n == 0 and not eng.queue:
                    break
                if p == 1:
                    d2h_steps.append(eng.kv.transfers["d2h_calls"] - before)
            else:
                raise RuntimeError("drift engine failed to drain")
            d = lambda k: eng.kv.traffic[k] - t0[k]
            ratios.append((d("kv_read_bytes") + d("kv_table_bytes"))
                          / max(d("kv_raw_bytes"), 1))
            tokens.extend(r.tokens for r in reqs)
        return eng, ratios, tokens, min(d2h_steps) if d2h_steps else 0

    eng_f, (fa, fb), toks_f, _ = run(False)
    eng_r, (ra, rb), toks_r, d2h = run(True)
    if toks_f != toks_r:
        # refresh must be invisible to sampling (losslessness) — a token
        # divergence is a correctness bug, not a perf regression
        raise RuntimeError("greedy tokens diverged between refresh and "
                           "frozen-table runs")
    steps = max(eng_r.stats["steps"], 1)
    t = eng_r.kv.traffic
    return {
        "pre_refresh_ratio": ra, "post_refresh_ratio": rb,
        "frozen_pre_ratio": fa, "frozen_post_ratio": fb,
        "refreshes": eng_r.stats["kv_refreshes"],
        "pages_repacked": eng_r.stats["kv_pages_repacked"],
        "repack_bytes_per_step": (t["kv_repack_read_bytes"]
                                  + t["kv_repack_write_bytes"]) / steps,
        "steady_d2h_calls": d2h,
        "generation": eng_r.kv.generation,
    }


def pressure_scenario(arch: str = "qwen3-1.7b", *, requests: int = 4,
                      prompt_len: int = 8, max_new: int = 16,
                      pool_frac: float = 0.6,
                      slot_deadline: int = 6) -> dict:
    """Memory-pressure workload: the same request wave served twice — an
    uncontended control (pool sized for the full working set) and a
    pressure run whose pool holds only ``pool_frac`` of the working-set
    pages, with pressure escalation + a slot deadline forcing
    preempt-with-spill rotation through the compressed host spill tier.

    Graceful-degradation gates (enforced here, re-checked in CI from the
    emitted rows): every request completes, greedy tokens are
    bit-identical to the uncontended run (spill/readahead is lossless),
    the steady-state decode loop still makes zero ``device_get`` calls
    (readahead h2d rides admission events), and the spill traffic is
    APack-compressed (spill ratio < 1.0 vs the dense working set)."""
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serve import Request, ServeEngine

    base = configs.get_smoke_config(arch)
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))
    max_len = prompt_len + max_new + 8
    per_req = M.PagedKVCache.pages_for_config(
        cfg, prompt_len + max_new, 4)
    working = per_req * requests

    def run(pages, pressure: bool):
        eng = ServeEngine(
            cfg, params, max_batch=requests, max_len=max_len,
            kv_page_size=4, kv_calib_pages=2, kv_pages=pages,
            kv_pressure=pressure,
            slot_deadline_steps=slot_deadline if pressure else None)
        rng = np.random.default_rng(11)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                   prompt_len)
                        .astype(np.int32), max_new_tokens=max_new)
                for i in range(requests)]
        for r in reqs:
            eng.submit(r)
        per_step_d2h = []
        for _ in range(500):
            before = eng.kv.transfers["d2h_calls"]
            n = eng.step()
            if n == 0 and not eng.queue:
                break
            per_step_d2h.append(eng.kv.transfers["d2h_calls"] - before)
        else:
            raise RuntimeError("pressure engine failed to drain")
        bad = [r.rid for r in reqs if not r.done or r.error]
        if bad:
            raise RuntimeError(f"requests failed under pressure: {bad}")
        return eng, [r.tokens for r in reqs], \
            min(per_step_d2h) if per_step_d2h else 0

    _, toks_c, _ = run(None, False)                 # uncontended control
    pages_p = max(per_req, int(np.ceil(working * pool_frac)))
    eng, toks_p, d2h = run(pages_p, True)
    if toks_c != toks_p:
        # spill -> readahead -> resume must be invisible to sampling
        raise RuntimeError("greedy tokens diverged between pressure and "
                           "uncontended runs")
    tr = eng.kv.traffic
    if tr["kv_spill_pages"] == 0:
        raise RuntimeError("pressure run never spilled — pool sizing or "
                           "escalation is not exercising the tier")
    return {
        "pool_pages": pages_p, "working_set_pages": working,
        "spilled_pages": tr["kv_spill_pages"],
        "readahead_pages": tr["kv_readahead_pages"],
        "spill_ratio": tr["kv_spill_bytes"] / max(tr["kv_spill_raw_bytes"],
                                                  1),
        "steady_d2h_calls": d2h,
        "preemptions": eng.stats["preempted"],
        "deadline_preempted": eng.stats["deadline_preempted"],
        "pressure_preempted": eng.stats["pressure_preempted"],
        "completed": eng.stats["completed"],
        "requests": requests,
    }


def weight_stream_scenario(arch: str = "qwen3-1.7b", *, requests: int = 2,
                           prompt_len: int = 8, max_new: int = 12,
                           max_batch: int = 2, max_len: int = 32,
                           min_size: int = 1024) -> dict:
    """Packed-weight serving: the engine's weight store is APack planes
    (``weights="apack-int8"``) and every decode/prefill projection runs
    through the fused decompress-matmul.

    Weights are drawn heavy-tailed (sparse 16x outliers over a narrow
    normal bulk — the shape trained checkpoints actually have, and what
    sets the per-channel absmax), so the smoke measures a *realistic*
    APack weight ratio instead of the near-incompressible random-normal
    init.  The parity control is a dense engine serving the int8
    DEQUANTIZED weights — same quantization, different matmul path — so
    greedy token identity isolates the fused kernel against the dense
    einsum with the quantization-parity bound already applied.  The
    scenario raises on token divergence; the emitted row re-asserts it
    for the CI gate, alongside the measured per-step weight-read ratio
    and the fused path's steady-state zero-``device_get`` guard."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.core import quant
    from repro.models import model as M
    from repro.models import modules as mm
    from repro.serve import ServeEngine

    base = configs.get_smoke_config(arch)
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))

    # heavy-tailed re-draw: narrow normal bulk (sigma 0.02) plus sparse
    # 32x outliers (~1 per 16 rows of every output channel) — the
    # per-channel absmax is then set by an outlier, the bulk quantizes
    # to a few int8 codes, and APack's weight-mode table gets the
    # low-entropy histogram trained checkpoints exhibit.  Outliers are
    # dense enough that every quantization group still contains one
    # when a stacked tensor is later sliced and quantized per layer (a
    # group with no outlier would spread its bulk over the full int8
    # range and decompress to ~8 bits).
    rs = np.random.RandomState(7)

    def redraw(w):
        arr = np.asarray(jax.device_get(w))
        if arr.ndim < 2 or arr.dtype.kind != "f" or arr.size < min_size:
            return w
        vals = rs.normal(0.0, 0.015, arr.shape)
        flat = vals.reshape(-1, arr.shape[-1])
        # one outlier every 32 rows of each channel (random phase): any
        # contiguous per-layer slice of a stacked tensor is guaranteed
        # coverage, so every quantization group's absmax is outlier-set
        for c in range(flat.shape[1]):
            rows = rs.randint(0, 32) + 32 * np.arange(flat.shape[0] // 32)
            flat[rows, c] = rs.choice([-1.0, 1.0], rows.size) * 0.64
        return jnp.asarray(flat.reshape(arr.shape).astype(arr.dtype))

    params = jax.tree.map(redraw, params)

    # dense control: identical int8 quantization, dense einsum path —
    # built from the packed tree's site map so both engines quantize
    # exactly the same tensors
    packed_map, _ = M.pack_weights(cfg, params, min_size=min_size)

    def dequantized(pw, w):
        if not isinstance(pw, mm.PackedWeight):
            return w
        q, qp = quant.quantize_symmetric(jnp.asarray(w, jnp.float32),
                                         axis=-1)
        return (q.astype(jnp.float32) * qp.scale).astype(w.dtype)

    dense_q = jax.tree.map(
        dequantized, packed_map, params,
        is_leaf=lambda x: isinstance(x, mm.PackedWeight))

    kw = dict(requests=requests, prompt_len=prompt_len, max_new=max_new)
    eng_p = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                        kv_page_size=4, kv_calib_pages=2,
                        weights="apack-int8", weight_min_size=min_size)
    eng_d = ServeEngine(cfg, dense_q, max_batch=max_batch, max_len=max_len,
                        kv_page_size=4, kv_calib_pages=2)

    def tokens_of(eng, seed):
        from repro.serve import Request
        rng = np.random.default_rng(seed)
        reqs = [Request(rid=seed * 1000 + i,
                        prompt=rng.integers(0, cfg.vocab_size, prompt_len)
                        .astype(np.int32), max_new_tokens=max_new)
                for i in range(requests)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return [(r.prompt, np.asarray(r.tokens, np.int32)) for r in reqs]

    _serve_wave(eng_p, cfg, 0, **kw)            # warmup: jit compiles
    waves = [_serve_wave(eng_p, cfg, 1 + i, **kw) for i in range(REPEAT)]
    best = min(waves, key=lambda w: w["s_per_step"])
    # parity waves on fresh seeds, mirrored on the dense control.  Free-
    # running greedy decode compounds: one near-tie argmax flip (the two
    # paths order their f32 K-accumulation differently) rewrites every
    # later token of that request, so raw wave equality is too brittle
    # to gate on.  Instead the packed engine's output sequences are
    # re-scored TEACHER-FORCED under both weight stores with one full
    # forward each, and parity is the per-position argmax agreement —
    # flips cannot compound, and the measured max logit gap pins the
    # quantization-parity bound the fused kernel must hold.
    seqs = []
    for i in range(REPEAT):
        toks = tokens_of(eng_p, 100 + i)
        tokens_of(eng_d, 100 + i)      # same traffic through the control
        for prompt, gen in toks:
            seqs.append(np.concatenate([prompt, gen]))
    batch = {"tokens": jnp.asarray(np.stack(seqs), jnp.int32)}
    lp, _, _ = M.forward(cfg, eng_p.params, batch, remat=False)
    ld, _, _ = M.forward(cfg, eng_d.params, batch, remat=False)
    pred = slice(prompt_len - 1, -1)   # positions that predict new tokens
    ap = np.asarray(jnp.argmax(lp[:, pred], -1))
    ad = np.asarray(jnp.argmax(ld[:, pred], -1))
    token_identity = float((ap == ad).mean())
    logit_max_diff = float(jnp.max(jnp.abs(
        lp[:, pred].astype(jnp.float32) - ld[:, pred].astype(jnp.float32))))
    if token_identity < 0.98:
        raise RuntimeError(
            f"packed-weight argmax disagrees with the dense control on "
            f"{(1 - token_identity):.1%} of teacher-forced positions "
            f"(max logit diff {logit_max_diff:.4f})")
    ws = eng_p.weight_stats()
    return {
        "us_per_step": best["s_per_step"] * 1e6,
        "steps_per_s": 1.0 / best["s_per_step"],
        "weight_ratio": ws["weight_ratio"],
        "native_ratio": ws["native_ratio"],
        "packed_tensors": ws["packed_tensors"],
        "compressed_read_bytes_per_step":
            ws["compressed_read_bytes_per_step"],
        "dense_read_bytes_per_step": ws["dense_read_bytes_per_step"],
        "token_identity": token_identity,
        "logit_max_diff": logit_max_diff,
        "steady_d2h_calls": min(w["steady_d2h_calls"] for w in waves),
    }


def emit_weight_stream(emit, d: dict) -> None:
    emit("decode/weight_stream/ratio", 0.0,
         f"per-step weight-read bytes, packed vs int8 dense "
         f"({d['compressed_read_bytes_per_step']} / "
         f"{d['dense_read_bytes_per_step']} B; "
         f"x{d['native_ratio']:.3f} vs native dtype, "
         f"{d['packed_tensors']} tensors)",
         value=float(d["weight_ratio"]))
    emit("decode/weight_stream/steps_per_s", d["us_per_step"],
         f"decode steps/s serving from APack-packed weights "
         f"(steps_per_s={d['steps_per_s']:.2f})",
         value=float(d["steps_per_s"]))
    emit("decode/weight_stream/token_identity", 0.0,
         f"teacher-forced argmax agreement vs the dequantized-dense "
         f"control (max logit diff {d['logit_max_diff']:.4f}; the "
         f"scenario raises below 0.98)",
         value=float(d["token_identity"]))
    emit("decode/weight_stream/steady_d2h_calls", 0.0,
         "min per-step device_get calls with packed weights (0 = the "
         "fused loop stayed device-resident)",
         value=float(d["steady_d2h_calls"]))


def serving_scenario(arch: str = "qwen3-1.7b", *, requests: int = 12,
                     max_new: int = 8, max_batch: int = 3,
                     max_len: int = 48, load: float = 2.0) -> dict:
    """Open-loop serving workload: Poisson arrivals (seeded exponential
    inter-arrival gaps, scaled so the offered load is ``load`` of one
    engine's measured decode throughput) over a varied prompt-length mix
    including one long prompt, served by the synchronous engine and the
    async event-loop engine on the *same wall-clock arrival schedule*.
    The default load oversubscribes the engine (queueing regime): that is
    where the overlap pays — an idle engine admits like sync and only
    adds its one-step pipeline latency.

    Reports per-engine end-to-end latency p50/p99 and tokens/s, checks
    greedy tokens bit-identical between the two engines, and keeps the
    fused path's steady-state zero-``device_get`` guard on the async
    run — the overlap must hide host work, not move it back onto the
    device boundary."""
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serve import Request, ServeEngine

    base = configs.get_smoke_config(arch)
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    lens = [6, 12, 9, 24, 7, 16, 10, 5]
    prompts = [rng.integers(0, cfg.vocab_size, lens[i % len(lens)])
               .astype(np.int32) for i in range(requests)]

    def build(scheduler):
        return ServeEngine(cfg, params, max_batch=max_batch,
                           max_len=max_len, kv_page_size=4,
                           kv_calib_pages=2, scheduler=scheduler)

    def warmup(eng):
        # two passes: the first eats every jit compile (prefill buckets,
        # decode); the second, compile-free, measures the honest service
        # rate — deriving arrival gaps from a compile-inflated step time
        # would spread the schedule out and quietly underload the wave
        t_step = 0.0
        for pass_base in (10_000, 20_000):
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=pass_base + i, prompt=p,
                                   max_new_tokens=max_new))
            steps0 = eng.stats["steps"]
            t0 = time.perf_counter()
            eng.run_until_drained(max_steps=4000)
            t_step = ((time.perf_counter() - t0)
                      / max(eng.stats["steps"] - steps0, 1))
        eng._lat_wait.clear()
        eng._lat_e2e.clear()
        return t_step

    def wave(eng, arrivals, rid_base):
        reqs = [Request(rid=rid_base + i, prompt=p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        eng._lat_wait.clear()
        eng._lat_e2e.clear()
        steps0, gen0 = eng.stats["steps"], eng.stats["generated"]
        per_step_d2h = []
        nxt = 0
        t0 = time.perf_counter()
        for _ in range(5000):
            now = time.perf_counter() - t0
            while nxt < len(reqs) and arrivals[nxt] <= now:
                eng.submit(reqs[nxt])
                nxt += 1
            before = eng.kv.transfers["d2h_calls"]
            n = eng.step()
            if n:
                per_step_d2h.append(eng.kv.transfers["d2h_calls"]
                                    - before)
            if n == 0 and not eng.queue and not eng._pump:
                if nxt >= len(reqs):
                    break
                # idle until the next arrival (open-loop workload)
                time.sleep(max(arrivals[nxt]
                               - (time.perf_counter() - t0), 0.0))
        else:
            raise RuntimeError("serving wave failed to drain")
        wall = time.perf_counter() - t0
        assert all(r.done and not r.error for r in reqs)
        lat = eng.latency_stats()
        return {"tokens": [r.tokens for r in reqs],
                "tokens_per_s": (eng.stats["generated"] - gen0) / wall,
                "steps_per_s": (eng.stats["steps"] - steps0) / wall,
                "e2e_p50_ms": lat["e2e_p50"] * 1e3,
                "e2e_p99_ms": lat["e2e_p99"] * 1e3,
                "queue_wait_p99_ms": lat["queue_wait_p99"] * 1e3,
                "steady_d2h_calls": (min(per_step_d2h)
                                     if per_step_d2h else 0)}

    engines = {s: build(s) for s in ("sync", "async")}
    t_step = warmup(engines["sync"])
    warmup(engines["async"])
    # offered load: ~`load` requests' worth of decode work per unit of
    # measured engine capacity (the same absolute schedule drives both
    # engines — identical offered traffic)
    mean_gap = t_step * max_new / (load * max_batch)
    gaps = rng.exponential(mean_gap, requests)
    arrivals = np.cumsum(gaps)
    # 5 *interleaved* sync/async wave pairs on the same schedule, then
    # the median of the per-pair ratios: interleaving makes machine
    # drift hit both engines alike, pairing cancels it out of the
    # ratio, and the median shrugs off a throttle spike landing on one
    # wave.  (A min-per-engine statistic is wrong here: it compares
    # sync's luckiest wave against async's, which on a noisy host is a
    # coin flip.)  Greedy tokens are asserted identical across every
    # wave of both engines.
    waves: dict = {"sync": [], "async": []}
    for w in range(5):
        for scheduler in ("sync", "async"):
            r = wave(engines[scheduler], arrivals, rid_base=(w + 1) * 1000)
            if waves[scheduler] and r["tokens"] != waves[scheduler][0]["tokens"]:
                raise RuntimeError("greedy tokens diverged across waves")
            waves[scheduler].append(r)
    if waves["sync"][0]["tokens"] != waves["async"][0]["tokens"]:
        # the event loop must reschedule work, never change it
        raise RuntimeError("greedy tokens diverged between sync and "
                           "async engines")

    med = lambda xs: float(np.median(xs))
    out = {}
    for scheduler, eng in engines.items():
        rs = waves[scheduler]
        out[scheduler] = {
            k: med([r[k] for r in rs])
            for k in ("tokens_per_s", "steps_per_s", "e2e_p50_ms",
                      "e2e_p99_ms", "queue_wait_p99_ms")}
        out[scheduler]["steady_d2h_calls"] = min(
            r["steady_d2h_calls"] for r in rs)
        out[scheduler]["prefill_chunks"] = eng.stats["prefill_chunks"]
        out[scheduler]["staged_readahead"] = eng.stats["staged_readahead"]
    out["paired"] = {
        "e2e_p99_ratio": med(
            [a["e2e_p99_ms"] / s["e2e_p99_ms"]
             for a, s in zip(waves["async"], waves["sync"])]),
        "queue_wait_p99_ratio": med(
            [a["queue_wait_p99_ms"] / max(s["queue_wait_p99_ms"], 1e-9)
             for a, s in zip(waves["async"], waves["sync"])]),
        "tokens_per_s_ratio": med(
            [a["tokens_per_s"] / s["tokens_per_s"]
             for a, s in zip(waves["async"], waves["sync"])]),
    }
    return out


def emit_serving(emit, d: dict) -> None:
    for mode in ("sync", "async"):
        r = d[mode]
        emit(f"decode/serving_tokens_per_s/{mode}", 0.0,
             f"Poisson-arrival open-loop throughput, median of 5 waves "
             f"(steps/s={r['steps_per_s']:.2f})",
             value=float(r["tokens_per_s"]))
        emit(f"decode/serving_e2e_p99_ms/{mode}", 0.0,
             f"end-to-end latency p99, median of 5 waves "
             f"(p50={r['e2e_p50_ms']:.1f}ms, "
             f"queue-wait p99={r['queue_wait_p99_ms']:.1f}ms)",
             value=float(r["e2e_p99_ms"]))
    emit("decode/serving_steady_d2h_calls/async", 0.0,
         "min per-step device_get calls, async engine (0 = overlap keeps "
         "host work off the device boundary)",
         value=float(d["async"]["steady_d2h_calls"]))
    p = d["paired"]
    emit("decode/serving_paired_queue_wait_ratio", 0.0,
         "async/sync queue-wait p99, median over interleaved wave pairs "
         "— the scheduling tail the event loop controls directly "
         "(continuous admission + chunked prefill vs step-boundary FIFO)",
         value=float(p["queue_wait_p99_ratio"]))
    emit("decode/serving_paired_p99_ratio", 0.0,
         "async/sync e2e p99, median over interleaved wave pairs (on a "
         "serial CPU host the overlap cannot run concurrently, so this "
         "carries scheduling wins + host noise; accelerator hosts see "
         "the full overlap win)",
         value=float(p["e2e_p99_ratio"]))
    emit("decode/serving_async_speedup", 0.0,
         f"async/sync tokens-per-s, median over interleaved wave pairs; "
         f"{d['async']['prefill_chunks']} prefill chunks pumped "
         "(tokens bit-identical)",
         value=float(p["tokens_per_s_ratio"]))


SHARDED_REQ, SHARDED_NEW, SHARDED_PLEN = 8, 16, 9


def sharded_run(n_data: int, n_model: int) -> dict:
    """The body of ``sharded_scenario``: single-device and batch controls,
    then the ``n_data`` x ``n_model`` mesh engine, over the devices this
    process holds."""
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serve import ServeEngine, Request

    ndev = n_data * n_model
    base = configs.get_smoke_config("qwen3-1.7b")
    cfg = dataclasses.replace(base, kv_cache_dtype="apack-int8")
    params = M.init_params(base, jax.random.PRNGKey(0))

    def build(mb, mesh=None):
        return ServeEngine(cfg, params, max_batch=mb, max_len=48,
                           kv_page_size=16, kv_calib_pages=2, mesh=mesh)

    def wave(eng, n_req, seed):
        rng = np.random.default_rng(seed)
        reqs = [Request(rid=seed * 1000 + i,
                        prompt=rng.integers(0, cfg.vocab_size, SHARDED_PLEN)
                        .astype(np.int32), max_new_tokens=SHARDED_NEW)
                for i in range(n_req)]
        for r in reqs:
            eng.submit(r)
        eng.step()                    # admission/prefill, untimed
        steps0 = eng.stats["steps"]
        d2h = []
        t0 = time.perf_counter()
        for _ in range(500):
            before = eng.kv.transfers["d2h_calls"]
            n = eng.step()
            if n == 0 and not eng.queue:
                break
            d2h.append(eng.kv.transfers["d2h_calls"] - before)
        else:
            raise RuntimeError("engine failed to drain within 500 steps")
        wall = time.perf_counter() - t0
        assert all(r.done and r.error is None for r in reqs)
        steps = max(eng.stats["steps"] - steps0, 1)
        return {"tokens": [list(r.tokens) for r in reqs],
                "tok_per_s": n_req * SHARDED_NEW / wall,
                "s_per_step": wall / steps,
                "steady_d2h": min(d2h) if d2h else 0}

    def serve(eng, n_req):
        wave(eng, n_req, 0)           # warmup eats every jit compile
        return [wave(eng, n_req, 1 + i) for i in range(REPEAT)]

    one = serve(build(1), 1)
    ctrl = serve(build(SHARDED_REQ), SHARDED_REQ)
    mesh = jax.make_mesh((n_data, n_model), ("data", "model"),
                         devices=jax.devices()[:ndev])
    eng_s = build(SHARDED_REQ, mesh)
    # wrap the combined sharded step to time its device portion: the host
    # platform executes the per-shard programs back-to-back, so jit/ndev
    # is the per-shard critical path a real mesh runs concurrently.  Seal
    # work (note_appended: HOT->COLD requantize, APack encode, fused plane
    # scatter) is per-PAGE host work — pages are owned by shards, so on a
    # real multi-host mesh each host seals only its own shards' pages and
    # this bucket divides by ndev too; only engine bookkeeping
    # (retire/admit, step meta, token pull) stays serialized
    jit_acc = {"s": 0.0, "n": 0}
    seal_acc = {"s": 0.0}
    orig = eng_s._step_mesh

    def timed_step(*a):
        t0 = time.perf_counter()
        out = orig(*a)
        jax.block_until_ready(out[0])
        jit_acc["s"] += time.perf_counter() - t0
        jit_acc["n"] += 1
        return out
    eng_s._step_mesh = timed_step
    orig_note = eng_s.kv.note_appended

    def timed_note(*a, **k):
        t0 = time.perf_counter()
        r = orig_note(*a, **k)
        seal_acc["s"] += time.perf_counter() - t0
        return r
    eng_s.kv.note_appended = timed_note
    wave(eng_s, SHARDED_REQ, 0)       # warmup eats every jit compile
    jit_acc["s"], jit_acc["n"] = 0.0, 0   # count compile-free steps only
    seal_acc["s"] = 0.0
    sh = [wave(eng_s, SHARDED_REQ, 1 + i) for i in range(REPEAT)]

    identical = all(w_s["tokens"] == w_c["tokens"]
                    for w_s, w_c in zip(sh, ctrl))
    t1 = min(w["s_per_step"] for w in one)
    tc = min(w["s_per_step"] for w in ctrl)
    ts = min(w["s_per_step"] for w in sh)
    ts_jit = jit_acc["s"] / max(jit_acc["n"], 1)
    ts_seal = seal_acc["s"] / max(jit_acc["n"], 1)
    serial = max(ts - ts_jit - ts_seal, 0.0)
    parallel_step = ts_jit / ndev + ts_seal / ndev + serial
    return {
        "mesh": f"{n_data}x{n_model}",
        "tok_per_s_single": max(w["tok_per_s"] for w in one),
        "tok_per_s_sharded": max(w["tok_per_s"] for w in sh),
        "s_per_step_single": t1, "s_per_step_batch": tc,
        "s_per_step_sharded": ts, "s_per_step_jit": ts_jit,
        "s_per_step_seal": ts_seal, "s_per_step_serial": serial,
        "scaling_serialized_x": SHARDED_REQ * t1 / ts,
        "scaling_x": SHARDED_REQ * t1 / parallel_step,
        "step_overhead_x": ts / tc,
        "token_identity": bool(identical),
        "steady_d2h_calls": max(w["steady_d2h"] for w in sh)}


def sharded_scenario(devices: int | None = None,
                     mesh_shape: tuple[int, int] = (8, 1)) -> dict:
    """Mesh-sharded serving scaling row (DESIGN.md §11): a
    ``mesh_shape`` = (data, model) engine — default 8x1, pure data
    parallel; the ``--mesh`` CLI flag selects e.g. 4x2 for kv-head
    tensor parallelism — vs the single-device engine.

    On an accelerator ``sharded_run`` runs in this process, over the
    devices it already holds (a child could not reach a chip this
    process holds), and fails when there are too few.  On the CPU it
    rehearses on a forced multi-device host platform, in a subprocess so
    the XLA device-count flag never leaks into this process (whose smoke
    rows must see 1 device); the child writes its result to a file.

    Reports aggregate tokens/s, greedy-token bit-identity against the
    single-device control serving the same waves, the per-shard
    steady-state zero-``device_get`` guard, and two scaling figures:
    ``scaling_serialized_x`` is the raw wall-clock aggregate over the
    single-request single-device rate (the host platform executes the 8
    per-shard programs back-to-back on one core, so this is floored near
    1x regardless of how well the sharding partitions); ``scaling_x``
    normalizes the two per-shard buckets to the critical path — device
    time (jit/n_devices) and per-page seal host work (seal/n_devices:
    pages are shard-owned, so on a real multi-host mesh each host
    requantizes/encodes/pushes only its own shards' pages) — while
    engine bookkeeping (retire/admit, step meta, token pull) stays
    fully serialized.  Every quantity is measured, none simulated."""
    import json
    import os
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    import jax

    n_data, n_model = mesh_shape
    devices = devices or n_data * n_model
    if jax.default_backend() != "cpu":
        held = len(jax.devices())
        if held < n_data * n_model:
            raise RuntimeError(
                f"sharded scenario needs {n_data * n_model} devices for "
                f"its {n_data}x{n_model} mesh; this process holds {held}")
        return sharded_run(n_data, n_model)
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = f"{root}{os.pathsep}{root / 'src'}"
    with tempfile.TemporaryDirectory() as tmp:
        result = Path(tmp) / "sharded.json"
        code = ("import json, sys\n"
                "from benchmarks.bench_decode import sharded_run\n"
                f"d = sharded_run({n_data}, {n_model})\n"
                "open(sys.argv[1], 'w').write(json.dumps(d))\n")
        out = subprocess.run([sys.executable, "-c", code, str(result)],
                             env=env, capture_output=True, text=True,
                             timeout=1800)
        if out.returncode != 0:
            raise RuntimeError(f"sharded scenario subprocess failed:\n"
                               f"{out.stdout}\n{out.stderr}")
        return json.loads(result.read_text())


def emit_sharded(emit, d: dict) -> None:
    mesh = d.get("mesh", "8x1")
    emit("decode/sharded_tokens_per_s", 0.0,
         f"{mesh} mesh aggregate, 8 requests (serialized host platform; "
         f"step {d['s_per_step_sharded']*1e3:.1f}ms = jit "
         f"{d['s_per_step_jit']*1e3:.1f} + per-page seal "
         f"{d['s_per_step_seal']*1e3:.1f} + serial "
         f"{d['s_per_step_serial']*1e3:.1f})",
         value=float(d["tok_per_s_sharded"]))
    emit("decode/sharded_scaling_x", 0.0,
         f"aggregate tokens/s on the {mesh} mesh vs single-device "
         f"single-request engine; device time and per-page seal work "
         f"(partitions with page ownership across hosts) normalize to "
         f"the per-shard critical path, engine bookkeeping stays "
         f"serialized (raw fully-serialized ratio "
         f"{d['scaling_serialized_x']:.2f}x)",
         value=float(d["scaling_x"]))
    emit("decode/sharded_step_overhead_x", 0.0,
         "sharded step time over the single-device step on the same "
         "8-request batch — the partitioning overhead the mesh pays "
         "even before shards parallelize",
         value=float(d["step_overhead_x"]))
    emit("decode/sharded_token_identity", 0.0,
         "greedy tokens bit-identical to the single-device engine "
         "across every timed wave",
         value=float(d["token_identity"]))
    emit("decode/sharded_steady_d2h_calls", 0.0,
         "max per-step device_get calls across sharded waves (0 = the "
         "combined decode+append step stays device-resident per shard)",
         value=float(d["steady_d2h_calls"]))


def emit_pressure(emit, d: dict) -> None:
    emit("decode/pressure_completed", 0.0,
         f"requests completed with pool at "
         f"{d['pool_pages']}/{d['working_set_pages']} working-set pages "
         f"(tokens bit-identical to uncontended control)",
         value=float(d["completed"] == d["requests"]))
    emit("decode/pressure_spill_ratio", 0.0,
         f"spilled bytes / dense working-set bytes over "
         f"{d['spilled_pages']} spilled pages "
         f"({d['readahead_pages']} restored by readahead)",
         value=float(d["spill_ratio"]))
    emit("decode/pressure_spilled_pages", 0.0,
         f"{d['preemptions']} preemptions "
         f"({d['deadline_preempted']} deadline, "
         f"{d['pressure_preempted']} admission-pressure)",
         value=float(d["spilled_pages"]))
    emit("decode/pressure_steady_d2h_calls", 0.0,
         "min per-step device_get calls under pressure (0 = readahead "
         "stays off the step critical path)",
         value=float(d["steady_d2h_calls"]))


def emit_drift(emit, d: dict) -> None:
    emit("decode/drift_kv_ratio/pre_refresh", 0.0,
         f"phase-A window ratio, refresh engine "
         f"(frozen control: {d['frozen_pre_ratio']:.4f})",
         value=d["pre_refresh_ratio"])
    emit("decode/drift_kv_ratio/post_refresh", 0.0,
         f"phase-B window ratio after {d['refreshes']} refreshes / "
         f"{d['pages_repacked']} re-packed pages (gen {d['generation']})",
         value=d["post_refresh_ratio"])
    emit("decode/drift_kv_ratio/frozen_control", 0.0,
         "phase-B window ratio with tables frozen at first calibration",
         value=d["frozen_post_ratio"])
    emit("decode/drift_repack_bytes_per_step", 0.0,
         "re-pack read+write overhead amortized over decode steps",
         value=float(d["repack_bytes_per_step"]))
    emit("decode/drift_steady_d2h_calls", 0.0,
         "min per-step device_get calls with refresh active (0 = "
         "device-resident loop survives refresh)",
         value=float(d["steady_d2h_calls"]))


def main(emit) -> None:
    rows = {}
    for fused in (False, True):
        r = decode_throughput(fused=fused)
        rows[r["mode"]] = r
        emit(f"decode/steps_per_s/{r['mode']}", r["us_per_step"],
             f"steps_per_s={r['steps_per_s']:.2f} "
             f"kv_ratio={r['kv_ratio']:.3f}",
             value=r["steps_per_s"])
        emit(f"decode/transfer_bytes_per_step/{r['mode']}", 0.0,
             "host<->device bytes per decode step (KV path)",
             value=float(r["bytes_per_step"]))
        emit(f"decode/steady_state_d2h_calls/{r['mode']}", 0.0,
             "min per-step device_get calls (0 = device-resident loop)",
             value=float(r["steady_d2h_calls"]))
    speedup = rows["materialize"]["us_per_step"] / rows["fused"]["us_per_step"]
    shrink = (rows["materialize"]["bytes_per_step"]
              / max(rows["fused"]["bytes_per_step"], 1.0))
    emit("decode/fused_speedup", 0.0,
         f"materialize/fused step-time ratio; transfer shrink "
         f"{shrink:.1f}x", value=speedup)
    emit_weight_stream(emit, weight_stream_scenario())
    emit_drift(emit, drift_scenario())
    emit_pressure(emit, pressure_scenario())
    emit_serving(emit, serving_scenario())
    emit_sharded(emit, sharded_scenario())


if __name__ == "__main__":
    # standalone entry: `python -m benchmarks.bench_decode --drift` /
    # `--pressure` run just that scenario (the full module runs via
    # benchmarks.run)
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--drift", action="store_true",
                    help="run only the two-phase drift workload")
    ap.add_argument("--pressure", action="store_true",
                    help="run only the memory-pressure spill workload "
                         "(pool at 60% of the working set)")
    ap.add_argument("--serving", action="store_true",
                    help="run only the Poisson-arrival serving workload "
                         "(sync vs async event-loop engine)")
    ap.add_argument("--weights", action="store_true",
                    help="run only the packed-weight serving workload "
                         "(APack weight store vs dequantized dense)")
    ap.add_argument("--sharded", action="store_true",
                    help="run only the mesh-sharded scaling workload "
                         "(data-parallel vs single-device, forced "
                         "multi-device host platform in a subprocess)")
    ap.add_argument("--mesh", default="8x1", metavar="DATAxMODEL",
                    help="mesh shape for --sharded as DATAxMODEL, e.g. "
                         "8x1 (pure data parallel) or 4x2 (kv-heads "
                         "tensor-parallel over the model axis); data "
                         "must divide max_batch=8 and model must divide "
                         "the smoke config's 2 kv heads (default: 8x1, "
                         "the CI-gated row)")
    args = ap.parse_args()

    def _emit(name, us, derived, value=None):
        print(f"{name},{us:.1f},{derived}"
              + (f",value={value}" if value is not None else ""), flush=True)

    if args.drift:
        emit_drift(_emit, drift_scenario())
    elif args.pressure:
        emit_pressure(_emit, pressure_scenario())
    elif args.serving:
        emit_serving(_emit, serving_scenario())
    elif args.weights:
        emit_weight_stream(_emit, weight_stream_scenario())
    elif args.sharded:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        emit_sharded(_emit, sharded_scenario(mesh_shape=(d, m)))
    else:
        main(_emit)
