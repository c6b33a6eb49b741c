"""Serving driver: batched requests against APack-compressed weights and
(optionally) a paged APack-compressed KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 16 --prompt-len 32 --max-new 16 --kv apack-int8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.runtime import spans
from repro.serve import (DEFAULT_WEIGHT_MIN_SIZE, Request, ServeEngine,
                         compress_params, decompress_params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--weights", default=None, choices=["apack-int8"],
                    help="serve directly from APack-packed weights: large "
                         "projection/FFN matrices live in HBM as compressed "
                         "planes and decode/prefill matmuls run through the "
                         "fused decompress kernel (supersedes the "
                         "checkpoint-style compress/decompress round-trip)")
    ap.add_argument("--weight-min-size", type=int,
                    default=DEFAULT_WEIGHT_MIN_SIZE,
                    help="smallest element count compressed by either "
                         "weight path (--weights and the checkpoint "
                         "round-trip share this one default)")
    ap.add_argument("--kv", default=None,
                    choices=["bfloat16", "int8", "apack-int8"],
                    help="KV-cache mode (apack-int8 = paged + compressed)")
    ap.add_argument("--kv-page-size", type=int, default=16)
    ap.add_argument("--window-size", type=int, default=None,
                    help="override the rolling-attention window (small "
                         "values demo page eviction on hybrid archs)")
    ap.add_argument("--kv-materialize", action="store_true",
                    help="use the legacy materialize decode path (dense "
                         "cache rebuilt from the pool every step) instead "
                         "of the default device-resident fused path")
    ap.add_argument("--kv-refresh", action="store_true",
                    help="adaptive table refresh: re-calibrate activation "
                         "tables from drift sketches and re-pack pages "
                         "when serving traffic drifts")
    ap.add_argument("--kv-refresh-every", type=int, default=None,
                    metavar="PAGES",
                    help="also refresh unconditionally every PAGES sealed "
                         "pages per layer (default: regression trigger "
                         "only)")
    ap.add_argument("--kv-refresh-threshold", type=float, default=0.15,
                    help="refresh when the drift sketch's expected coded "
                         "size regresses this fraction past the "
                         "calibration-time expectation")
    ap.add_argument("--kv-repack-budget", type=int, default=4,
                    help="max pages re-packed per decode step (amortizes "
                         "a refresh over the serve instead of stalling)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page-pool size (default: worst-case for "
                         "max_batch × max_len; smaller values exercise the "
                         "pressure/spill path)")
    ap.add_argument("--kv-pressure", action="store_true",
                    help="enable pressure escalation: blocked admission "
                         "may preempt-with-spill active slots (compressed "
                         "host spill tier, exponential backoff)")
    ap.add_argument("--slot-deadline", type=int, default=None,
                    metavar="STEPS",
                    help="preempt-with-spill any slot that decodes this "
                         "many steps while other requests queue")
    ap.add_argument("--scheduler", default="sync",
                    choices=["sync", "async"],
                    help="engine core: 'async' runs the event-loop "
                         "scheduler (host work overlaps the in-flight "
                         "device step, chunked prefill, continuous "
                         "admission); requires the fused apack-int8 KV")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="TOKENS",
                    help="async scheduler: prompt tokens ingested per "
                         "overlapped step (default: 4 pages' worth)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request end-to-end latency SLO; admission "
                         "orders by earliest deadline instead of FIFO")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="mesh-sharded serving, e.g. '8x1' (decode jobs "
                         "data-parallel, kv-heads tensor-parallel): shards "
                         "the page pool, free lists and fused gather-decode "
                         "across devices; requires the fused apack-int8 KV "
                         "and DATA*MODEL visible devices (debug: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.kv:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=args.kv)
    if args.window_size is not None:
        cfg = dataclasses.replace(cfg, window_size=args.window_size)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if not args.no_compress and not args.weights:
        # checkpoint-style round-trip (legacy): compress, report, decompress
        # back to dense.  --weights apack-int8 supersedes it — the packed
        # planes ARE the weight store, no decompressed copy exists.
        t0 = time.time()
        cp = compress_params(params, min_size=args.weight_min_size)
        print(f"APack weight compression: {cp.original_bytes/1e6:.1f} MB -> "
              f"{cp.compressed_bytes/1e6:.1f} MB "
              f"({cp.ratio:.2f}x, {time.time()-t0:.1f}s)")
        params = decompress_params(cp)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_debug_mesh
        n_data, _, n_model = args.mesh.partition("x")
        mesh = make_debug_mesh(int(n_data), int(n_model or 1))
        print(f"serving mesh: {dict(mesh.shape)} over "
              f"{len(mesh.devices.flat)} devices")

    engine = ServeEngine(cfg, params, max_batch=args.max_batch,
                         max_len=args.prompt_len + args.max_new + 8,
                         mesh=mesh,
                         weights=args.weights,
                         weight_min_size=args.weight_min_size,
                         kv_page_size=args.kv_page_size,
                         kv_fused=not args.kv_materialize,
                         kv_refresh=args.kv_refresh,
                         kv_refresh_every_pages=args.kv_refresh_every,
                         kv_refresh_threshold=args.kv_refresh_threshold,
                         kv_repack_budget=args.kv_repack_budget,
                         kv_pages=args.kv_pages,
                         kv_pressure=args.kv_pressure,
                         slot_deadline_steps=args.slot_deadline,
                         scheduler=args.scheduler,
                         prefill_chunk_tokens=args.prefill_chunk)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new,
                    slo_ms=args.slo_ms)
            for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.time()
    engine.run_until_drained()
    dt = time.time() - t0
    failed = [(r.rid, r.error) for r in reqs
              if not r.done or r.error is not None]
    if failed:
        raise SystemExit(f"requests failed: {failed}")
    print(f"{engine.stats} in {dt:.1f}s "
          f"({engine.stats['generated']/max(dt,1e-9):.1f} tok/s)")
    if args.weights:
        ws = engine.weight_stats()
        print(f"packed weight store: {ws['packed_tensors']} tensors, "
              f"{ws['native_bytes']/1e6:.1f} MB native -> "
              f"{(ws['payload_bytes'] + ws['scale_bytes'])/1e6:.1f} MB "
              f"compressed (payload {ws['payload_bytes']/1e6:.1f} MB + "
              f"scale {ws['scale_bytes']/1e6:.2f} MB); "
              f"per-step weight reads x{ws['weight_ratio']:.3f} vs int8 "
              f"dense, x{ws['native_ratio']:.3f} vs native")
    lat = engine.latency_stats()
    if lat["n"]:
        print(f"latency ({args.scheduler} scheduler, n={lat['n']}): "
              f"queue-wait p50={lat['queue_wait_p50']*1e3:.1f}ms "
              f"p99={lat['queue_wait_p99']*1e3:.1f}ms; "
              f"ttft p50={lat['ttft_p50']*1e3:.1f}ms "
              f"p99={lat['ttft_p99']*1e3:.1f}ms; "
              f"e2e p50={lat['e2e_p50']*1e3:.1f}ms "
              f"p99={lat['e2e_p99']*1e3:.1f}ms")
    if engine.paged:
        ks = engine.kv_stats()
        ratio = ("n/a (no KV reads)" if ks["kv_ratio"] is None
                 else f"{ks['kv_ratio']:.3f}")
        print(f"paged KV traffic: raw={ks['kv_raw_bytes']/1e3:.1f} kB -> "
              f"read={ks['kv_read_bytes']/1e3:.1f} kB "
              f"(+{ks['kv_table_bytes']} B tables) "
              f"ratio={ratio} "
              f"packed_pages={ks['kv_pages_packed']} "
              f"evicted_pages={ks['kv_pages_evicted']} "
              f"pool={ks['kv_pages_high_water']}/{ks['kv_pool_pages']} pages")
        for kind, st in ks["kv_streams"].items():
            if kind in ("repack", "spill"):  # dedicated lines below
                continue
            r = st.get("ratio")
            print(f"  stream {kind:7s}: "
                  + " ".join(f"{k}={v}" for k, v in st.items()
                             if k != "ratio")
                  + (f" ratio={r:.3f}" if r is not None else " ratio=n/a"))
        rp = ks["kv_repack"]
        print(f"table refresh: {'on' if args.kv_refresh else 'off'}; "
              f"generation={rp['generation']} "
              f"refreshes={rp['refreshes']} "
              f"repacked={rp['pages']} pages "
              f"({rp['read_bytes']/1e3:.1f} kB read + "
              f"{rp['write_bytes']/1e3:.1f} kB written, "
              f"{rp['pending']} pending)")
        sp = ks["kv_spill"]
        spr = sp.get("ratio")
        print(f"spill tier: {sp['pages']} pages spilled "
              f"({sp['spill_bytes']/1e3:.1f} kB compressed vs "
              f"{sp['raw_bytes']/1e3:.1f} kB dense, "
              + (f"ratio={spr:.3f}" if spr is not None else "ratio=n/a")
              + f"); readahead {sp['readahead_pages']} pages "
              f"{sp['readahead_bytes']/1e3:.1f} kB; "
              f"parked={sp['live_records']} "
              f"quarantined={sp['quarantined']}; "
              f"spill_preempt={engine.stats['pressure_preempted']}"
              f"+{engine.stats['deadline_preempted']}ddl "
              f"failed={engine.stats['failed']}")
        tr = ks["transfers"]
        mode = "fused (device-resident)" if ks["kv_fused"] else "materialize"
        print(f"decode path: {mode}; host<->device "
              f"h2d={tr['h2d_bytes']/1e3:.1f} kB "
              f"d2h={tr['d2h_bytes']/1e3:.1f} kB "
              f"({tr['h2d_calls']}/{tr['d2h_calls']} calls)")
    print("program spans (whole process):")
    print(f"  {'span':26s} {'count':>8s} {'total s':>10s} {'mean ms':>10s}")
    for name, (n, secs) in sorted(spans.totals().items()):
        print(f"  {name:26s} {n:8d} {secs:10.3f} {secs / n * 1e3:10.3f}")
    print("sample output:", reqs[0].tokens[:16])


if __name__ == "__main__":
    main()
