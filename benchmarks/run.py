"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--json PATH`` additionally
dumps the rows as machine-readable JSON (the perf trajectory across PRs is
tracked by committing ``BENCH_codec.json`` from ``--only codec --json
BENCH_codec.json``).  ``--only SUBSTR`` restricts to matching sections.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as JSON to PATH")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only sections whose label contains SUBSTR")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_distributions, bench_tablegen, bench_traffic,
                   bench_energy, bench_speedup, bench_codec, bench_decode,
                   bench_roofline, bench_trained, bench_analysis)
    mods = [
        ("distributions(Fig2)", bench_distributions),
        ("tablegen(TableI)", bench_tablegen),
        ("traffic(Fig5)", bench_traffic),
        ("energy(Fig6)", bench_energy),
        ("speedup(Fig7/8)", bench_speedup),
        ("codec(§VII-B)", bench_codec),
        ("decode(§Serving)", bench_decode),
        ("trained(§VII-A)", bench_trained),
        ("roofline(§Roofline)", bench_roofline),
        ("analysis(§Invariants)", bench_analysis),
    ]
    if args.only:
        mods = [(label, mod) for label, mod in mods if args.only in label]
        if not mods:
            ap.error(f"--only {args.only!r} matches no benchmark section")
    print("name,us_per_call,derived")

    rows: list[dict] = []

    def emit(name: str, us: float, derived: str, value: float | None = None) -> None:
        print(f"{name},{us:.1f},{derived}", flush=True)
        row = {"name": name, "us_per_call": round(us, 1), "derived": derived}
        if value is not None:
            # machine-readable scalar (e.g. the measured KV compression
            # ratio) so trajectory tooling doesn't parse `derived` strings
            row["value"] = value
        rows.append(row)

    failed = 0
    for label, mod in mods:
        t0 = time.time()
        try:
            mod.main(emit)
            emit(f"_section/{label}", (time.time() - t0) * 1e6, "ok")
        except Exception as e:
            failed += 1
            traceback.print_exc(file=sys.stderr)
            emit(f"_section/{label}", (time.time() - t0) * 1e6, f"FAILED: {e}")

    if args.json:
        doc = {
            "schema": "apack-bench-v1",
            "platform": platform.platform(),
            "device": {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind,
                       "count": len(jax.devices())},
            "python": platform.python_version(),
            "results": rows,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
