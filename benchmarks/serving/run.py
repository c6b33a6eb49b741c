"""Serving benchmark: run one cell of ``BENCHMARK.json`` once on the chip.

    python3 benchmarks/serving/run.py --workload qwen3-1.7b-packed.decode \
        --seed 7 --seconds 20 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
Progress goes to stderr, ending with the compared numbers.  Without a TPU
whose ``device_kind`` is in ``peaks.json``, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def chip(cell):
    """(device, peaks) of the TPU the cell runs on; None, after saying why,
    without a TPU whose kind ``peaks.json`` knows or with too few chips."""
    import jax
    import spec
    devices = jax.devices()
    device = devices[0]
    peaks = spec.load_peaks(device.device_kind)
    if device.platform != "tpu" or peaks is None:
        print(f"no TPU with known peaks: JAX sees {device.platform} "
              f"{device.device_kind!r}", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"{len(devices)} chips, the cell asks for {cell.chips}",
              file=sys.stderr)
        return None
    return device, peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    import spec
    cell = spec.load_cell(args.workload)
    found = chip(cell)
    if found is None:
        return 2
    device, peaks = found
    import harness
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, peaks, device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
