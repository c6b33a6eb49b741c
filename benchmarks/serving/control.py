"""Control of the correctness check: the reference in the next lower
precision, put in the program's place, must come out as not correct.

    python3 benchmarks/serving/control.py --workload qwen3-1.7b-densew.chat \
        --seeds 11,12,13 --seconds 20

For each seed, in one process: a run of the cell as ``run.py`` makes it
(set-up, a window of ``--seconds``), then the comparison that decides
``correct``, with the tokens that the reference in the configuration's
``control`` precision ranks first, at the same positions of the same
prompts and served tokens, in place of the served ones.  The program's own
gap goes to stderr.  Prints one JSON line per seed; each should read
``"correct": false``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import run
    import spec
    cell = spec.load_cell(args.workload)
    found = run.chip(cell)
    if found is None:
        return 2
    device, peaks = found
    import harness
    t0 = T_START                       # later seeds' set-up starts anew
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False, t0, peaks, device,
                          control=cell.config["control"])
        t0 = time.perf_counter()
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
