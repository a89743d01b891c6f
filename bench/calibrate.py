"""Readings from which a cell's limits are set: the numbers that the check
compares, for the program on many seeds and for the control (the reference
in the next lower precision, in the program's place) on a few, all in one
process on the chip at the cell's own size.  Not run by the benchmark.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --seconds 2 \
        --control-seeds 3 --out cal.json

Each seed is one ``run_cell`` of the cell with a short window; the first
``--control-seeds`` seeds also run the check with the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = bench_run.load_cell(args.workload)
    rows = []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        try:
            res = bench_run.run_cell(cell, seed, args.seconds, False,
                                     t_start=time.perf_counter(),
                                     control=n < args.control_seeds)
        except bench_run.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": res["correct"],
               "numbers": {k: v["value"] for k, v in res["checked"].items()},
               "control": res["log"].get("control"),
               "latency_s": res["log"]["latency_s"],
               "compile_s": res["log"]["compile_s"],
               "check_s": res["log"]["check_s"]}
        rows.append(row)
        print(json.dumps(row, default=float), flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f,
                      indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
