#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload sift1m-ivfpq4.k5k \\
        --seeds 101,102,103 --seconds 3

For each seed, one short run of the cell as ``run.py`` makes it (the
window at the cell's own load, the direct parity calls, the reference),
and the control's numbers on the same sampled queries: the reference at
three-pass bf16 precision in the program's place.  Prints one JSON line
per seed, then the largest program reading and the smallest control
reading of each number.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# worse is higher for these readings of each check's number
WORSE = {"mean_recall": lambda v: 1.0 - v, "median_dist_gap": lambda v: v,
         "parity_mismatch": lambda v: v}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    import harness
    cell = harness.load_cell(args.workload)
    prog, ctrl = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False, time.perf_counter(),
                          control=True)
        line = {"seed": seed, "correct": out["correct"],
                "qps": out["metrics"]["qps"]["value"],
                "program": {n: c["value"] for n, c in out["checks"].items()},
                "control": {n: c["value"]
                            for n, c in out["control_checks"].items()},
                "widest_gap": out["widest_gap"],
                "worst_recall": out["worst_recall"]}
        print(json.dumps(line), flush=True)
        for n, v in line["program"].items():
            prog.setdefault(n, []).append(v)
        for n, v in line["control"].items():
            ctrl.setdefault(n, []).append(v)
    summary = {n: {"program_worst": max(prog[n], key=WORSE[n]),
                   "control_best": min(ctrl[n], key=WORSE[n])}
               for n in prog}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
