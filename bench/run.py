#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload sift1m-ivfrabitq.k100k --seed 7 \\
        --seconds 45 --trace 0

Run from the root of a checkout.  Builds the cell from ``--seed`` (corpus,
queries and index are made on the device), warms its shapes, serves the
closed-loop traffic for ``--seconds``, then checks the served results
against the plain reference.  ``--trace 1`` profiles the window and reports
the cell's per-layer metrics in place of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and last ``checks``: each number compared beside its limit,
which are also the last lines on standard error.  Exits non-zero, with no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[bench] no program under {src}: run from a checkout of the "
              f"repository", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, src]
    import harness
    from roofline import UnknownDevice
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS)
    except (harness.NoChip, UnknownDevice) as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
