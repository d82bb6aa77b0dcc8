"""Chip peaks and the least time a piece of work can take on them.

The peaks are the table in ``peaks.json`` beside this file, keyed by the
``device_kind`` JAX reports, with their source.  A device that is not in
the table is an error, never a default.

Work is counted from the algorithm and the cell's shapes (see the kernel
readers under ``metrics/``), in two numbers: f32 operations and bytes that
must cross HBM.  An f32 matmul at precision ``highest`` is six bf16 passes
on the MXU, so f32 operations are counted at a sixth of the bf16 peak.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
F32_HIGHEST_PASSES = 6


class UnknownDevice(RuntimeError):
    """The chip is not in the peaks table."""


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    peaks = table["devices"].get(device_kind)
    if peaks is None:
        raise UnknownDevice(
            f"device {device_kind!r} is not in the peaks table "
            f"({', '.join(sorted(table['devices']))})")
    return peaks


def least_time(peaks: dict, f32_ops: float, hbm_bytes: float
               ) -> tuple[float, str]:
    """(seconds, bound): the larger of compute time and memory time."""
    t_ops = f32_ops * F32_HIGHEST_PASSES / peaks["bf16_flops"]
    t_mem = hbm_bytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops > t_mem else (t_mem, "hbm")


def kernel_share(ctx, pattern: str, per_call) -> float | None:
    """Roofline share (%) of the device ops whose name starts with
    ``pattern`` (the kernel's name as ``devtrace.op_name`` gives it): the
    least time of the work they had to do over their summed device time.
    ``per_call(ctx, block)`` gives one call's (f32 ops, HBM bytes) for the
    window's ``block``-th block; the kernel must run once per block, or
    there is nothing to read.  Logs which bound sets the least time."""
    names = [n for n in ctx.op_seconds if n.startswith(pattern)]
    calls = sum(ctx.op_counts.get(n, 0) for n in names)
    seconds = sum(ctx.op_seconds[n] for n in names)
    if not names or not seconds or calls != ctx.blocks:
        return None
    least = [least_time(ctx.peaks, *per_call(ctx, b))
             for b in range(ctx.blocks)]
    bounds = sorted({bound for _, bound in least})
    total = sum(t for t, _ in least)
    print(f"[bench] {pattern} least time {total:.6f} s ({'/'.join(bounds)} "
          f"bound) over {seconds:.6f} s in {calls} calls", file=sys.stderr)
    return 100.0 * total / seconds
