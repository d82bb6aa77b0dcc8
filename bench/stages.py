#!/usr/bin/env python3
"""Device time by the search program's stage scopes, and the device's idle
time split at the serving spans, from one traced window of a cell.

    python3 bench/stages.py --workload sift1m-ivfrabitq.k100k --seed 7 \\
        --seconds 10 [--keep profiles]

Run from the root of a checkout, on the chip.  Builds and warms the cell
as ``bench/run.py`` does (``harness.setup``), profiles ``--seconds`` of
its closed-loop traffic (``harness.run_window``) and prints one JSON
object.  Its readings carry the names a per-layer metric would take:

- ``stage.<s>_ms_per_req`` for s in route, plan, scan, collect, rerank
  and final: device self milliseconds per completed request of the ops in
  the program's ``bbc.<s>`` scope;
- ``stage.unscoped_share``: the share of device self time in no scope;
- ``serving.fetch_idle_share`` and ``serving.trim_idle_share``: idle
  device time under the ``Server``'s ``serving.fetch`` and
  ``serving.trim`` spans, as a share of the window.

Beside them, for comparison, it prints ``search.device_ms_per_req`` and
the midpoint-labelled ``idle_by_span`` that ``bench/run.py --trace 1``
reads from the same kind of window.  It checks no results.
``bench/run.py`` does not call this module: its readers see neither the
host spans nor an op's scope.

The stage of a device op comes from its ``tf_op`` stat, the op_name path
XLA writes from the HLO ``op_name`` metadata (``jit(f)/bbc.scan/...``).  A
fusion carries the op_name of its root, so a fusion is charged to the
scope of its root; a fusion or copy the compiler made with no source op
is ``none``.  Idle gaps are cut where host spans start and end, and each
piece goes to the innermost span covering it (``idle_split_by_span``),
where ``devtrace.idle_by_span`` gives a whole gap to the span around its
midpoint.
"""
from __future__ import annotations

import argparse
import bisect
import itertools
import json
import os
import sys
import tempfile
from collections import defaultdict

import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STAGES = ("bbc.route", "bbc.plan", "bbc.scan", "bbc.collect", "bbc.rerank",
          "bbc.final")
SCOPE_PREFIX = "bbc."
NO_SCOPE = "none"
SCOPE_STAT = "tf_op"
SPLIT_SPANS = ("serving.fetch", "serving.trim")


def _proto_fields(buf: bytes):
    """(field number, value) of each field of one serialized protobuf
    message: ints for varints and fixed-width fields, bytes for the
    length-delimited ones."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out
    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            val = varint()
        elif wire == 2:
            size = varint()
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, val


def op_scopes(path: str) -> dict[str, str]:
    """``{op event name: op_name path}`` of every device plane, from the
    ``tf_op`` stat of each event metadata.  ``ProfileData`` shows an
    event's own stats only, so the ``XSpace`` message is read here: planes
    (field 1); a plane's name (2), event metadata (4) and stat metadata (5)
    maps; an event metadata's name (2) and stats (5); a stat's metadata id
    (1) and string (5) or interned string (7)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, plane in _proto_fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, val in _proto_fields(plane):
            if pnum == 2:
                name = val.decode()
            elif pnum == 4:
                events.append(dict(_proto_fields(val)).get(2, b""))
            elif pnum == 5:
                md = dict(_proto_fields(dict(_proto_fields(val)).get(2, b"")))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        if not name.startswith(devtrace.DEVICE_PLANE_PREFIX):
            continue
        for md in events:
            fields = list(_proto_fields(md))
            ev_name = next((v.decode() for k, v in fields if k == 2), "")
            for k, stat in fields:
                st = dict(_proto_fields(stat)) if k == 5 else {}
                if stat_names.get(st.get(1)) == SCOPE_STAT:
                    out[ev_name] = (st[5].decode() if 5 in st
                                    else stat_names.get(st.get(7), ""))
    return out


def scope_of(op_path: str) -> str:
    """The innermost ``bbc.*`` component of an op_name path, else
    ``none``."""
    parts = [p for p in op_path.split("/") if p.startswith(SCOPE_PREFIX)]
    return parts[-1].rstrip(":") if parts else NO_SCOPE


def stage_form(path: str, host: list) -> dict:
    """The plain form of ``devtrace.load_xplane`` with each device op
    named by its stage (``bbc.route`` .. ``bbc.final``, or ``none``) and
    the host spans ``host``: ``devtrace.op_seconds`` of it gives device
    self seconds per stage."""
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    device = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE_PREFIX):
            device[plane.name] = [
                [scope_of(scopes.get(e.name, "")), int(e.start_ns),
                 int(e.duration_ns)]
                for line in plane.lines if line.name == devtrace.OPS_LINE
                for e in line.events]
    return {"device": device, "host": host}


def idle_split_by_span(tr: dict) -> dict[str, float]:
    """Idle device seconds in the window by host span, averaged over the
    device planes.  Each gap is cut at the starts and ends of the spans
    inside it, and each piece goes to the innermost span covering it
    (``none`` where none does)."""
    t0, t1 = devtrace.window(tr)
    spans = sorted(tr["host"], key=lambda e: e[1])
    starts = [s for _, s, _ in spans]
    # ends_upto[i]: the latest end of spans[:i + 1]; no span before the
    # first index whose value passes ``a`` reaches ``a``
    ends_upto = list(itertools.accumulate((s + d for _, s, d in spans), max))
    out: dict[str, float] = defaultdict(float)
    planes = list(tr["device"].values())
    for evs in planes:
        edge, gaps = t0, []
        for a, b in devtrace.busy_intervals(evs, t0, t1):
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if t1 > edge:
            gaps.append((edge, t1))
        for a, b in gaps:
            lo = bisect.bisect_right(ends_upto, a)
            hi = bisect.bisect_left(starts, b)
            near = [(s, s + d, d, n) for n, s, d in spans[lo:hi]
                    if s + d > a]
            cuts = sorted({a, b} | {x for s, e, _, _ in near for x in (s, e)
                                    if a < x < b})
            for p, q in zip(cuts, cuts[1:]):
                cover = [(d, n) for s, e, d, n in near if s <= p and q <= e]
                owner = min(cover)[1] if cover else NO_SCOPE
                out[owner] += (q - p) / 1e9 / len(planes)
    return dict(out)


def readings(tr: dict, st: dict, completed: int) -> dict[str, float]:
    """The readings of one window from its trace named by op (``tr``) and
    by stage (``st``).  The stage readings are left out where no op
    carries a stage, as in a program without the scopes, and the serving
    ones where neither split span holds idle time."""
    t0, t1 = devtrace.window(tr)
    out = {}
    stage_s = devtrace.op_seconds(st)
    if completed and set(stage_s) - {NO_SCOPE}:
        for s in STAGES:
            out[f"stage.{s[len(SCOPE_PREFIX):]}_ms_per_req"] = (
                1e3 * stage_s.get(s, 0.0) / completed)
        out["stage.unscoped_share"] = (stage_s.get(NO_SCOPE, 0.0)
                                       / sum(stage_s.values()))
    idle = idle_split_by_span(tr)
    if any(s in idle for s in SPLIT_SPANS):
        for s in SPLIT_SPANS:
            out[f"{s}_idle_share"] = idle.get(s, 0.0) / ((t1 - t0) / 1e9)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", help="directory to keep the profile in")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src")]
    import harness
    from roofline import UnknownDevice
    cell = harness.load_cell(args.workload)
    try:
        harness.check_device(cell.chips)
    except (harness.NoChip, UnknownDevice) as e:
        print(f"[stages] {e}", file=sys.stderr, flush=True)
        return 1
    harness.enable_compile_cache()
    compiles = harness.CompileCounter()
    s = harness.setup(cell, args.seed)
    with tempfile.TemporaryDirectory() as tmp:
        log_dir = (os.path.join(args.keep, f"{args.workload}.{args.seed}")
                   if args.keep else tmp)
        w = harness.run_window(s, args.seconds, log_dir, compiles, set())
        path = devtrace.find_xplane(log_dir)
        tr = devtrace.load_xplane(path)
        st = stage_form(path, tr["host"])
    completed = sum(n for _, n, _, _ in w.served)
    t0, t1 = devtrace.window(tr)
    out = {"workload": args.workload, "seed": args.seed,
           "window_s": (t1 - t0) / 1e9, "busy_s": devtrace.busy_seconds(tr),
           "blocks": len(w.served), "completed": completed,
           "compiles_in_window": w.compiles,
           "readings": readings(tr, st, completed),
           "search.device_ms_per_req":
               1e3 * sum(devtrace.op_seconds(tr).values()) / completed,
           "stage_seconds": devtrace.op_seconds(st),
           "idle_by_span": devtrace.idle_by_span(tr),
           "idle_split_by_span": idle_split_by_span(tr)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
