"""Reduction of a profiler trace to the benchmark's device readings.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain form, ``{"device": {plane: [[name, start_ns, dur_ns], ...]},
"host": [[name, start_ns, dur_ns], ...]}``: the device planes' op events
(from the ``XLA Ops`` line, named by ``op_name``) and the harness's own
host spans (names starting with one of
``HOST_PREFIXES``).  Everything else works on that form, so the tests can
check it against a small recorded trace.

The window is the stretch from the start of the first ``bench.block`` span
to the end of the last one.  Busy time is the union of a device plane's op
intervals clipped to the window, averaged over the device planes; an op's
time is its self time (what the ops nested in it leave).  An idle
gap (no op running) is labelled with the innermost harness span that covers
its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

BLOCK = "bench.block"
HOST_PREFIXES = ("bench.", "serving.")
DEVICE_PLANE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
TOP = 10
SPAN_DEPTH = 8


def op_name(hlo: str) -> str:
    """``"sort.3 sort"`` from an op event's HLO text
    (``"%sort.3 = (f32[...], ...) sort(...), ..."``): the instruction's name
    and its opcode.  Text that is not an HLO instruction comes back whole."""
    if not hlo.startswith("%") or " = " not in hlo:
        return hlo
    name, rest = hlo[1:].split(" = ", 1)
    if rest.startswith("("):                    # a tuple-typed result
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return f"{name} {rest.strip().split('(', 1)[0]}".strip()


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [[op_name(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            device[plane.name] = evs
        else:
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return {"device": device, "host": host}


def window(tr: dict) -> tuple[int, int]:
    blocks = [(s, s + d) for n, s, d in tr["host"] if n == BLOCK]
    if not blocks:
        raise RuntimeError(f"the trace holds no {BLOCK!r} span")
    return min(b[0] for b in blocks), max(b[1] for b in blocks)


def _clipped(events, t0: int, t1: int):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def busy_intervals(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Union of the events' intervals inside [t0, t1], sorted."""
    out: list[list[int]] = []
    for _, a, b in sorted(_clipped(events, t0, t1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(tr: dict) -> float:
    """Busy seconds in the window, averaged over the device planes."""
    t0, t1 = window(tr)
    planes = list(tr["device"].values())
    if not planes:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(evs, t0, t1))
               for evs in planes) / len(planes) / 1e9


def op_seconds(tr: dict) -> dict[str, float]:
    """Device self seconds per op name in the window, summed over the
    planes.  Control-flow ops (``while``, ``conditional``) span the ops of
    their bodies on the same line; each op is charged only the time that
    none of the ops inside it covers, so the sum is the busy time."""
    t0, t1 = window(tr)
    out: dict[str, float] = defaultdict(float)
    for evs in tr["device"].values():
        open_: list[list] = []                  # [name, end] of the parents
        for name, a, b in sorted(_clipped(evs, t0, t1),
                                 key=lambda e: (e[1], -e[2])):
            while open_ and open_[-1][1] <= a:
                open_.pop()
            if open_:
                out[open_[-1][0]] -= (min(b, open_[-1][1]) - a) / 1e9
            out[name] += (b - a) / 1e9
            open_.append([name, b])
    return dict(out)


def op_counts(tr: dict) -> dict[str, int]:
    """Number of events per op name that start inside the window."""
    t0, t1 = window(tr)
    out: dict[str, int] = defaultdict(int)
    for evs in tr["device"].values():
        for name, s, _ in evs:
            if t0 <= s < t1:
                out[name] += 1
    return dict(out)


def idle_by_span(tr: dict) -> dict[str, float]:
    """Idle device seconds in the window by the harness span around each
    gap (innermost span covering the gap's midpoint; ``none`` where no
    span does), averaged over the device planes."""
    t0, t1 = window(tr)
    spans = sorted(tr["host"], key=lambda e: e[1])
    starts = [s for _, s, _ in spans]
    out: dict[str, float] = defaultdict(float)
    planes = list(tr["device"].values())
    for evs in planes:
        edge = t0
        gaps = []
        for a, b in busy_intervals(evs, t0, t1):
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if t1 > edge:
            gaps.append((edge, t1))
        for a, b in gaps:
            mid = (a + b) / 2
            # spans nest at most a few deep: the ones covering ``mid`` are
            # among the last few that started before it
            i = bisect.bisect_right(starts, mid)
            cover = [(d, n) for n, s, d in spans[max(0, i - SPAN_DEPTH):i]
                     if mid <= s + d]
            out[min(cover)[1] if cover else "none"] += (b - a) / 1e9 / len(
                planes)
    return dict(out)


def breakdown(tr: dict) -> dict:
    """The ``breakdown`` of a traced run's result line: the device ops
    with the most time, and idle time by what the host was doing."""
    ops = sorted(op_seconds(tr).items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(idle_by_span(tr).items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
