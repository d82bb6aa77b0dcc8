"""Device time by stage scope and the idle split at host spans
(``stages.py``), on hand-made traces, a hand-encoded XSpace and a trace
recorded on the chip."""
import json
import os

import pytest

import devtrace
import stages
from test_trace import MS, _made

HERE = os.path.dirname(os.path.abspath(__file__))
STAGE_READINGS = ["stage.route_ms_per_req", "stage.plan_ms_per_req",
                  "stage.scan_ms_per_req", "stage.collect_ms_per_req",
                  "stage.rerank_ms_per_req", "stage.final_ms_per_req"]
SERVING_READINGS = ["serving.fetch_idle_share", "serving.trim_idle_share"]


def _staged():
    # _made() with each op named by its stage: the while op and one body
    # op in bbc.rerank, the kernel in bbc.scan, two ops in no stage
    t = _made()
    names = ["bbc.rerank", "bbc.rerank", "bbc.scan", "none", "bbc.scan",
             "none", "bbc.final"]
    t["device"]["/device:TPU:0"] = [
        [n] + e[1:] for n, e in zip(names, t["device"]["/device:TPU:0"])]
    return t


def _served_batch():
    # one block: the device runs [0, 4) and [18, 20) ms; the gap between
    # runs from the end of the wait through fetch, trim and assembly
    return {
        "device": {"/device:TPU:0": [["a", 0, 4 * MS],
                                     ["b", 18 * MS, 2 * MS]]},
        "host": [["bench.block", 0, 20 * MS],
                 ["serving.wait", 1 * MS, 4 * MS],
                 ["serving.finish", 5 * MS, 10 * MS],
                 ["serving.fetch", 5 * MS, 3 * MS],
                 ["serving.trim", 8 * MS, 6 * MS],
                 ["serving.assemble", 16 * MS, 2 * MS]],
    }


def test_scope_of_takes_the_innermost_stage():
    assert stages.scope_of("jit(ivf_pq_search_batch)/bbc.scan/"
                           "jit(fused_scan_batch)/pallas_call:") == "bbc.scan"
    assert stages.scope_of("jit(f)/bbc.collect/cond/branch_1_fun/top_k:") == \
        "bbc.collect"
    assert stages.scope_of("jit(f)/bbc.rerank:") == "bbc.rerank"
    assert stages.scope_of("jit(f)/while/body/add:") == "none"
    assert stages.scope_of("") == "none"


def test_stage_self_seconds_sum_to_busy():
    t = _staged()
    st = devtrace.op_seconds(t)
    # the while op keeps what its body ops leave: 6 - 2 - 2.5 ms
    assert st == pytest.approx({"bbc.rerank": 0.0015 + 0.002,
                                "bbc.scan": 0.0025 + 0.002,
                                "none": 0.0005 + 0.0, "bbc.final": 0.001})
    assert sum(st.values()) == pytest.approx(devtrace.busy_seconds(t))
    r = stages.readings(_made(), t, completed=4)
    assert r["stage.rerank_ms_per_req"] == pytest.approx(1e3 * 0.0035 / 4)
    assert r["stage.route_ms_per_req"] == 0.0
    total = sum(r[n] for n in STAGE_READINGS) + 1e3 * st["none"] / 4
    assert total == pytest.approx(1e3 * devtrace.busy_seconds(t) / 4)
    assert r["stage.unscoped_share"] == pytest.approx(0.0005 / 0.0095)


def test_stage_readings_left_out_without_stages():
    # a program with no stage scopes: every op is "none"
    t = _made()
    unscoped = {"device": {p: [["none"] + e[1:] for e in evs]
                           for p, evs in t["device"].items()},
                "host": t["host"]}
    for st in (unscoped, {"device": {}, "host": t["host"]}):
        r = stages.readings(t, st, completed=4)
        assert not set(r) & set(STAGE_READINGS + ["stage.unscoped_share"])
    assert "stage.scan_ms_per_req" not in stages.readings(t, _staged(), 0)


def test_idle_split_cuts_gaps_at_span_edges():
    t = _made()
    idle = stages.idle_split_by_span(t)
    # [7, 10.5) is finish to 10, then the gap between blocks; [11, 13) is
    # that gap to 12, then the second block
    assert idle == pytest.approx({"bench.block": 0.006,
                                  "serving.finish": 0.003,
                                  "bench.gap": 0.0015})
    assert sum(idle.values()) == pytest.approx(0.02 - devtrace.busy_seconds(t))


def test_idle_split_shares_a_gap_between_fetch_and_trim():
    t = _served_batch()
    assert stages.idle_split_by_span(t) == pytest.approx(
        {"serving.wait": 0.001, "serving.fetch": 0.003,
         "serving.trim": 0.006, "serving.finish": 0.001,
         "bench.block": 0.001, "serving.assemble": 0.002})
    # the midpoint labelling gives the whole gap to trim
    assert devtrace.idle_by_span(t) == pytest.approx({"serving.trim": 0.014})


def test_serving_idle_readings():
    t = _served_batch()
    r = stages.readings(t, t, completed=16)
    assert r["serving.fetch_idle_share"] == pytest.approx(0.15)
    assert r["serving.trim_idle_share"] == pytest.approx(0.3)
    # only trim holds idle: fetch reads 0, not nothing
    t["host"] = [s for s in t["host"] if s[0] != "serving.fetch"]
    r = stages.readings(t, t, completed=16)
    assert r["serving.fetch_idle_share"] == 0.0
    # neither span (a program without them): nothing to read
    t["host"] = [s for s in t["host"] if s[0] != "serving.trim"]
    assert not set(stages.readings(t, t, 16)) & set(SERVING_READINGS)


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num: int, val) -> bytes:
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _xspace() -> bytes:
    """A hand-encoded XSpace: a device plane whose event metadata carry a
    ``tf_op`` stat as a string and as an interned reference, one without
    it, a fixed-width stat to skip, three op events on its ``XLA Ops``
    line, and a host plane that is left out."""
    stat_md = b"".join(
        _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
        for i, n in ((1, "tf_op"), (2, "flops"),
                     (3, "jit(f)/bbc.final/top_k:")))

    def metadata(i, name, *stats):
        return _field(4, _field(1, i) + _field(
            2, _field(1, i) + _field(2, name) + b"".join(
                _field(5, s) for s in stats)))

    def event(md, offset_ms, dur_ms):
        return _field(4, _field(1, md) + _field(2, offset_ms * MS * 1000)
                      + _field(3, dur_ms * MS * 1000))
    flops = _field(1, 2) + _varint(2 << 3 | 1) + (7).to_bytes(8, "little")
    ops = _field(3, _field(1, 1) + _field(2, devtrace.OPS_LINE)
                 + _field(3, 1000 * MS) + event(1, 0, 2) + event(2, 2, 3)
                 + event(3, 5, 1))
    device = (_field(2, "/device:TPU:0") + ops + stat_md
              + metadata(1, "%sort.3 = sort(...)", flops,
                         _field(1, 1) + _field(5, "jit(f)/bbc.scan/sort:"))
              + metadata(2, "%fusion.9 = fusion(...)", _field(1, 1)
                         + _field(7, 3))
              + metadata(3, "%copy.1 = copy(...)", flops))
    host = _field(2, "/host:CPU") + stat_md + metadata(
        1, "%sort.3 = sort(...)", _field(1, 1) + _field(5, "jit(g)/x:"))
    return _field(1, device) + _field(1, host)


def test_op_scopes_reads_the_tf_op_stat(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    scopes = stages.op_scopes(str(path))
    assert scopes == {"%sort.3 = sort(...)": "jit(f)/bbc.scan/sort:",
                      "%fusion.9 = fusion(...)": "jit(f)/bbc.final/top_k:"}
    assert stages.scope_of(scopes.get("%copy.1 = copy(...)", "")) == "none"


def test_stage_form_names_each_op_by_its_stage(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    host = [["bench.block", 1000 * MS, 6 * MS]]
    st = stages.stage_form(str(path), host)
    assert st["host"] is host
    assert st["device"] == {"/device:TPU:0": [
        ["bbc.scan", 1000 * MS, 2 * MS], ["bbc.final", 1002 * MS, 3 * MS],
        ["none", 1005 * MS, 1 * MS]]}
    assert devtrace.op_seconds(st) == pytest.approx(
        {"bbc.scan": 0.002, "bbc.final": 0.003, "none": 0.001})


def test_recorded_trace_with_stages():
    """A few blocks of a traced run of sift1m-ivfrabitq.k100k on one TPU
    v5e chip, with each device op named by its stage."""
    with open(os.path.join(HERE, "data", "trace_stages_small.json")) as f:
        t = json.load(f)
    busy = devtrace.busy_seconds(t)
    st = devtrace.op_seconds(t)
    assert set(st) <= set(stages.STAGES) | {"none"}
    assert set(stages.STAGES) <= set(st)
    assert sum(st.values()) == pytest.approx(busy)
    assert st["none"] / busy < 0.05
    idle = stages.idle_split_by_span(t)
    r = stages.readings(t, t, completed=48)
    # the device is idle through both the copy and the trim
    assert r["serving.trim_idle_share"] > 0
    assert r["serving.fetch_idle_share"] > 0
    finish = sum(idle.get(n, 0.0) for n in
                 ("serving.finish", "serving.fetch", "serving.trim"))
    assert idle["serving.fetch"] + idle["serving.trim"] >= 0.8 * finish
    assert sum(idle.values()) == pytest.approx(
        sum(devtrace.idle_by_span(t).values()))
