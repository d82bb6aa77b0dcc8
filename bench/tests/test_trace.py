"""The trace reduction on a hand-made trace and on a recorded one."""
import json
import os

import pytest

import roofline
import devtrace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _made():
    # two blocks [0, 10) and [12, 20) ms; a while op in the first holds two
    # body ops
    return {
        "device": {"/device:TPU:0": [
            ["while.1 while", 1 * MS, 6 * MS],
            ["fusion.2 fusion", 2 * MS, 2 * MS],
            ["kernel.3 custom-call", 4 * MS, 5 * MS // 2],
            ["copy", 10 * MS + MS // 2, MS // 2],
            ["scan", 13 * MS, 2 * MS], ["before", -5 * MS, 4 * MS],
            ["straddle", 19 * MS, 3 * MS]]},
        "host": [["bench.block", 0, 10 * MS], ["bench.gap", 10 * MS, 2 * MS],
                 ["bench.block", 12 * MS, 8 * MS],
                 ["serving.finish", 7 * MS, 3 * MS]],
    }


def test_window_and_busy_union():
    t = _made()
    assert tr.window(t) == (0, 20 * MS)
    # [1, 7) + [10.5, 11) + [13, 15) + [19, 20) = 9.5 ms; the op before
    # the window is out, the one across its end is clipped
    assert tr.busy_intervals(t["device"]["/device:TPU:0"], 0, 20 * MS) == [
        (1 * MS, 7 * MS), (10 * MS + MS // 2, 11 * MS), (13 * MS, 15 * MS),
        (19 * MS, 20 * MS)]
    assert tr.busy_seconds(t) == pytest.approx(0.0095)


def test_op_self_seconds_and_counts():
    t = _made()
    ops = tr.op_seconds(t)
    assert ops == pytest.approx(
        {"while.1 while": 0.0015, "fusion.2 fusion": 0.002,
         "kernel.3 custom-call": 0.0025, "copy": 0.0005, "scan": 0.002,
         "straddle": 0.001})
    assert sum(ops.values()) == pytest.approx(tr.busy_seconds(t))
    assert tr.op_counts(t) == {
        "while.1 while": 1, "fusion.2 fusion": 1, "kernel.3 custom-call": 1,
        "copy": 1, "scan": 1, "straddle": 1}


def test_idle_gaps_take_the_innermost_span():
    t = _made()
    idle = tr.idle_by_span(t)
    # gaps [0, 1) and [15, 19) in a block; [7, 10.5) in finish, which
    # nests in the first block; [11, 13) in the gap between blocks
    assert idle == pytest.approx({"bench.block": 0.005,
                                  "serving.finish": 0.0035,
                                  "bench.gap": 0.002})
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["kernel.3 custom-call",
                                  pytest.approx(0.0025)]
    assert b["idle_gaps"][0] == ["bench.block", pytest.approx(0.005)]


def test_op_name_keeps_name_and_opcode():
    assert tr.op_name("%sort.3 = (f32[16,8]{1,0}, s32[16,8]{1,0}) sort("
                      "f32[16,8]{1,0} %a, s32[16,8]{1,0} %b), dimensions={1}"
                      ) == "sort.3 sort"
    assert tr.op_name("%fusion.21 = f32[1600000]{0:T(1024)} fusion(f32[16,"
                      "100]{0,1} %s), kind=kCustom") == "fusion.21 fusion"
    assert tr.op_name("jit_search(123)") == "jit_search(123)"


def test_no_block_span_is_an_error():
    with pytest.raises(RuntimeError, match="bench.block"):
        tr.window({"device": {}, "host": []})


def test_recorded_trace():
    """A few blocks of a traced run of sift1m-ivfrabitq.k100k on one TPU
    v5e chip, reduced to the plain form."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        t = json.load(f)
    t0, t1 = tr.window(t)
    busy = tr.busy_seconds(t)
    assert 0 < busy < (t1 - t0) / 1e9
    ops = tr.op_seconds(t)
    assert any(n.startswith("fused_rabitq_scan_batch.") for n in ops)
    assert any(n.startswith("l2_exact_batch.") for n in ops)
    assert sum(ops.values()) == pytest.approx(busy)
    idle = tr.idle_by_span(t)
    assert busy + sum(idle.values()) == pytest.approx((t1 - t0) / 1e9)


def test_unknown_device_is_an_error():
    with pytest.raises(roofline.UnknownDevice, match="TPU v99"):
        roofline.load_peaks("TPU v99")


def test_least_time_names_its_bound():
    peaks = roofline.load_peaks("TPU v5 lite")
    t, bound = roofline.least_time(peaks, 0.0, 819e9)
    assert (t, bound) == (pytest.approx(1.0), "hbm")
    t, bound = roofline.least_time(peaks, 197e12 / 6, 0.0)
    assert (t, bound) == (pytest.approx(1.0), "compute")


def test_kernel_share_reads_only_a_kernel_run_once_per_block():
    from types import SimpleNamespace
    peaks = roofline.load_peaks("TPU v5 lite")
    ctx = SimpleNamespace(
        peaks=peaks, blocks=2,
        op_seconds={"fused_scan_batch.1 custom-call": 0.004, "sort.1 sort": 1},
        op_counts={"fused_scan_batch.1 custom-call": 2, "sort.1 sort": 2})

    def work(ctx, b):                   # 819 MB each: 1 ms at 819 GB/s
        return 0.0, 819e6
    assert roofline.kernel_share(ctx, "fused_scan_batch.", work) == \
        pytest.approx(50.0)
    ctx.op_counts["fused_scan_batch.1 custom-call"] = 3
    assert roofline.kernel_share(ctx, "fused_scan_batch.", work) is None
    assert roofline.kernel_share(ctx, "l2_exact_batch.", work) is None
