"""Live-socket integration tests for the transport tier (PR 10).

Everything in this file runs REAL worker subprocesses over Unix-domain
sockets: round-trip parity against in-process engine calls, the exact-key
result cache over a Zipf trace, typed rejection of malformed / corrupt /
oversized frames (workers must survive all of it), byte-identical
record/replay of a live run, worker-death detection + respawn, and a
subprocess SIGTERM graceful-drain test of ``launch/serve.py --mode net``.

These tests spawn engines (~seconds of JAX compile per process), so the
file shares one module-scoped server across the fast tests and keeps the
expensive standalone scenarios (respawn, SIGTERM) to one server each.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.data import synthetic
from repro.serving.batcher import k_ceilings
from repro.serving.queue import make_zipf_trace
from repro.serving.router import RetryPolicy, outcome_digest
from repro.transport import frames
from repro.transport.client import NetClient
from repro.transport.core import MasterConfig
from repro.transport.enginehost import (build_spec, build_state_from_spec,
                                        make_dataset, make_exec_fn)
from repro.transport.master import MasterServer
from repro.transport.replay import replay_transcript
from repro.transport.wire import Transcript

KS = (10, 100)
SPEC = build_spec(n=4096, d=16, seed=0, ks=KS, n_probe=8)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand_q(rng):
    return rng.standard_normal(SPEC["d"]).astype(np.float32)


def _trace(n, seed=0, rate=150.0, deadline=5.0):
    rng = np.random.default_rng(seed)
    x = make_dataset(SPEC)
    pool = synthetic.queries_from(rng, x, 8)
    return make_zipf_trace(rng, pool, n, KS, rate=rate, deadline=deadline,
                           n_probe=SPEC["n_probe"])


@pytest.fixture(scope="module")
def net():
    """One live master + 2 worker subprocesses + an in-process twin engine
    (for parity and replay), shared by the fast tests below."""
    cfg = MasterConfig(n_workers=2, ceilings=k_ceilings(KS), cache_size=64)
    ms = MasterServer(cfg, SPEC, record=True)
    ms.start()
    assert ms.wait_workers(timeout=300.0), "workers never came up"
    stop = threading.Event()
    th = threading.Thread(target=lambda: ms.serve(until=stop.is_set),
                          daemon=True)
    th.start()
    state, ceilings = build_state_from_spec(SPEC)
    ns = SimpleNamespace(ms=ms, stop=stop, thread=th, cfg=cfg, state=state,
                         exec_fn=make_exec_fn(state, ceilings))
    yield ns
    stop.set()
    th.join(timeout=10.0)
    ms.shutdown()


def test_ready_frames_report_worker_devices(net):
    """Every worker's READY frame names the devices its engine runs on."""
    devices = net.ms.worker_devices
    assert sorted(devices) == list(range(net.cfg.n_workers))
    assert all(devs and all(isinstance(d, str) for d in devs)
               for devs in devices.values()), devices


def test_live_roundtrip_parity_and_cache(net):
    trace = _trace(40)
    with NetClient(net.ms.addr) as c:
        records = c.run_trace(trace, settle=30.0)
    assert len(records) == len(trace)
    by_rid = {r.rid: r for r in trace}
    for rid, rec in records.items():
        assert rec["status"] in ("ok", "degraded"), (rid, rec)
        req = by_rid[rid]
        _, ids = net.exec_fn(req.q, req.k, req.n_probe)
        # parity: what came over the wire == the direct in-process call,
        # cached or not (cache hits are byte-identical by construction)
        np.testing.assert_array_equal(np.asarray(rec["ids"]),
                                      np.asarray(ids))
    # the Zipf head actually hit the exact-key cache
    assert any(r["cached"] for r in records.values())
    assert net.ms.core.stats["cache_hits"] > 0


def test_live_malformed_frames_typed_errors_workers_survive(net):
    ms = net.ms
    # stream-level garbage: typed bad_frame error, then the server closes
    c = NetClient(ms.addr).connect()
    c.send_raw(b"\xff\xff\xff\xff garbage that is not a frame")
    r = c.recv_reply(timeout=10.0)
    assert r is not None and r["kind"] == frames.ERR
    assert r["code"] == "bad_frame"
    with pytest.raises(ConnectionError):    # no resync point: conn closed
        c.recv_reply(timeout=10.0)
    c.sock.close()

    # seeded fuzz over the real wire: corrupted copies of a valid frame
    rng = np.random.default_rng(7)
    base = frames.encode_frame(
        {"kind": frames.REQ, "rid": 1, "q": frames.pack_array(_rand_q(rng)),
         "k": 10, "n_probe": 8, "deadline_s": 1.0}, "json")
    for trial in range(8):
        blob = bytearray(base)
        for _ in range(3):
            blob[int(rng.integers(0, len(blob)))] = int(rng.integers(0, 256))
        cx = NetClient(ms.addr).connect()
        try:
            cx.send_raw(bytes(blob))
            reply = cx.recv_reply(timeout=5.0)
            # any reply must be typed protocol traffic, never silence from
            # a crashed master (None = corrupt bytes happened to parse as a
            # valid frame the server is still waiting to complete)
            if reply is not None:
                assert reply["kind"] in (frames.ERR, frames.RESP,
                                         frames.RETRY_AFTER)
        except ConnectionError:
            pass                            # closed on corruption: correct
        finally:
            cx.sock.close()

    # structurally-valid frames with hostile payloads: typed errors, the
    # connection stays open, and the next valid request still works
    with NetClient(ms.addr) as c2:
        c2.sock.sendall(frames.encode_frame(
            {"kind": frames.REQ, "rid": 1, "q": "not an array",
             "k": 10, "n_probe": 8, "deadline_s": 1.0}, c2.codec))
        r = c2.recv_reply(10.0)
        assert r["kind"] == frames.ERR and r["code"] == "bad_request"
        c2.send_request(2, np.full(SPEC["d"], np.nan, np.float32), 10, 8,
                        1.0)                # non-finite embedding
        r = c2.recv_reply(10.0)
        assert r["kind"] == frames.ERR and r["code"] == "bad_request"
        c2.sock.sendall(frames.encode_frame(
            {"kind": frames.REQ, "rid": 3,
             "q": frames.pack_array(_rand_q(rng)), "k": "lots",
             "n_probe": 8, "deadline_s": 1.0}, c2.codec))
        r = c2.recv_reply(10.0)
        assert r["kind"] == frames.ERR and r["code"] == "bad_request"
        c2.sock.sendall(frames.encode_frame(
            {"kind": "totally_unknown"}, c2.codec))
        r = c2.recv_reply(10.0)
        assert r["kind"] == frames.ERR and r["code"] == "bad_kind"
        # same connection, valid request: full service
        c2.send_request(9, _rand_q(rng), 10, 8, 10.0)
        r = c2.recv_reply(30.0)
        assert r["kind"] == frames.RESP and r["rid"] == 9

    # an oversized frame announcement is rejected before buffering
    c3 = NetClient(ms.addr).connect()
    c3.send_raw((64 * 1024 * 1024).to_bytes(4, "big") + b"J")
    r = c3.recv_reply(10.0)
    assert r is not None and r["code"] == "bad_frame"
    c3.sock.close()

    # none of that killed a worker
    assert all(p.poll() is None for p in ms.procs.values())
    assert ms.core.stats["malformed"] >= 2


def test_live_record_replay_digest_identical(net):
    """Stop the serve loop, then replay the recorded transcript through a
    fresh core with the in-process twin engine: the outcome digest must be
    byte-identical, and every re-executed payload must reproduce the
    checksum the worker subprocess computed over the wire."""
    net.stop.set()
    net.thread.join(timeout=10.0)
    ms = net.ms
    live_digest = outcome_digest(ms.core.outcome_list())
    assert len(ms.core.outcomes) > 0
    tr = Transcript.loads(ms.transcript.dumps())    # full serialize cycle
    res = replay_transcript(tr, net.cfg, net.state.centroids, net.exec_fn)
    assert res.digest == live_digest
    assert res.checksum_mismatches == []
    assert res.core.stats["offered"] == ms.core.stats["offered"]
    assert res.core.stats["cache_hits"] == ms.core.stats["cache_hits"]
    assert res.core.stats["malformed"] == ms.core.stats["malformed"]


def test_live_worker_death_detection_and_respawn(tmp_path, monkeypatch):
    """REPRO_WORKER_EXIT_AFTER makes the worker die mid-request: the
    master must detect the death, respawn the worker, and complete the
    orphaned request on the fresh process — the client just sees a slower
    answer, never an error."""
    monkeypatch.setenv("REPRO_WORKER_EXIT_AFTER", "3")
    cfg = MasterConfig(
        n_workers=1, ceilings=k_ceilings(KS),
        retry=RetryPolicy(relative=True, timeout_mult=6.0, max_retries=3,
                          backoff_base=0.005, backoff_cap=0.1))
    ms = MasterServer(cfg, SPEC, run_dir=str(tmp_path))
    ms.start()
    assert ms.wait_workers(timeout=300.0)
    # the replacement worker must NOT inherit the suicide hook
    monkeypatch.delenv("REPRO_WORKER_EXIT_AFTER")
    stop = threading.Event()
    th = threading.Thread(target=lambda: ms.serve(until=stop.is_set),
                          daemon=True)
    th.start()
    try:
        rng = np.random.default_rng(3)
        with NetClient(ms.addr) as c:
            for rid in range(2):
                c.send_request(rid, _rand_q(rng), 10, 8, 30.0)
                r = c.recv_reply(30.0)
                assert r is not None and r["kind"] == frames.RESP \
                    and r["rid"] == rid
            # the 3rd served request kills the worker before it replies;
            # completion requires detect -> respawn -> re-dispatch, so the
            # deadline must cover a full engine rebuild
            c.send_request(2, _rand_q(rng), 100, 8, 120.0)
            r = c.recv_reply(120.0)
            assert r is not None and r["kind"] == frames.RESP \
                and r["rid"] == 2, r
        assert ms.core.stats["worker_lost"] >= 1
        assert ms.core.stats["respawns"] >= 1
        out = [o for o in ms.core.outcome_list() if o.request.k == 100]
        assert out and out[-1].completed
    finally:
        stop.set()
        th.join(timeout=10.0)
        ms.shutdown()


def test_worker_startup_failure_ends_the_wait(tmp_path):
    """A worker that dies before READY (here: an engine spec it cannot
    build) ends ``wait_workers`` at once instead of being respawned until
    the timeout."""
    class BadSpecMaster(MasterServer):
        def _worker_spec(self, wid):
            spec = super()._worker_spec(wid)
            spec["engine"] = dict(spec["engine"], n=-1)
            return spec

    cfg = MasterConfig(n_workers=1, ceilings=k_ceilings(KS))
    ms = BadSpecMaster(cfg, SPEC, run_dir=str(tmp_path))
    ms.start()
    try:
        t0 = time.monotonic()
        assert not ms.wait_workers(timeout=300.0)
        assert time.monotonic() - t0 < 120.0
    finally:
        ms.shutdown()


def test_sigterm_graceful_drain_subprocess():
    """`launch/serve.py --mode net --serve-forever` under SIGTERM: one
    request completes while up, the drain terminates every in-flight or
    newly-arriving request with a typed reply (RESP or RETRY_AFTER), the
    summary conserves all offered requests, and the exit code is 0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--mode", "net",
         "--workers", "1", "--n", "4096", "--d", "16", "--n-probe", "8",
         "--k-choices", "10,100", "--serve-forever"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    addr = None
    try:
        while True:
            line = proc.stdout.readline()
            if not line:
                break
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if obj.get("event") == "listening":
                addr = obj["addr"]
                break
        assert addr is not None, "server never announced its address"
        rng = np.random.default_rng(0)
        c = NetClient(addr, timeout=30.0).connect()
        c.send_request(0, _rand_q(rng), 10, 8, 10.0)
        r = c.recv_reply(30.0)
        assert r is not None and r["kind"] == frames.RESP and r["rid"] == 0
        # put several requests in flight, then SIGTERM while they travel
        inflight = list(range(1, 6))
        for rid in inflight:
            c.send_request(rid, _rand_q(rng), 100, 8, 10.0)
        # first reply back proves the batch was read and admitted (one
        # recv parses the whole back-to-back burst), so the drain below
        # must account for every one of them
        got, closed = {}, False
        r = c.recv_reply(30.0)
        assert r is not None
        got[r.get("rid")] = r
        proc.send_signal(signal.SIGTERM)
        probe_rid = 100
        end = time.monotonic() + 20.0
        while time.monotonic() < end and not closed and \
                not all(i in got for i in inflight):
            try:                        # new arrivals during the drain
                c.send_request(probe_rid, _rand_q(rng), 10, 8, 10.0)
                probe_rid += 1
            except OSError:
                closed = True
                break
            try:
                r = c.recv_reply(0.1)
            except ConnectionError:
                closed = True
                break
            if r is not None:
                got[r.get("rid")] = r
        # drain contract: every reply that came back is a typed terminal
        # frame — completed work or an explicit retriable rejection
        assert got or closed
        for rid, r in got.items():
            assert r["kind"] in (frames.RESP, frames.RETRY_AFTER), (rid, r)
        for rid in inflight:            # in-flight never silently dropped
            if rid in got:
                assert got[rid]["kind"] in (frames.RESP,
                                            frames.RETRY_AFTER)
        try:
            c.sock.close()
        except OSError:
            pass
        rc = proc.wait(timeout=120)
        assert rc == 0, f"serve.py exited {rc}"
        summary = None
        for line in proc.stdout:
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if "conserved" in obj:
                summary = obj
        assert summary is not None and summary["conserved"], summary
        assert summary["requests"] >= 1 + len(inflight)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
