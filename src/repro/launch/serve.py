"""Batched large-k retrieval serving driver (the paper's workload).

Builds a quantized ANN index over a corpus and serves large-k queries
through the batched fused-kernel search engine (``index.engine``): one
routing matmul per batch, one shared candidate-stream gather, batched
estimate/bucketize/re-rank kernels.  ``--batch 1`` falls back to the
single-query searchers.  ``examples/serve_retrieval.py`` wires an LM encoder
in front of this.

  PYTHONPATH=src python -m repro.launch.serve --n 100000 --d 96 --k 5000 \
      --method ivfpq_bbc --queries 64 --batch 32

``--shards N`` serves the same index mesh-sharded over N devices (the
distributed BBC collector: per-shard scan, histogram psum, survivor-only
all-gather).  On a CPU host without real accelerators the flag forces N
host devices so the collective path is exercised end-to-end:

  PYTHONPATH=src python -m repro.launch.serve --method ivfpq_bbc --shards 8

``--tau-pred on`` switches on predictive early-exact re-ranking: the loop
maintains a cross-batch threshold predictor (EMA over the bucket histograms
of previous batches) and threads it through every engine call, so the
re-rank pool shrinks from the static n_cand cut to the predicted threshold
with a correctness fallback (see index/engine.py and core/rerank.py).

``--mode async`` serves an asynchronous open-loop request stream through
the micro-batching subsystem (``repro.serving``): a seeded synthetic trace
(``--trace poisson|bursty`` at ``--rate`` req/s, per-request deadline
``--deadline-ms``, heterogeneous k via ``--k-choices``) flows through
admission control and deadline-aware batch assembly onto AOT-warmed
(B, k)-bucketed engines; ``--mode static`` is the fixed-batch loop above.

  PYTHONPATH=src python -m repro.launch.serve --mode async --rate 200 \
      --deadline-ms 500 --k-choices 1000,5000 --max-batch 16

``--mode net`` serves over REAL sockets: a master process (bounded
queues, 429-style backpressure, retries, health, the exact-key result
cache) in front of N worker subprocesses it spawns and supervises, each
hosting a spec-built engine behind a framed Unix/TCP socket loop
(``repro.transport``).  By default it drives a seeded Zipf trace through
a framed client and prints a summary; ``--serve-forever`` keeps serving
until SIGTERM/SIGINT, which triggers a graceful drain — in-flight
requests finish, new ones are rejected with retriable ``retry_after``
frames, workers get ``bye``, and the process exits 0.

  PYTHONPATH=src python -m repro.launch.serve --mode net --workers 4 \
      --n 20000 --d 32 --k-choices 10,100,1000 --rate 300 \
      --wire-faults 'drop=0.02,slow=0.1,seed=7' --record /tmp/run.jsonl

The last stdout line of either mode is one machine-readable JSON summary
(QPS, latency percentiles, shed/deadline rates, recall sample); with
``--check-parity`` the async mode also verifies every completed request's
ids against a direct engine call and exits non-zero on any mismatch.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _forced_shards() -> int:
    """Pre-jax-import peek at --shards: forcing host devices only works via
    XLA_FLAGS set before jax initializes its backends.  Malformed values
    fall through to 1 so argparse reports them properly later."""
    argv = sys.argv
    for i, a in enumerate(argv):
        val = None
        if a == "--shards" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--shards="):
            val = a.split("=", 1)[1]
        if val is not None:
            try:
                return int(val)
            except ValueError:
                return 1
    return 1


def _is_entrypoint() -> bool:
    """True when this module IS the serve entrypoint (``python -m`` or the
    ``repro-serve`` console script) — importing it for its helpers must not
    scan argv or rewrite the process environment."""
    return __name__ == "__main__" or \
        os.path.basename(sys.argv[0] or "").startswith("repro-serve")


if _is_entrypoint():
    _n_shards = _forced_shards()
    if _n_shards > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n_shards}").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import synthetic
from repro.index import engine, flat, search
from repro.launch import compile_cache


METHODS = ("ivfpq", "ivfpq_bbc", "ivfrabitq", "ivfrabitq_bbc", "flat")
RECALL_SAMPLE = 8   # queries with exact ground truth for the recall estimate


def build_index(method: str, x, n_clusters: int, seed: int = 0):
    key = jax.random.key(seed)
    if method.startswith("ivfpq"):
        return search.build_pq_index(key, x, n_clusters)
    if method.startswith("ivfrabitq"):
        return search.build_rabitq_index(key, x, n_clusters)
    return None


def mean_recall_entries(x, entries) -> float:
    """Mean recall over (query, ids, k) triples, against exact ground truth
    (per-entry k so heterogeneous-k serving outcomes average correctly)."""
    recalls = []
    for q, ids, k in entries:
        _, gt_i = flat.search(x, q, k)
        got = set(np.asarray(ids).tolist()) - {-1}
        recalls.append(len(got & set(np.asarray(gt_i).tolist())) / k)
    return float(np.mean(recalls)) if recalls else float("nan")


def sample_indices(n: int, n_sample: int) -> np.ndarray:
    """Evenly spaced sample over [0, n) that always includes the LAST index,
    so the recall estimate covers the ragged tail batch instead of weighting
    only the leading full batches."""
    return np.unique(np.linspace(0, max(n - 1, 0),
                                 min(n_sample, n)).round().astype(int))


def run_static(args, x, qs, index, mesh, n_probe, tuned=None):
    """The fixed-batch synchronous loop (PR 1-3 behavior)."""
    tau_pred_on = args.tau_pred == "on"
    operating_point = "flat"
    if args.method == "flat":
        if tau_pred_on:
            raise SystemExit("--tau-pred does not apply to the flat baseline")
        searcher = lambda q: flat.search(x, q, args.k)  # noqa: E731
        batch = 1
    else:
        if tau_pred_on and not args.method.endswith("bbc"):
            raise SystemExit("--tau-pred on requires a *_bbc method")
        # n_cand / pred_count resolve from the tuned operating point when
        # one covers this (method, k) cell, else the engine's hand
        # defaults (the pre-tuner formula n_cand = min(8k, n))
        eng = engine.SearchEngine.build(
            index, k=args.k, n_probe=n_probe,
            use_bbc=args.method.endswith("bbc"), mesh=mesh,
            pred_count=args.pred_count, tuned=tuned,
            recall_target=args.recall_target)
        from repro.tuning.points import HAND_TUNED
        operating_point = eng.tuned_from or HAND_TUNED
        if tau_pred_on:
            # the serving loop owns the predictor: every request folds its
            # batch histogram into the EMA that thresholds the next request
            pred_state = [eng.predictor_init()]

            def searcher(qb):
                r, pred_state[0] = eng.search(qb, pred_state=pred_state[0])
                return r
        else:
            searcher = eng.search
        batch = max(1, args.batch)

    batches = [qs[i:i + batch] for i in range(0, args.queries, batch)]
    if batch == 1:
        batches = [q for q in qs]

    # warmup / compile — the final batch may be ragged (queries % batch),
    # which is a distinct jit shape; compile it outside the timed loop too
    r = searcher(batches[0])
    jax.block_until_ready(r)
    if batch > 1 and batches[-1].shape[0] != batches[0].shape[0]:
        r = searcher(batches[-1])
        jax.block_until_ready(r)

    t0 = time.monotonic()
    results = []
    for qb in batches:
        r = searcher(qb)
        ids = r.ids if hasattr(r, "ids") else r[1]   # flat returns a pair
        results.append(ids if ids.ndim > 1 else ids[None])
    jax.block_until_ready(r)
    dt = time.monotonic() - t0
    qps = args.queries / dt

    # recall sample vs exact ground truth, evenly spaced over the WHOLE
    # query stream (always includes the last query, so the ragged tail
    # batch is covered instead of sampling only the leading full batches)
    all_ids = [row for ids in results for row in np.asarray(ids)]
    idx = sample_indices(args.queries, RECALL_SAMPLE)
    recall = mean_recall_entries(
        x, [(qs[i], all_ids[i], args.k) for i in idx])
    print(json.dumps({
        "mode": "static",
        "method": args.method, "k": args.k, "batch": batch,
        "shards": args.shards, "tau_pred": args.tau_pred,
        "operating_point": operating_point,
        "qps": round(qps, 2),
        "ms_per_query": round(1e3 * dt / args.queries, 2),
        "ms_per_batch": round(1e3 * dt / len(batches), 2),
        "recall_mean": round(recall, 4),
        "recall_queries": int(len(idx))}))
    return 0


def run_async(args, x, qs, index, mesh, n_probe, tuned=None):
    """The micro-batching event loop over ``repro.serving``."""
    from repro.serving import batcher as sv_batcher
    from repro.serving import queue as sv_queue
    from repro.serving import server as sv_server
    from repro.serving.state import ServingState

    if args.method == "flat":
        raise SystemExit("--mode async does not apply to the flat baseline")
    tau_pred_on = args.tau_pred == "on"
    if tau_pred_on and not args.method.endswith("bbc"):
        raise SystemExit("--tau-pred on requires a *_bbc method")
    if tau_pred_on and args.check_parity:
        raise SystemExit(
            "--check-parity compares against non-predictive direct calls; "
            "run it with --tau-pred off")

    ks = tuple(int(s) for s in args.k_choices.split(",")) \
        if args.k_choices else (args.k,)
    deadline = args.deadline_ms / 1e3
    trace = sv_queue.make_trace(
        np.random.default_rng(args.seed), np.asarray(qs), ks,
        rate=args.rate, deadline=deadline, n_probe=n_probe,
        pattern=args.trace, burst=args.burst,
        recall_target=args.recall_target)

    state = ServingState(
        index, use_bbc=args.method.endswith("bbc"), tau_pred=tau_pred_on,
        mesh=mesh, pred_count=args.pred_count, tuned=tuned)
    max_wait = args.max_wait_ms / 1e3 if args.max_wait_ms else None
    if args.replicas > 1:
        # fault-tolerant multi-replica tier: affinity routing, health
        # checks, retries/hedges, supervisor respawn (serving/router.py)
        from repro.serving import faults as sv_faults
        from repro.serving.router import (HedgePolicy, ReplicaServer,
                                          RetryPolicy, outcome_digest)
        schedule = sv_faults.FaultSchedule.parse(args.faults) \
            if args.faults else None
        # degrade along the tuned recall/cost frontier when the store
        # covers this method (lower recall target + narrower n_probe per
        # rung), instead of the blunt hand-picked k-caps
        ladder = None
        if tuned is not None:
            from repro.serving.admission import DegradeLadder
            frontier = tuned.frontier(state.kind, max(ks))
            if len(frontier) > 1:
                ladder = DegradeLadder.from_frontier(frontier)
        srv = ReplicaServer(
            state, args.replicas, ceilings=sv_batcher.k_ceilings(ks),
            batch=args.max_batch, ladder=ladder,
            retry=RetryPolicy(max_retries=args.retries),
            hedge=HedgePolicy(enabled=args.hedge == "on"),
            faults=schedule, max_wait=max_wait,
            hb_interval=args.hb_ms / 1e3,
            respawn_delay=args.respawn_ms / 1e3)
    elif args.faults:
        raise SystemExit("--faults requires --replicas > 1 (faults are "
                         "injected at the replica service boundary)")
    else:
        srv = sv_server.Server(
            state, ceilings=sv_batcher.k_ceilings(ks),
            batch=args.max_batch, admission=not args.no_admission,
            max_wait=max_wait)
    n_buckets = len({(min(r.k, max(ks)), r.n_probe) for r in trace})
    t0 = time.monotonic()
    srv.warmup(trace)
    print(f"[serve] warmed {n_buckets} shape buckets in "
          f"{time.monotonic()-t0:.1f}s", flush=True)
    outcomes = srv.run_trace(trace, warmup=False)

    # per-bucket knob provenance rides in the summary line: which tuned
    # operating point (or "hand-tuned fallback") served each bucket
    summary = sv_server.summarize(outcomes, state=state)
    if args.replicas > 1:
        summary.update({
            "replicas": args.replicas, "faults": args.faults or "",
            "outcome_digest": outcome_digest(outcomes),
            "fault_stats": dict(sorted(srv.stats.items())),
        })
    done = [o for o in outcomes if o.status != sv_server.SHED]
    idx = sample_indices(len(done), RECALL_SAMPLE)
    # None (json null), not NaN, when everything was shed — the summary
    # line must stay strictly parseable exactly when it reports a pathology
    recall = mean_recall_entries(
        x, [(jnp.asarray(done[i].request.q), done[i].ids,
             done[i].k_effective) for i in idx]) if done else None

    parity = n_checked = None
    if args.check_parity:
        parity, n_checked = sv_server.parity_vs_direct(state, outcomes)

    summary.update({
        "mode": "async", "method": args.method, "trace": args.trace,
        "rate": args.rate, "deadline_ms": args.deadline_ms,
        "k_choices": list(ks), "max_batch": args.max_batch,
        "shards": args.shards, "tau_pred": args.tau_pred,
        "recall_mean": round(recall, 4) if recall is not None else None,
        "recall_queries": int(len(idx)),
    })
    if parity is not None:
        summary["parity"] = round(parity, 4)
        summary["parity_checked"] = n_checked
    print(json.dumps(summary))
    # an all-shed run verified nothing: that's a parity FAILURE, not a pass
    return 1 if (parity is not None and (parity < 1.0 or n_checked == 0)) \
        else 0


def _parse_net_addr(spec: str):
    """'' -> driver default; 'unix:/path' -> Unix socket; 'host:port' ->
    TCP."""
    from repro.transport.master import tcp_addr, unix_addr
    if not spec:
        return None
    if spec.startswith("unix:"):
        return unix_addr(spec[len("unix:"):])
    host, _, port = spec.rpartition(":")
    try:
        return tcp_addr(host or "127.0.0.1", int(port))
    except ValueError:
        raise SystemExit(f"--addr {spec!r}: want 'unix:/path' or "
                         f"'host:port'")


def _device_census() -> tuple[str, int]:
    """(platform, device count) as a fresh process sees them.  A child
    answers, so this process initializes no backend: on a TPU host the
    first process to touch the chips holds them until it exits."""
    probe = ("import jax; ds = jax.devices(); "
             "print(ds[0].platform, len(ds))")
    out = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                         text=True, timeout=300, env=dict(os.environ))
    if out.returncode != 0:
        raise SystemExit(f"device probe failed:\n{out.stderr[-2000:]}")
    platform, count = out.stdout.split()[-2:]
    return platform, int(count)


def run_net(args):
    """The multi-process socket front-end (``repro.transport``)."""
    import signal
    import threading

    from repro.serving import faults as sv_faults
    from repro.serving import server as sv_server
    from repro.serving.batcher import k_ceilings
    from repro.serving.queue import make_zipf_trace
    from repro.serving.router import outcome_digest
    from repro.transport.client import NetClient
    from repro.transport.core import MasterConfig
    from repro.transport.enginehost import build_spec, make_dataset
    from repro.transport.master import MasterServer

    # one worker process per device; the master itself serves nothing and
    # stays on the CPU, so the workers can take the accelerators
    platform, n_devices = _device_census()
    if platform != "cpu" and args.workers > n_devices:
        raise SystemExit(
            f"--workers {args.workers}: this host has {n_devices} "
            f"{platform} device(s), and each worker process needs one of "
            f"its own")
    jax.config.update("jax_platforms", "cpu")

    ks = tuple(int(s) for s in args.k_choices.split(",")) \
        if args.k_choices else (args.k,)
    n_clusters = min(args.n_clusters, max(args.n // 64, 16))
    n_probe = min(args.n_probe, n_clusters)
    spec = build_spec(n=args.n, d=args.d, seed=args.seed, ks=ks,
                      n_probe=n_probe, n_clusters=n_clusters)
    wire = sv_faults.WireSchedule.parse(args.wire_faults) \
        if args.wire_faults else None
    cfg = MasterConfig(n_workers=args.workers, ceilings=k_ceilings(ks),
                       cache_size=args.net_cache,
                       hb_interval=args.hb_ms / 1e3)
    ms = MasterServer(cfg, spec, addr=_parse_net_addr(args.addr), wire=wire,
                      record=bool(args.record) or args.check_replay,
                      device_per_worker=platform == "tpu"
                      and args.workers > 1)
    t0 = time.monotonic()
    ms.start()
    if not ms.wait_workers(timeout=300.0):
        print(json.dumps({"error": "workers failed to come up"}))
        ms.shutdown()
        return 1
    print(f"[serve] {args.workers} workers ready in "
          f"{time.monotonic()-t0:.1f}s on {ms.addr}; devices "
          f"{json.dumps(ms.worker_devices, sort_keys=True)}", flush=True)

    want_drain = threading.Event()
    signal.signal(signal.SIGTERM, lambda s, f: want_drain.set())
    signal.signal(signal.SIGINT, lambda s, f: want_drain.set())

    records: dict[int, dict] = {}
    client_thread = None
    if not args.serve_forever:
        rng = np.random.default_rng(args.seed + 1)
        x = make_dataset(spec)
        pool = synthetic.queries_from(rng, x,
                                      max(args.requests // 8, 4))
        trace = make_zipf_trace(rng, pool, args.requests, ks,
                                rate=args.rate,
                                deadline=args.deadline_ms / 1e3,
                                n_probe=n_probe)

        def _drive():
            try:
                with NetClient(ms.addr) as c:
                    records.update(c.run_trace(trace))
            finally:
                want_drain.set()
        client_thread = threading.Thread(target=_drive, daemon=True)
        client_thread.start()
    else:
        print(json.dumps({"event": "listening", "addr": ms.addr}),
              flush=True)

    while not ms.stopped:
        if want_drain.is_set():
            ms.drain()
        if ms._drain_started is not None and (
                ms.core.idle() or ms.clock.now() - ms._drain_started
                > ms.drain_timeout):
            ms.shutdown()
            break
        ms.step()
    if client_thread is not None:
        client_thread.join(timeout=10.0)

    outcomes = ms.core.outcome_list()
    summary = sv_server.summarize(outcomes)
    summary.update({
        "mode": "net", "workers": args.workers,
        "k_choices": list(ks), "rate": args.rate,
        "wire_faults": args.wire_faults or "",
        "outcome_digest": outcome_digest(outcomes),
        "net_stats": {k: v for k, v in sorted(ms.core.stats.items()) if v},
        "cache": ms.core.cache_stats(),
    })
    if records:
        done = [r for r in records.values()
                if r["status"] in ("ok", "degraded")]
        summary["client_completed"] = len(done)
        lat = sorted(r["latency_s"] for r in done)
        if lat:
            summary["client_p99_ms"] = round(
                1e3 * lat[min(int(0.99 * len(lat)), len(lat) - 1)], 2)
    rc = 0
    if args.check_replay:
        from repro.transport.enginehost import (build_state_from_spec,
                                                make_exec_fn)
        from repro.transport.replay import replay_transcript
        state, ceil = build_state_from_spec(spec)
        res = replay_transcript(ms.transcript, cfg, state.centroids,
                                make_exec_fn(state, ceil))
        summary["replay_digest"] = res.digest
        summary["replay_identical"] = \
            res.digest == summary["outcome_digest"]
        if not summary["replay_identical"]:
            rc = 1
    if args.record:
        ms.transcript.save(args.record)
        summary["transcript"] = args.record
    print(json.dumps(summary))
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=96)
    ap.add_argument("--k", type=int, default=5_000)
    ap.add_argument("--method", choices=METHODS, default="ivfpq_bbc")
    ap.add_argument("--n-probe", type=int, default=64)
    ap.add_argument("--n-clusters", type=int, default=316)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--mode", choices=("static", "async", "net"),
                    default="static",
                    help="static = fixed-batch synchronous loop; async = "
                         "deadline-aware micro-batching over an open-loop "
                         "arrival trace (repro.serving); net = real "
                         "multi-process socket front-end "
                         "(repro.transport)")
    ap.add_argument("--batch", type=int, default=32,
                    help="[static] queries per engine call (1 = "
                         "single-query path)")
    ap.add_argument("--shards", type=int, default=1,
                    help="mesh-shard the corpus over this many devices "
                         "(forces host devices when none are present)")
    ap.add_argument("--tau-pred", choices=("on", "off"), default="off",
                    help="predictive early-exact re-ranking: the serving "
                         "loop maintains a cross-batch threshold predictor "
                         "(EMA over previous batches' bucket histograms) "
                         "and threads it through every engine call "
                         "(per shape bucket in --mode async)")
    ap.add_argument("--pred-count", type=int, default=None,
                    help="predictive re-rank pool target (default ~2.5k). "
                         "The pool is a subset of the static n_cand cut, so "
                         "on coarse-estimate indexes (paper-default M=d/4 "
                         "4-bit PQ) a shallow pool trades recall for fewer "
                         "re-ranks; raise toward n_cand to recover the "
                         "static selection")
    ap.add_argument("--tuned", type=str, default="auto",
                    help="tuned operating points: 'auto' loads "
                         "tuned_points.json from the repo root (or "
                         "$REPRO_TUNED_POINTS) when present, 'off' forces "
                         "the hand-tuned defaults, anything else is a path "
                         "to a point-store JSON.  The summary line reports "
                         "which operating point (or 'hand-tuned fallback') "
                         "served each bucket")
    ap.add_argument("--recall-target", type=float, default=0.95,
                    help="recall@k requirement: selects the tuned operating "
                         "point knobs resolve from, and stamps async-mode "
                         "requests (the DegradeLadder may lower it under "
                         "overload, serving a cheaper tuned point)")
    # -- async-mode knobs ---------------------------------------------------
    ap.add_argument("--trace", choices=("poisson", "bursty"),
                    default="poisson", help="[async] arrival pattern")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="[async] offered load, requests/s")
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="[async] per-request deadline after arrival")
    ap.add_argument("--k-choices", type=str, default="",
                    help="[async] comma-separated k values sampled per "
                         "request (default: just --k); the bucket ladder")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="[async] padded batch width B of the shape buckets")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="[async] cap on queueing wait before a partial "
                         "batch fires (default: deadline-slack only)")
    ap.add_argument("--burst", type=int, default=8,
                    help="[async] burst size for --trace bursty")
    ap.add_argument("--no-admission", action="store_true",
                    help="[async] disable admission control (serve "
                         "everything, deadlines may blow)")
    ap.add_argument("--check-parity", action="store_true",
                    help="[async] verify every completed request's ids "
                         "against a direct engine call; exit non-zero on "
                         "any mismatch")
    # -- multi-replica fault-tolerance knobs (async mode) ---------------------
    ap.add_argument("--replicas", type=int, default=1,
                    help="[async] replica pool size; > 1 routes through the "
                         "fault-tolerant tier (affinity routing, health "
                         "checks, retries, hedges, supervisor respawn)")
    ap.add_argument("--faults", type=str, default="",
                    help="[async] deterministic fault schedule, e.g. "
                         "'crash@1:t=0.5;stall@0:t=0.2,dur=0.1;"
                         "slow@2:t=0.0,dur=1.0,factor=4;corrupt@3:t=0.3,"
                         "dur=0.2' (requires --replicas > 1)")
    ap.add_argument("--retries", type=int, default=2,
                    help="[async] max retry attempts per request after a "
                         "timeout or corrupt response (--replicas > 1)")
    ap.add_argument("--hedge", choices=("on", "off"), default="on",
                    help="[async] hedged second sends when deadline slack "
                         "runs low; first response wins (--replicas > 1)")
    ap.add_argument("--hb-ms", type=float, default=20.0,
                    help="[async] replica heartbeat interval, ms "
                         "(--replicas > 1)")
    ap.add_argument("--respawn-ms", type=float, default=50.0,
                    help="[async] supervisor respawn delay after a replica "
                         "is marked DOWN, ms (--replicas > 1)")
    # -- net-mode knobs (--mode net) ------------------------------------------
    ap.add_argument("--workers", type=int, default=4,
                    help="[net] worker subprocesses to spawn and supervise")
    ap.add_argument("--net-cache", type=int, default=256,
                    help="[net] exact-key result cache capacity in the "
                         "master (0 = off)")
    ap.add_argument("--wire-faults", type=str, default="",
                    help="[net] seeded wire-fault schedule, e.g. "
                         "'drop=0.02,dup=0.01,slow=0.1,slow_ms=2:8,"
                         "disconnect=0.005,seed=7'")
    ap.add_argument("--record", type=str, default="",
                    help="[net] write the run's record/replay transcript "
                         "to this path")
    ap.add_argument("--check-replay", action="store_true",
                    help="[net] after the run, replay the transcript "
                         "in-process and exit non-zero unless the "
                         "outcome digest is byte-identical")
    ap.add_argument("--serve-forever", action="store_true",
                    help="[net] keep serving until SIGTERM/SIGINT, then "
                         "drain gracefully and exit 0")
    ap.add_argument("--addr", type=str, default="",
                    help="[net] listen address: 'unix:/path' or "
                         "'host:port' (default: a Unix socket in a "
                         "fresh run dir)")
    ap.add_argument("--requests", type=int, default=200,
                    help="[net] trace length for the built-in driver")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace/corpus RNG seed")
    args = ap.parse_args()
    compile_cache.enable()

    if args.mode == "net":
        sys.exit(run_net(args))

    mesh = None
    if args.shards > 1:
        if args.method == "flat":
            raise SystemExit("--shards does not apply to the flat baseline")
        if len(jax.devices()) < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs {args.shards} devices, have "
                f"{len(jax.devices())} (is XLA_FLAGS already set?)")
        mesh = jax.make_mesh((args.shards,), ("model",))

    n_probe = min(args.n_probe, args.n_clusters)
    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(synthetic.clustered(rng, args.n, args.d))
    qs = jnp.asarray(synthetic.queries_from(rng, np.asarray(x), args.queries))

    t0 = time.monotonic()
    index = build_index(args.method, x, args.n_clusters)
    print(f"[serve] index built in {time.monotonic()-t0:.1f}s", flush=True)

    tuned = None
    if args.tuned != "off":
        from repro.tuning.points import PointStore
        store = PointStore.load(None if args.tuned == "auto" else args.tuned)
        if args.tuned != "auto" and not len(store):
            raise SystemExit(f"--tuned {args.tuned}: no usable point store")
        tuned = store if len(store) else None

    run = run_async if args.mode == "async" else run_static
    sys.exit(run(args, x, qs, index, mesh, n_probe, tuned=tuned))


if __name__ == "__main__":
    main()
