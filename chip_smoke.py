#!/usr/bin/env python3
"""Smoke test of the served large-k search path on TPU.

    python chip_smoke.py             # one chip: the serving path
    python chip_smoke.py --chips 4   # the mesh-sharded engine on four chips

The corpus has the deployment shape of ann-benchmarks' sift-128-euclidean
(SIFT1M): 1,000,000 x 128 f32 vectors, squared L2, queries drawn from the
corpus distribution.  It is generated from ``--seed``
(``data.synthetic.clustered`` / ``queries_from``); nothing is downloaded.

One chip: builds IVF+PQ and IVF+RaBitQ, both with the BBC collector, with
the knobs below stated explicitly, and answers a seeded request trace at
k in {5000, 100000} the way ``python -m repro.launch.serve --mode async``
does: ``SearchEngine`` -> ``serving.ServingState`` -> the micro-batching
``Server``.  Per method and k it prints recall@k against ``flat.search``,
id-set parity of every request against a direct engine call, and the time
per request; it checks that the search programs hold the Pallas kernels
(``tpu_custom_call``), and prints set-up time, compile time and peak HBM.

``--chips 4`` runs only the mesh-sharded engine (a ("model",) mesh over
four chips) against the one-chip engine, for both methods, over the same
corpus: id-set overlap, recall against ``flat.search``, and the placement
of every shard stream on its own chip.

Any failed check or phase exits non-zero without the final line.  The last
line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Config:
    """One smoke deployment; the defaults are the SIFT1M-shaped one."""
    n: int = 1_000_000
    d: int = 128
    n_clusters: int = 1000            # ~sqrt(n), 1000 rows per list
    n_probe: int = 300                # probed lanes >> the largest k
    ks: tuple = (5000, 100_000)
    # IVF+PQ (4-bit, M = d/4) estimate cut per k; RaBitQ's bounds size its
    # own re-rank band
    n_cand: dict = field(default_factory=lambda: {5000: 80_000,
                                                  100_000: 400_000})
    m: int = 128                      # BBC buckets
    # padded batch of every shape bucket: serve.py's default --max-batch,
    # twice the kernels' query chunk, so parity compares a query served in
    # a 16-wide batch with the same query in a direct singleton call
    max_batch: int = 16
    requests: int = 48                # per method
    recall_queries: int = 8           # per (method, k)
    mesh_ks: tuple = (5000, 100_000)
    seed: int = 0


MIN_RECALL = 0.9
MIN_MESH_OVERLAP = 0.99


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    """A check of the smoke failed: the run exits non-zero."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# set-up shared by both modes
# --------------------------------------------------------------------------

def make_corpus(cfg: Config):
    import jax.numpy as jnp
    import numpy as np

    from repro.data import synthetic
    rng = np.random.default_rng(cfg.seed)
    x = synthetic.clustered(rng, cfg.n, cfg.d)
    qs = synthetic.queries_from(rng, x, cfg.requests)
    return jnp.asarray(x), qs


def build_indexes(cfg: Config, x) -> dict:
    import jax

    from repro.index import search
    key = jax.random.key(cfg.seed)
    out = {}
    for kind, build in (("ivfpq", search.build_pq_index),
                        ("ivfrabitq", search.build_rabitq_index)):
        t0 = time.perf_counter()
        index = build(key, x, cfg.n_clusters)
        jax.block_until_ready(index)
        log(f"{kind}: index built in {time.perf_counter() - t0:.3f} s")
        out[kind] = index
    return out


def knob_store(cfg: Config):
    """The explicit knobs as operating points, the form serving takes them
    in (nothing is read from tuned_points.json)."""
    from repro.tuning.knobs import KnobConfig
    from repro.tuning.points import OperatingPoint, PointStore
    points = []
    for kind in ("ivfpq", "ivfrabitq"):
        for k in cfg.ks:
            n_cand = cfg.n_cand[k] if kind == "ivfpq" else None
            points.append(OperatingPoint(
                method=kind, k=k, recall_target=MIN_RECALL,
                knobs=KnobConfig(n_probe=cfg.n_probe, n_cand=n_cand),
                recall=float("nan"), cost_units=0.0, feasible=True,
                corpus={"fingerprint": "chip_smoke"}))
    return PointStore(points)


def recall_at_k(x, q, ids, k: int, truth: dict, key) -> float:
    """recall@k of ``ids`` against ``flat.search``; ``truth`` caches the
    exact top-k under ``key`` so every method is scored on one reference."""
    import numpy as np

    from repro.index import flat
    if key not in truth:
        truth[key] = set(np.asarray(flat.search(x, q, k)[1]).tolist())
    got = set(np.asarray(ids).tolist()) - {-1}
    return len(got & truth[key]) / k


def searcher_text(eng, qs) -> str:
    """Compiled text of the batched searcher an engine call runs (the jitted
    function the engine's strategy calls, caught on its way in)."""
    from repro.index import search
    name = {"ivfpq": "ivf_pq_search_batch",
            "ivfrabitq": "ivf_rabitq_search_batch"}[eng.kind]
    fn = getattr(search, name)
    seen = {}

    def spy(*args, **kwargs):
        seen["text"] = fn.lower(*args, **kwargs).compile().as_text()
        return fn(*args, **kwargs)

    setattr(search, name, spy)
    try:
        eng.search_batch(qs)
    finally:
        setattr(search, name, fn)
    return seen["text"]


# --------------------------------------------------------------------------
# one chip: the serving path
# --------------------------------------------------------------------------

def serve_smoke(cfg: Config, backend: str | None = None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, platform
    from repro.serving import batcher as sv_batcher
    from repro.serving import queue as sv_queue
    from repro.serving import server as sv_server
    from repro.serving.state import ServingState

    t_setup = time.perf_counter()
    x, qs = make_corpus(cfg)
    log(f"corpus {cfg.n} x {cfg.d} f32 in "
        f"{time.perf_counter() - t_setup:.3f} s")
    indexes = build_indexes(cfg, x)
    store = knob_store(cfg)
    ceilings = sv_batcher.k_ceilings(cfg.ks)
    trace = sv_queue.make_trace(
        np.random.default_rng(cfg.seed + 1), qs, cfg.ks, rate=200.0,
        deadline=60.0, n_probe=cfg.n_probe)
    buckets = [sv_batcher.ShapeBucket(k=k, batch=cfg.max_batch,
                                      n_probe=cfg.n_probe) for k in ceilings]
    states = {}
    for kind, index in indexes.items():
        state = ServingState(index, use_bbc=True, m=cfg.m, backend=backend,
                             tuned=store)
        for bucket in buckets:
            eng = state.engine(bucket)
            log(f"{kind} k={bucket.k}: n_probe={eng.n_probe} "
                f"n_cand={eng.n_cand} m={eng.m} fused={eng.fused} "
                f"backend={eng.backend}")
        states[kind] = state
    log(f"set-up (corpus, indexes, engines) "
        f"{time.perf_counter() - t_setup:.3f} s")

    truth: dict = {}
    for kind, state in states.items():
        srv = sv_server.Server(state, ceilings=ceilings, batch=cfg.max_batch,
                               admission=False, max_wait=0.05)
        t0 = time.perf_counter()
        srv.warmup(trace)
        log(f"{kind}: compile + warm-up of {len(buckets)} shape buckets "
            f"{time.perf_counter() - t0:.3f} s")
        if ops.resolve_backend(backend) == "pallas" \
                and not platform.default_interpret():
            text = searcher_text(state.engine(buckets[0]),
                                 jnp.asarray(qs[:cfg.max_batch]))
            check("tpu_custom_call" in text,
                  f"{kind}: the search program holds no Pallas kernel")
            log(f"{kind}: search program holds "
                f"{text.count('tpu_custom_call')} tpu_custom_call sites")
        t0 = time.perf_counter()
        outcomes = srv.run_trace(trace, warmup=False)
        wall = time.perf_counter() - t0
        done = [o for o in outcomes if o.completed]
        check(len(done) == len(trace),
              f"{kind}: {len(trace) - len(done)} requests not served")
        log(f"{kind}: served {len(done)} requests in {wall:.3f} s")
        for bucket in buckets:
            k = bucket.k
            outs = [o for o in done if o.bucket == bucket]
            check(len(outs) >= cfg.recall_queries,
                  f"{kind} k={k}: only {len(outs)} requests")
            parity, n_checked = sv_server.parity_vs_direct(state, outs)
            recalls = [recall_at_k(x, jnp.asarray(o.request.q), o.ids, k,
                                   truth, (o.request.rid, k))
                       for o in outs[:cfg.recall_queries]]
            batch = sv_batcher.assemble(bucket, [o.request for o in
                                                 outs[:bucket.batch]])
            t0 = time.perf_counter()
            res = state.run(batch)
            jax.block_until_ready((res.dists, res.ids))
            ms = 1e3 * (time.perf_counter() - t0) / bucket.batch
            lat = 1e3 * float(np.mean([o.latency for o in outs]))
            recall = float(np.mean(recalls))
            log(f"{kind} k={k}: recall@k {recall:.4f} over {len(recalls)} "
                f"queries (min {min(recalls):.4f}), parity {parity:.4f} "
                f"over {n_checked} requests, {ms:.3f} ms per request at "
                f"B={bucket.batch}, mean served latency {lat:.3f} ms, "
                f"exact re-ranks per query "
                f"{float(np.mean(res.n_reranked)):.0f} (second pass "
                f"{float(np.mean(res.n_second_pass)):.0f})")
            check(n_checked == len(outs) and parity == 1.0,
                  f"{kind} k={k}: parity {parity} over {n_checked}")
            check(recall >= MIN_RECALL,
                  f"{kind} k={k}: recall@k {recall:.4f} < {MIN_RECALL}")


# --------------------------------------------------------------------------
# four chips: the mesh-sharded engine against the one-chip engine
# --------------------------------------------------------------------------

def mesh_smoke(cfg: Config, mesh, backend: str | None = None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.index import engine

    n_shards = mesh.devices.size
    t_setup = time.perf_counter()
    x, qs = make_corpus(cfg)
    indexes = build_indexes(cfg, x)
    log(f"set-up (corpus, indexes) {time.perf_counter() - t_setup:.3f} s")
    qb = jnp.asarray(qs[:cfg.max_batch])
    truth: dict = {}
    for kind, index in indexes.items():
        for k in cfg.mesh_ks:
            n_cand = cfg.n_cand[k] if kind == "ivfpq" else None
            knobs = dict(k=k, n_probe=cfg.n_probe, n_cand=n_cand,
                         use_bbc=True, m=cfg.m, backend=backend)
            # RaBitQ's survivors are every lane whose lower bound reaches
            # the k-th upper bound's bucket: far more than the default
            # budget (4k / shards) at this width, so each shard keeps all
            # of its lanes (the budget clamps to the shard's stream)
            budget = cfg.n if kind == "ivfrabitq" else None
            t0 = time.perf_counter()
            one = engine.SearchEngine.build(index, **knobs)
            sharded = engine.SearchEngine.build(index, mesh=mesh,
                                                shard_budget=budget, **knobs)
            for arr in sharded.shard_streams:
                devs = [s.device for s in arr.addressable_shards]
                check(len(set(devs)) == n_shards
                      and all(s.data.shape[0] == arr.shape[0] // n_shards
                              for s in arr.addressable_shards),
                      f"{kind}: a shard stream is not split one shard per "
                      f"device: {devs}")
            r1 = one.search_batch(qb)
            rs = sharded.search_batch(qb)
            jax.block_until_ready((r1.ids, rs.ids))
            log(f"{kind} k={k}: engines built and compiled in "
                f"{time.perf_counter() - t0:.3f} s; shard streams on "
                f"{n_shards} distinct devices")
            ids1, idss = np.asarray(r1.ids), np.asarray(rs.ids)
            overlaps = [len(set(a.tolist()) & set(b.tolist()) - {-1}) / k
                        for a, b in zip(ids1, idss)]
            recalls = [recall_at_k(x, qb[i], idss[i], k, truth, (i, k))
                       for i in range(min(cfg.recall_queries, len(qb)))]
            t0 = time.perf_counter()
            jax.block_until_ready(sharded.search_batch(qb).ids)
            ms = 1e3 * (time.perf_counter() - t0) / qb.shape[0]
            log(f"{kind} k={k}: id-set overlap with one chip "
                f"{min(overlaps):.4f} (min over {len(overlaps)} queries), "
                f"recall@k {np.mean(recalls):.4f}, {ms:.3f} ms per query "
                f"at B={qb.shape[0]} on {n_shards} chips")
            check(min(overlaps) >= MIN_MESH_OVERLAP,
                  f"{kind} k={k}: overlap {min(overlaps):.4f}")
            check(float(np.mean(recalls)) >= MIN_RECALL,
                  f"{kind} k={k}: recall {np.mean(recalls):.4f}")


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: only the "
                         "mesh-sharded engine against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] no repro package under {src}: run this script from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)} compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print(f"[smoke] JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.kernels import ops, platform
    backend = ops.resolve_backend(None)
    log(f"kernel backend={backend} interpret={platform.default_interpret()}")
    cfg = Config(seed=args.seed)
    try:
        check(backend == "pallas" and not platform.default_interpret(),
              "the kernels would not run as compiled Pallas on this device")
        if args.chips == 4:
            check(len(devices) >= 4, f"--chips 4 needs 4 devices, have "
                                     f"{len(devices)}")
            mesh = jax.make_mesh((4,), ("model",), devices=devices[:4])
            mesh_smoke(cfg, mesh)
            count = 4
        else:
            serve_smoke(cfg)
            count = len(devices)
        stats = dev.memory_stats() or {}
        log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"(device 0, of bytes_limit={stats.get('bytes_limit')})")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
