"""Mesh-sharded engine QPS: distributed BBC collector vs naive top-k
all-gather, on a forced 8-host-device ("model",) mesh.

The BBC collective moves (m+1)*4 bytes of histogram per query (psum) plus a
budgeted survivor gather; the naive distributed top-k all-gathers k (dist,
id) pairs per shard per query.  ``collective_cost_model`` prices both for
the roofline table; the measured QPS compares the two collectors end-to-end
through ``SearchEngine(mesh=...)`` (same index, same routing, same scan —
the collector is the only difference).  Since the fused
shard-scan->histogram->compaction pipeline (kernels/shard_collect.py +
the speculative three-tier survivor selection) the BBC path must WIN this
measured comparison for every method at every k row — that is the
acceptance gate, not just the modeled wire bytes.

Rows run at k=5000 and the large-k extreme (k=100000, clamped to the
corpus size when it exceeds it — at the default 60k corpus the second row
exercises the k ~= N regime where the collector dominates end-to-end
cost).  Each k also records a per-stage breakdown at the executed
per-shard shapes (scan / collect / legacy compaction / collective /
re-rank / final-select) and a depth-1 pipelined QPS measurement — the
double-buffered host loop (dispatch batch j+1 while batch j runs) the
serving tier uses (``Server(overlap=True)``).

CPU-container caveat: the 8 "devices" here are host threads on one CPU, so
absolute QPS understates a real pod and the interconnect term is emulated
shared-memory copies — the wire-byte ratio from the cost model is the
hardware-independent claim; measured QPS shows the BBC path no longer pays
for its smaller payload with serving throughput.

Writes ``BENCH_shard_qps.json`` (override with REPRO_BENCH_OUT).
"""
from __future__ import annotations

import os

N_SHARDS = int(os.environ.get("REPRO_BENCH_SHARDS", 8))
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_SHARDS}")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks import common
from repro.core import buffer as rb
from repro.core import distributed as dist
from repro.data import synthetic
from repro.index import engine
from repro.kernels import ops
from repro.tuning import knobs as tn_knobs
from repro.tuning import points as tn_points

B = int(os.environ.get("REPRO_BENCH_B", 32))
KS = tuple(int(s) for s in
           os.environ.get("REPRO_BENCH_KS", "5000,100000").split(","))
N_PROBE = int(os.environ.get("REPRO_BENCH_NPROBE", 64))
M = 128
COST_MODEL_KS = (1000, 5000, 20000, 100000)
PIPE_DEPTH = 4   # batches in flight for the pipelined-QPS measurement


def _time_batch(fn, qs, repeats: int = 5):
    """(min wall seconds over ``repeats``, last result) post-compile.

    Min, not median: on the single-core emulated mesh every shard's compute
    serializes onto one CPU, so any stray host activity inflates a repeat
    by whole scheduler quanta.  The minimum is the reproducible compute
    floor; medians of 3 flipped ~5%-margin comparisons run to run."""
    r = fn(qs)
    jax.block_until_ready(r)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = fn(qs)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts)), r


def _time_pipelined(fn, qs, depth: int = PIPE_DEPTH, repeats: int = 5):
    """Min wall seconds per batch with a depth-1 double buffer: dispatch
    batch j+1 while batch j still occupies the executor (jax dispatch is
    async), block on each result one step late — the serving loop's
    ``Server(overlap=True)`` pattern as a raw engine measurement."""
    jax.block_until_ready(fn(qs))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        prev = None
        for _j in range(depth):
            r = fn(qs)
            if prev is not None:
                jax.block_until_ready(prev)
            prev = r
        jax.block_until_ready(prev)
        ts.append((time.perf_counter() - t0) / depth)
    return float(np.min(ts))


def _overlap(ids_a: np.ndarray, ids_b: np.ndarray) -> float:
    """Mean per-query id-set overlap, normalized by the NAIVE collector's
    returned set size (-1 pad lanes dropped) — at k ~= N both collectors
    legitimately return fewer than k ids (only probed lanes exist), so
    dividing by k would punish the regime instead of the collector."""
    fr = []
    for i in range(ids_a.shape[0]):
        sa = set(ids_a[i].tolist()) - {-1}
        sb = set(ids_b[i].tolist()) - {-1}
        fr.append(len(sa & sb) / max(len(sb), 1))
    return float(np.mean(fr))


# -------------------------------------------------------------------------
# Per-stage breakdown at the executed per-shard shapes
# -------------------------------------------------------------------------

def _median_ms(fn, *args, repeats: int = 3) -> float:
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return round(1e3 * float(np.median(ts)), 3)


def _stage_breakdown(mesh, b: int, k: int, shard_flat: int, bud: int,
                     d: int, m: int = M) -> dict:
    """Isolated per-stage costs at this row's per-shard shapes: one shard's
    scan and collect, the legacy full-stream top_k compaction it replaced,
    the psum+gather collective on the emulated mesh, the budget-width
    re-rank, and the replicated final selection over the gathered pool."""
    rng = np.random.default_rng(3)
    vecs = jnp.asarray(rng.standard_normal((shard_flat, d)), jnp.float32)
    qs = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    valid = jnp.asarray(rng.random((b, shard_flat)) < 0.3)
    dists = jnp.where(
        valid, jnp.asarray(rng.random((b, shard_flat)) * 9 + 1, jnp.float32),
        jnp.inf)
    k_cb = max(8, min(shard_flat // 2, 4096))
    cbs = jax.vmap(lambda s: rb.build_codebook(s, k=k_cb, m=m))(dists)
    tau_spec = jnp.full((b,), m // 2, jnp.int32)
    pos = jnp.asarray(rng.integers(0, shard_flat, (b, bud)), jnp.int32)
    hist = jnp.asarray(rng.integers(0, 50, (b, m + 1)), jnp.int32)
    surv = jnp.asarray(rng.standard_normal((b, bud)), jnp.float32)
    w = N_SHARDS * bud
    pool = jnp.asarray(rng.standard_normal((b, w)), jnp.float32)

    scan = jax.jit(lambda v, q: ops.l2_exact_batch(v, q))

    def collect():
        return ops.shard_collect_batch(dists, valid, cbs.d_min, cbs.delta,
                                       cbs.ew_map, m, tau_spec, bud)

    legacy = jax.jit(lambda x: jax.lax.top_k(-x, min(bud, shard_flat)))

    def _coll_body(h, s):
        gh = dist.hier_psum(h[0], "model")
        (g,) = dist.gather_survivors("model", s[0])
        return gh, g

    coll = jax.jit(jax.shard_map(
        _coll_body, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None, None)),
        out_specs=(P(), P()), check_vma=False))
    h_sh = jnp.broadcast_to(hist, (N_SHARDS, b, m + 1))
    s_sh = jnp.broadcast_to(surv, (N_SHARDS, b, bud))

    def _rerank(p, q):
        g = vecs[p]
        return jnp.sum((g - q[:, None, :]) ** 2, axis=-1)

    rerank = jax.jit(_rerank)
    final = jax.jit(lambda x: jax.lax.top_k(-x, min(k, w)))

    return {
        "shard_flat": shard_flat, "budget": bud, "B": b,
        "scan_ms": _median_ms(scan, vecs, qs),
        "collect_ms": _median_ms(collect),
        "legacy_compact_topk_ms": _median_ms(legacy, dists),
        "collective_ms": _median_ms(coll, h_sh, s_sh),
        "rerank_ms": _median_ms(rerank, pos, qs),
        "final_select_ms": _median_ms(final, pool),
    }


def _resolve_cell(store, fp, method: str, k: int):
    """(point, provenance) for this bench's (method, k) cell — tuned only
    on an EXACT corpus-fingerprint match (a pool/budget tuned on another
    corpus is a prior, not a contract the overlap gate should ride on);
    anything else is the documented hand-tuned fallback."""
    point, provenance = store.resolve(method, k, corpus_fp=fp)
    if point is None or provenance != "tuned":
        return None, tn_points.HAND_TUNED
    return point, f"{point.name} (tuned)"


def run(b: int = B, ks=KS, n_probe: int = N_PROBE):
    mesh = jax.make_mesh((N_SHARDS,), ("model",))
    x, _ = common.corpus()
    rng = np.random.default_rng(7)
    qs = jnp.asarray(synthetic.queries_from(rng, np.asarray(x), b))
    store = tn_points.PointStore.load()
    corpus_fp = tn_points.corpus_fingerprint(np.asarray(x))

    pq_index = common.pq_index()
    rq_index = common.rq_index()
    indexes = {
        "ivf": (pq_index.ivf, dict(vectors=x)),
        "ivfpq": (pq_index, {}),
        "ivfrabitq": (rq_index, {}),
    }

    results, breakdowns = [], []
    shard_flat = None
    for k_req in ks:
        # clamp to the corpus: k rows beyond N would select everything
        # anyway, and top_k needs k <= pool width.  k == N is the honest
        # large-k extreme this corpus supports.
        k = min(k_req, common.N)
        # Pools and survivor budgets resolve through the constrained tuner's
        # operating points (tuning/: slack constants documented per method,
        # budget <= stream clamp applied in knobs.shard_budget).  The
        # hand-tuned fallback keeps the pre-tuner sizing: an n_cand pool of
        # 4k (2k starved the collector at k=5000/8 shards — overlap 0.8459
        # — and 8k overshoots the probed mass, going cut-vacuous), slacks
        # {ivf: 2.0, ivfpq: 1.25, ivfrabitq: 4.0} over the balanced share.
        # The overlap gate below catches any sizing that actually starves
        # the collector, tuned or hand-picked.
        method_pools, method_budgets, method_points = {}, {}, {}
        for method in indexes:
            point, provenance = _resolve_cell(store, corpus_fp, method, k)
            n_cand = None
            slack = None
            if method == "ivfpq":
                n_cand = min(4 * k, common.N)
                if point is not None and point.knobs.n_cand is not None:
                    n_cand = max(k, min(point.knobs.n_cand, common.N))
            if point is not None:
                slack = point.knobs.budget_slack
            method_pools[method] = n_cand
            method_budgets[method] = tn_knobs.shard_budget(
                method, k, n_cand, N_SHARDS, slack=slack)
            method_points[method] = provenance
        for method, (index, extra) in indexes.items():
            n_cand = method_pools[method]
            row = {"method": method, "B": b, "k": k, "k_requested": k_req,
                   "n_probe": n_probe, "n_shards": N_SHARDS,
                   "operating_point": method_points[method]}
            ids = {}
            for collector, use_bbc in (("bbc", True), ("naive", False)):
                # the recorded budget is the executed one: passed
                # explicitly, not re-derived, so the JSON cannot drift from
                # the engine's internal defaults
                kw = dict(extra)
                if method == "ivfpq":
                    kw["n_cand"] = n_cand
                eng = engine.SearchEngine.build(
                    index, k=k, n_probe=n_probe, use_bbc=use_bbc, mesh=mesh,
                    shard_budget=method_budgets[method], **kw)
                shard_flat = eng.shard_streams[-1].shape[1]
                t, r = _time_batch(eng.search, qs)
                ids[collector] = np.asarray(r.ids)
                row[f"qps_{collector}"] = round(b / t, 2)
                row[f"ms_per_batch_{collector}"] = round(1e3 * t, 2)
                if use_bbc:
                    row["qps_bbc_pipelined"] = round(
                        b / _time_pipelined(eng.search, qs), 2)
                common.emit(
                    f"shard_qps/{method}/{collector}/S{N_SHARDS}/B{b}/k{k}",
                    t / b * 1e6, f"qps={b / t:.2f}")
            # collector-overlap acceptance signal: the BBC pool must
            # produce (nearly) the same top-k as the naive all-gather
            # collector — a low overlap means the pool/budget is starving
            # the collector, not a legitimate speed/accuracy trade
            row["survivor_budget"] = method_budgets[method]
            row["topk_overlap_bbc_vs_naive"] = round(
                _overlap(ids["bbc"], ids["naive"]), 4)
            row["qps_win"] = bool(row["qps_bbc"] >= row["qps_naive"])
            results.append(row)
        bud_iv = max(8, min(method_budgets["ivf"], shard_flat))
        bd = _stage_breakdown(mesh, b, k, shard_flat, bud_iv, common.D)
        bd["k"] = k
        breakdowns.append(bd)

    cost_model = []
    for ck in COST_MODEL_KS:
        cm = dist.collective_cost_model(k=ck, m=M, n_shards=N_SHARDS,
                                        n_hosts=2)
        cm["k"] = ck
        cost_model.append(cm)

    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_shard_qps.json")
    min_overlap = min(r["topk_overlap_bbc_vs_naive"] for r in results)
    qps_all_win = all(r["qps_win"] for r in results)
    payload = {
        "bench": "shard_qps",
        "corpus": {"n": common.N, "d": common.D},
        "config": {"B": b, "ks": list(ks), "n_probe": n_probe, "m": M,
                   "n_shards": N_SHARDS, "pipeline_depth": PIPE_DEPTH},
        "platform": jax.devices()[0].platform,
        "results": results,
        "stage_breakdown": breakdowns,
        "collective_cost_model": cost_model,
        "acceptance": {
            "claim": "sharded BBC beats the naive distributed top-k on "
                     "MEASURED QPS for every method at every k row (fused "
                     "scan->histogram->compaction pipeline), at >= 0.95 "
                     "top-k overlap with the naive collector, and moves "
                     "fewer modeled bytes per link at k >= 5000",
            "qps_all_win": qps_all_win,
            "min_topk_overlap": min_overlap,
            "overlap_target": 0.95,
            "pass": qps_all_win and min_overlap >= 0.95 and all(
                c["bbc_bytes_per_link"] < c["naive_bytes_per_link"]
                for c in cost_model if c["k"] >= 5000),
        },
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return payload


if __name__ == "__main__":
    payload = run()
    acc = payload["acceptance"]
    # REPRO_SHARD_STRICT=1 gates the collector-correctness half (top-k
    # overlap + modeled bytes) at ANY size; REPRO_SHARD_STRICT_QPS=1
    # additionally gates the measured-QPS win — meaningful only at sizes
    # where the per-query work dwarfs the BBC path's fixed overheads
    # (codebook build, sample threshold), i.e. the CI smoke sizes and up.
    bytes_ok = all(c["bbc_bytes_per_link"] < c["naive_bytes_per_link"]
                   for c in payload["collective_cost_model"] if c["k"] >= 5000)
    if os.environ.get("REPRO_SHARD_STRICT") == "1" \
            and (acc["min_topk_overlap"] < acc["overlap_target"]
                 or not bytes_ok):
        raise SystemExit(f"bench_shard_qps overlap/bytes gate failed: "
                         f"{json.dumps(acc, indent=2)}")
    if os.environ.get("REPRO_SHARD_STRICT_QPS") == "1" \
            and not acc["qps_all_win"]:
        rows = [(r["method"], r["k"], r["qps_bbc"], r["qps_naive"])
                for r in payload["results"] if not r["qps_win"]]
        raise SystemExit(f"bench_shard_qps QPS gate regressed "
                         f"(method, k, qps_bbc, qps_naive): {rows}")
