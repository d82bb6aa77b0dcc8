"""One run of one benchmark cell: set-up, the measured window, the checks.

A cell is a configuration (``configs/<name>.json``: corpus, index and the
guarantees it states) under a traffic mix (``workloads/<name>.json``),
both named by the cell's entry in ``BENCHMARK.json``.  Per-layer metrics
are readers ``metrics/<name>.py``, each with ``read(ctx) -> float | None``.
Adding a configuration, a traffic mix or a metric adds files; nothing here
names one.

The window drives the program's own serving loop: ``serving.Server``
(admission off) over ``ServingState`` -> ``SearchEngine.search_batch`` ->
the kernels.  Traffic is a closed loop: one caller submits a block of
``clients`` queries, all arriving at once, as one ``run_trace`` call, and
waits for the whole block before it submits the next.  A request's latency
is the wall-clock time of its block's call.  Blocks cycle through a query
pool made in set-up, so nothing but the calls runs in the window.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WARM_BLOCKS = 2
KEEP_MIN = 1000


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------

def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> SimpleNamespace:
    """The cell's entry, configuration, traffic and metric entries."""
    spec = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def applies(m):
        return name in m.get("workloads", [name])
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        config=_load_json(os.path.join(ROOT, cfg["file"])),
        traffic=_load_json(os.path.join(BENCH, "workloads",
                                        cell["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def check_device(chips: int):
    """The first device, the peaks of its kind; NoChip without a TPU."""
    import jax
    from roofline import load_peaks
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU: platform {dev.platform!r}, device "
                     f"{dev.device_kind!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)} ({dev.device_kind!r})")
    return dev, load_peaks(dev.device_kind)


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, word)
    return key


def make_data(config: dict, seed: int):
    """Corpus (n, d) and query pool (pool, d), f32, on the device: the
    ``clustered`` / ``queries_from`` recipes in one jitted call.  The
    corpus is the configuration's fixed dataset (``corpus.seed``), as a
    published corpus is; ``seed`` draws the query pool.  So every seed
    serves one index, with shapes that do not change from run to run."""
    import functools

    import jax
    import jax.numpy as jnp
    corpus, queries = config["corpus"], config["queries"]
    if corpus["recipe"] != "clustered" or queries["recipe"] != "queries_from":
        raise ValueError("only the clustered / queries_from recipes exist")

    @functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
    def gen(kc, kq, n, d, n_centers, pool, center_scale, point_scale,
            jitter):
        kc, ka, kx = jax.random.split(kc, 3)
        kq, kj = jax.random.split(kq)
        centers = jax.random.normal(kc, (n_centers, d)) * center_scale
        asg = jax.random.randint(ka, (n,), 0, n_centers)
        x = centers[asg] + jax.random.normal(kx, (n, d)) * point_scale
        pick = jax.random.choice(kq, n, (pool,), replace=False)
        qs = x[pick] + jax.random.normal(kj, (pool, d)) * jitter
        return x.astype(jnp.float32), qs.astype(jnp.float32)

    return gen(seed_key(corpus["seed"]), seed_key(seed), config["n"],
               config["d"], corpus["n_centers"], queries["pool"],
               corpus["center_scale"], corpus["point_scale"],
               queries["jitter"])


def build_index(key, x, config: dict):
    from repro.index import search
    idx = config["index"]
    if idx["method"] == "ivfpq":
        return search.build_pq_index(key, x, idx["n_clusters"],
                                     n_sub=idx["pq_m"], n_bits=idx["pq_bits"])
    if idx["method"] == "ivfrabitq":
        if idx.get("rabitq_bits", 1) != 1 or idx.get("eps0", 3.0) != 3.0:
            raise ValueError("serving runs 1-bit RaBitQ at eps0 3 only")
        return search.build_rabitq_index(key, x, idx["n_clusters"])
    raise ValueError(f"unknown method {idx['method']!r}")


def knob_store(config: dict, k: int):
    """The configuration's knobs as the operating point serving resolves,
    so nothing is read from ``tuned_points.json``."""
    from repro.tuning.knobs import KnobConfig
    from repro.tuning.points import OperatingPoint, PointStore
    idx = config["index"]
    n_cand = idx.get("n_cand", {}).get(str(k))
    return PointStore([OperatingPoint(
        method=idx["method"], k=k,
        recall_target=config["guarantees"]["recall_target"],
        knobs=KnobConfig(n_probe=idx["n_probe"], n_cand=n_cand),
        recall=float("nan"), cost_units=0.0, feasible=True,
        corpus={"fingerprint": config["name"]})])


def _annotated(fn, name: str):
    import jax

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    return wrapped


def make_blocks(qs_host: np.ndarray, traffic: dict, n_probe: int) -> list:
    """The query pool as closed-loop blocks of ``clients`` requests, all
    arriving at 0."""
    from repro.serving.queue import Request
    c = int(traffic["clients"])
    return [[Request(rid=i, q=qs_host[b * c + i], k=int(traffic["k"]),
                     n_probe=n_probe, arrival=0.0,
                     deadline=float(traffic["deadline_s"]))
             for i in range(c)]
            for b in range(len(qs_host) // c)]


def setup(cell, seed: int) -> SimpleNamespace:
    """Corpus, index, serving state and server, warmed for the cell's
    (k, max_batch) shape and the B=1 shape of the parity calls."""
    import jax

    from repro.serving import batcher as sv_batcher
    from repro.serving import server as sv_server
    from repro.serving.state import ServingState
    config, traffic = cell.config, cell.traffic
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic loop {traffic['loop']!r}: only closed "
                         f"loops exist")
    idx = config["index"]
    k, batch = int(traffic["k"]), int(idx["max_batch"])
    phases = {}
    t = time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = round(now - t, 3)
        t = now
    x, qs = make_data(config, seed)
    qs_host = np.asarray(qs)
    phase("data")
    index = build_index(jax.random.fold_in(seed_key(config["corpus"]["seed"]),
                                           1), x, config)
    jax.block_until_ready(index)
    phase("index")
    state = ServingState(index, use_bbc=True, m=idx["bbc_m"],
                         tuned=knob_store(config, k))
    bucket = sv_batcher.ShapeBucket(k=k, batch=batch, n_probe=idx["n_probe"])
    state.warmup([bucket])
    eng = state.engine(bucket)
    phase("engine")
    jax.block_until_ready(eng.search_batch(qs[:1]))
    phase("b1")
    srv = sv_server.Server(state, ceilings=(k,), batch=batch,
                           admission=False, max_wait=0.0)
    state.run = _annotated(state.run, "serving.dispatch")
    srv._finish = _annotated(srv._finish, "serving.finish")
    blocks = make_blocks(qs_host, traffic, idx["n_probe"])
    for blk in blocks[:WARM_BLOCKS]:
        srv.run_trace(blk, warmup=False)
    phase("warm")
    return SimpleNamespace(phases=phases, x=x, index=index, srv=srv,
                           eng=eng, blocks=blocks,
                           knobs=dict(k=k, n_probe=eng.n_probe,
                                      n_cand=eng.n_cand, m=eng.m,
                                      fused=eng.fused, backend=eng.backend,
                                      batch=batch))


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

class CompileCounter:
    """Counts the persistent compile cache's misses (programs compiled)
    and hits (programs loaded)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event.endswith("/cache_misses"):
            self.compiles += 1
        elif event.endswith("/cache_hits"):
            self.cache_hits += 1


def run_window(s: SimpleNamespace, seconds: float, trace_dir: str | None,
               compiles: CompileCounter, keep: set) -> SimpleNamespace:
    """Serve blocks for ``seconds``.  Of each block only the outcomes whose
    pool position is in ``keep`` are held for the checks: holding every
    k-wide row would pile up gigabytes of host memory in the window."""
    import jax
    srv, blocks = s.srv, s.blocks
    clients = len(blocks[0])
    served = []                 # (block, completed, latency s, kept outcomes)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = compiles.compiles
    t_start = time.perf_counter()
    i = 0
    while True:
        b = i % len(blocks)
        with jax.profiler.TraceAnnotation("bench.block"):
            t0 = time.perf_counter()
            outs = srv.run_trace(blocks[b], warmup=False)
            t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.gap"):
            served.append((b, sum(o.completed for o in outs), t1 - t0,
                           [o for j, o in enumerate(outs)
                            if b * clients + j in keep]))
            i += 1
            if t1 - t_start >= seconds:
                break
    compiled = compiles.compiles - before
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return SimpleNamespace(served=served, seconds=t1 - t_start,
                           compiles=compiled, clients=clients)


# --------------------------------------------------------------------------
# the checks
# --------------------------------------------------------------------------

def trim(dists: np.ndarray, ids: np.ndarray, k: int):
    order = np.argsort(dists, kind="stable")[:k]
    return dists[order], ids[order]


def kept_positions(pool: int, seed: int) -> set:
    """Pool positions whose outcomes the window holds: a tenth of the
    pool, drawn from the seed, and never fewer than ``KEEP_MIN``."""
    rng = np.random.default_rng([seed, 0x5EED])
    n = min(pool, max(KEEP_MIN, pool // 10))
    return set(rng.choice(pool, size=n, replace=False).tolist())


def sample_requests(served, n: int, seed: int) -> list:
    """A seeded sample of the held outcomes of completed requests."""
    done = [o for *_, kept in served for o in kept if o.completed]
    rng = np.random.default_rng([seed, 0x5A3])
    pick = rng.choice(len(done), size=min(n, len(done)), replace=False)
    return [done[i] for i in sorted(pick)]


def served_numbers(x, queries: np.ndarray, ids: list, dists: list, k: int
                   ) -> dict:
    """Recall@k of each served row against the reference, and the relative
    gap between each row's farthest served distance and the reference's
    distance for that corpus row.  Runs the reference on the device."""
    import jax.numpy as jnp

    import reference
    _, ref_ids = reference.exact_topk(x, queries, k)
    far = [int(np.argmax(d)) for d in dists]
    ref_far = np.asarray(reference.row_dists(
        x, jnp.asarray([int(i[f]) for i, f in zip(ids, far)]),
        jnp.asarray(queries)))
    recalls = [len(set(got.tolist()) & set(want.tolist())) / k
               for got, want in zip(ids, ref_ids)]
    gaps = [abs(float(d[f]) - float(r)) / float(r)
            for d, f, r in zip(dists, far, ref_far)]
    return {"recall": recalls, "gap": gaps}


def control_numbers(x, queries: np.ndarray, k: int) -> dict:
    """The checks' numbers for the control: the reference at three-pass
    bf16 precision in the program's place."""
    import reference
    d, ids = reference.exact_topk_bf16x3(x, queries, k)
    return served_numbers(x, queries, list(ids), list(d), k)


def judge(numbers: dict, parity_bad: int, checks: dict) -> dict:
    """Each number compared beside its limit.  The distance check takes
    the median gap: the widest is a one- or two-ulp rounding difference on
    a few rows, common to sound runs, and does not separate the program
    from the control (PERF.md, section 2)."""
    recall = float(np.mean(numbers["recall"]))
    gap = float(np.median(numbers["gap"]))
    return {
        "mean_recall": {"value": recall, "limit": checks["min_recall"],
                        "ok": recall >= checks["min_recall"]},
        "median_dist_gap": {"value": gap,
                            "limit": checks["max_median_dist_gap"],
                            "ok": gap <= checks["max_median_dist_gap"]},
        "parity_mismatch": {"value": parity_bad,
                            "limit": checks["max_parity_mismatch"],
                            "ok": parity_bad <= checks["max_parity_mismatch"]},
    }


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def _probed_lanes(centroids: np.ndarray, sizes: np.ndarray,
                  qs: np.ndarray, n_probe: int) -> tuple[int, int]:
    """(lanes probed by any query of the block, lanes summed over its
    queries): the sizes of the n_probe nearest lists of each query."""
    d2 = ((qs[:, None, :].astype(np.float64) - centroids[None]) ** 2).sum(-1)
    probed = np.argsort(d2, axis=1, kind="stable")[:, :n_probe]
    union = np.unique(probed)
    return int(sizes[union].sum()), int(sizes[probed].sum())


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        require_chip: bool = True, control: bool = False) -> dict:
    """One run of ``cell``: returns the result line's object.  ``control``
    adds ``control_checks``: the checks' numbers for the control on the
    same sampled queries (``calibrate.py``)."""
    import jax

    import devtrace
    if require_chip:
        dev, peaks = check_device(cell.chips)
    else:
        dev, peaks = jax.devices()[0], None
    cache = enable_compile_cache()
    compiles = CompileCounter()
    s = setup(cell, seed)
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.3f} s {s.phases}, {compiles.compiles} compiles, "
        f"{compiles.cache_hits} compile-cache hits, knobs {s.knobs}, compile "
        f"cache {cache}")

    keep = kept_positions(len(s.blocks) * len(s.blocks[0]), seed)
    with tempfile.TemporaryDirectory() as tmp:
        w = run_window(s, seconds, tmp if trace else None, compiles, keep)
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        tr = None
        if trace:
            tr = devtrace.load_xplane(devtrace.find_xplane(tmp))
    completed = sum(n for _, n, _, _ in w.served)
    attempted = w.clients * len(w.served)
    lat_ms = np.repeat([lat * 1e3 for _, _, lat, _ in w.served],
                       [n for _, n, _, _ in w.served])
    blk_ms = np.array([lat * 1e3 for _, _, lat, _ in w.served])
    slow = blk_ms[blk_ms > 2 * np.median(blk_ms)]
    log(f"window {w.seconds:.3f} s: {len(w.served)} blocks, {completed} of "
        f"{attempted} requests completed, {w.compiles} compiles in the "
        f"window, peak HBM {peak} bytes; block ms median "
        f"{np.median(blk_ms):.1f} max {blk_ms.max():.1f}, blocks over twice "
        f"the median {[round(float(v), 1) for v in slow]}")

    # outside the window: direct engine calls for parity and the counters
    sample = sample_requests(w.served, int(cell.traffic["sample_requests"]),
                             seed)
    parity_bad, n_rr, n_sp = 0, [], []
    for o in sample:
        res = s.eng.search_batch(jax.numpy.asarray(o.request.q)[None])
        _, want = trim(np.asarray(res.dists)[0], np.asarray(res.ids)[0],
                       o.k_effective)
        parity_bad += set(want.tolist()) != set(o.ids.tolist())
        n_rr.append(int(np.asarray(res.n_reranked)[0]))
        n_sp.append(int(np.asarray(res.n_second_pass)[0]))
    ivf = s.index.ivf
    centroids = np.asarray(ivf.centroids, np.float64)
    sizes = np.asarray(ivf.cluster_sizes)
    block_qs = [np.stack([r.q for r in s.blocks[b]]) for b, *_ in w.served]
    queries = np.stack([o.request.q for o in sample])
    ids = [o.ids for o in sample]
    dists = [o.dists for o in sample]
    x = s.x
    del s                                   # free the program's state
    gc.collect()
    numbers = served_numbers(x, queries, ids, dists, cell.traffic["k"])
    checks = judge(numbers, parity_bad, cell.config["checks"])
    correct = bool(completed == attempted and sample
                   and all(c["ok"] for c in checks.values()))

    if trace:
        t0, t1 = devtrace.window(tr)
        ctx = SimpleNamespace(
            config=cell.config, traffic=cell.traffic, peaks=peaks,
            window_s=(t1 - t0) / 1e9,
            busy_s=devtrace.busy_seconds(tr),
            op_seconds=devtrace.op_seconds(tr),
            op_counts=devtrace.op_counts(tr),
            blocks=len(w.served), completed=completed,
            n_reranked=np.array(n_rr), n_second_pass=np.array(n_sp),
            probed=[_probed_lanes(centroids, sizes, q,
                                  cell.config["index"]["n_probe"])
                    for q in block_qs])
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = {"qps": completed / w.seconds,
               "p95_ms": float(np.percentile(lat_ms, 95)),
               "recall_at_k": checks["mean_recall"]["value"],
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted,
           "failed": attempted - completed, "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        out["breakdown"] = devtrace.breakdown(tr)
    if control:
        ctrl = control_numbers(x, queries, cell.traffic["k"])
        out["control_checks"] = judge(ctrl, 0, cell.config["checks"])
        out["widest_gap"] = [max(numbers["gap"]), max(ctrl["gap"])]
        out["worst_recall"] = min(numbers["recall"])
    # a missing answer (no id, infinite distance) reads as null, not as
    # JSON's non-standard Infinity
    out["checks"] = {n: {"value": c["value"] if np.isfinite(c["value"])
                         else None, "limit": c["limit"]}
                     for n, c in checks.items()}
    return out
