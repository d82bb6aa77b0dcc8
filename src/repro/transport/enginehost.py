"""Spec-built engine host: the one place a (dataset, index) pair is
constructed from a declarative spec.

The transport tier needs the *same* engine in three different processes:
worker subprocesses (live serving), the replay driver (re-executing
recorded responses), and the bench's direct-call parity baseline.  All
three build from one JSON-able spec through this module, so "the same
engine" is a guarantee by construction — same seeds, same k-means
iterations, same PQ codebooks — and the record/replay checksum contract
(a replayed response must reproduce the recorded payload checksum
bit-for-bit) is checking cross-process engine determinism, not hoping
for it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import synthetic
from repro.index import ivf as ivf_mod
from repro.index import search as idx_search
from repro.serving.batcher import ShapeBucket, bucket_of, k_ceilings
from repro.serving.server import trim_topk
from repro.serving.state import ServingState


def build_spec(*, n: int = 4096, d: int = 32, seed: int = 0,
               ks=(10, 100, 1000), n_probe: int = 8,
               data: str = "clustered", n_clusters: int | None = None,
               n_bits: int = 4, n_iter: int = 6,
               use_bbc: bool = True) -> dict:
    """A fully-determined, JSON-able engine description."""
    if data not in ("clustered", "isotropic", "manifold"):
        raise ValueError(f"unknown dataset kind {data!r}")
    return {"n": int(n), "d": int(d), "seed": int(seed),
            "ks": [int(k) for k in ks], "n_probe": int(n_probe),
            "data": data,
            "n_clusters": int(n_clusters or max(int(np.sqrt(n)), 16)),
            "n_bits": int(n_bits), "n_iter": int(n_iter),
            "use_bbc": bool(use_bbc)}


def make_dataset(spec: dict) -> np.ndarray:
    rng = np.random.default_rng(int(spec["seed"]))
    kind = spec.get("data", "clustered")
    n, d = int(spec["n"]), int(spec["d"])
    if kind == "clustered":
        return synthetic.clustered(rng, n, d)
    if kind == "isotropic":
        return synthetic.isotropic(rng, n, d)
    return synthetic.manifold(rng, n, d)


def centroids_from_spec(spec: dict) -> np.ndarray:
    """The spec's coarse centroids alone — the IVF leg of
    ``build_state_from_spec``'s index build, with the same key split — for a
    process that routes requests but serves none (the net master)."""
    x = jnp.asarray(make_dataset(spec))
    k_ivf, _ = jax.random.split(jax.random.key(int(spec["seed"])))
    ivf = ivf_mod.build(k_ivf, x, int(spec["n_clusters"]),
                        int(spec["n_iter"]))
    return np.asarray(ivf.centroids)


def build_state_from_spec(spec: dict) -> tuple[ServingState, tuple[int, ...]]:
    """Spec -> (ServingState, k ceilings).  Deterministic: every process
    handed the same spec builds a bit-identical engine."""
    x = jnp.asarray(make_dataset(spec))
    index = idx_search.build_pq_index(
        jax.random.key(int(spec["seed"])), x, int(spec["n_clusters"]),
        n_bits=int(spec["n_bits"]), n_iter=int(spec["n_iter"]))
    state = ServingState(index, use_bbc=bool(spec.get("use_bbc", True)))
    return state, k_ceilings(spec["ks"])


def make_exec_fn(state: ServingState, ceilings: tuple[int, ...]):
    """Singleton executor: run a (d,) query at its bucket ceiling, trim to
    the requested k.  This is the worker's hot path AND the replay /
    parity baseline — one definition, three processes."""
    def exec_fn(q: np.ndarray, k: int,
                n_probe: int) -> tuple[np.ndarray, np.ndarray]:
        bucket = bucket_of(int(k), int(n_probe), ceilings, 1)
        res = state.engine(bucket).search(jnp.asarray(q))
        jax.block_until_ready((res.dists, res.ids))
        return trim_topk(np.asarray(res.dists), np.asarray(res.ids), int(k))
    return exec_fn


def warmup_and_measure(exec_fn, spec: dict,
                       ceilings: tuple[int, ...]) -> dict[str, float]:
    """Compile every serving bucket and measure post-compile singleton
    service times — the ``{"k,n_probe": seconds}`` map a worker's READY
    frame carries so the master's service EMA starts from evidence."""
    rng = np.random.default_rng(int(spec["seed"]) + 1)
    q = rng.standard_normal(int(spec["d"])).astype(np.float32)
    n_probe = int(spec["n_probe"])
    svc: dict[str, float] = {}
    for k in ceilings:
        exec_fn(q, k, n_probe)                  # compile
        t0 = time.perf_counter()
        exec_fn(q, k, n_probe)                  # measure warm
        svc[f"{k},{n_probe}"] = time.perf_counter() - t0
    return svc


def service_fn_from_svc(svc: dict[str, float], default: float = 0.005):
    """The sim-facing inverse of a READY frame's svc map."""
    table = {tuple(int(s) for s in key.split(",")): float(dt)
             for key, dt in svc.items()}

    def service_fn(bucket: ShapeBucket) -> float:
        return table.get((bucket.k, bucket.n_probe), default)
    return service_fn
