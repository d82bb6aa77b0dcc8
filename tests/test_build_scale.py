"""The index build at chip scale: chunked k-means, PQ encoding and fused
scan, argsort packing, and the served path at a small DEEP-shaped size.

The chunk constants are monkeypatched small so that several chunks run at
CPU sizes; each chunked result is compared with the one-chunk result.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic
from repro.index import ivf as ivf_mod
from repro.index import kmeans as km
from repro.index import pq as pq_mod
from repro.index import search
from repro.serving import server as sv_server
from repro.serving.queue import Request
from repro.serving.state import ServingState
from repro.tuning.knobs import KnobConfig
from repro.tuning.points import OperatingPoint, PointStore

N, D, C = 6000, 96, 48


def _small_chunks(monkeypatch, rows: int, n_clusters: int) -> None:
    """Make ``chunk_rows(n_clusters)`` equal ``rows``."""
    lanes = -(-n_clusters // km.LANE) * km.LANE
    monkeypatch.setattr(km, "CHUNK_BYTES", rows * 4 * lanes)
    assert km.chunk_rows(n_clusters) == rows


@pytest.fixture(scope="module")
def x():
    rng = np.random.default_rng(3)
    return jnp.asarray(synthetic.clustered(rng, N, D, n_centers=40))


def test_chunk_rows_fit_the_budget():
    for k in (16, 384, 1000, 16384):
        rows = km.chunk_rows(k)
        assert rows % 8 == 0
        assert rows * 4 * (-(-k // km.LANE) * km.LANE) <= km.CHUNK_BYTES
    # SIFT1M's 1000 lists take 4 chunks; DEEP-10M's 16,384 take 611
    assert km.n_chunks(1_000_000, 1000) == 4
    assert km.n_chunks(10_000_000, 16384) == 611


def test_chunked_kmeans_matches_one_chunk(x, monkeypatch):
    key = jax.random.key(4)
    cent1, a1 = km.kmeans(key, x, C, n_iter=6)
    _small_chunks(monkeypatch, 1000, C)             # 6 chunks, no tail
    cent6, a6 = km.kmeans(key, x, C, n_iter=6)
    _small_chunks(monkeypatch, 704, C)              # 8 chunks and a tail
    cent9, a9 = km.kmeans(key, x, C, n_iter=6)
    for cent, a in ((cent6, a6), (cent9, a9)):
        np.testing.assert_allclose(np.asarray(cent), np.asarray(cent1),
                                   rtol=1e-5, atol=1e-6)
        # rows may move only where their two nearest centroids are a tie
        d2 = np.asarray(km._pairwise_sq(x, cent1))
        two = np.sort(d2, axis=1)[:, :2]
        near_tie = (two[:, 1] - two[:, 0]) < 1e-5 * np.abs(two[:, 1])
        moved = np.asarray(a) != np.asarray(a1)
        assert not np.any(moved & ~near_tie)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iter"))
def _unchunked_kmeans(key, x, n_clusters, n_iter):
    """k-means as it was before chunking: (n, K) arrays over every row."""
    n, d = x.shape
    cent0 = x[jax.random.choice(key, n, (n_clusters,), replace=False)]

    def step(cent, _):
        one = jax.nn.one_hot(km.assign(x, cent), n_clusters, dtype=x.dtype)
        counts = jnp.sum(one, axis=0)
        sums = jnp.matmul(one.T, x, precision="highest")
        newc = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where(counts[:, None] > 0, newc, cent), None

    cent, _ = jax.lax.scan(step, cent0, None, length=n_iter)
    return cent, km.assign(x, cent)


def test_one_chunk_kmeans_is_the_unchunked_computation(x):
    """Where one chunk covers every row, k-means is bit for bit what it
    was before chunking."""
    assert km.n_chunks(N, C) == 1
    key = jax.random.key(5)
    cent, a = km.kmeans(key, x, C, n_iter=3)
    want_c, want_a = _unchunked_kmeans(key, x, C, 3)
    np.testing.assert_array_equal(np.asarray(cent), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want_a))


def test_one_full_chunk_kmeans_is_the_unchunked_computation(x, monkeypatch):
    """A chunk of exactly every row (one loop trip, no tail) is also bit
    for bit the unchunked computation."""
    _small_chunks(monkeypatch, N, C)
    key = jax.random.key(5)
    cent, a = km.kmeans(key, x, C, n_iter=3)
    want_c, want_a = _unchunked_kmeans(key, x, C, 3)
    np.testing.assert_array_equal(np.asarray(cent), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want_a))


def _loop_member_table(a: np.ndarray, n_clusters: int, lane: int = 128):
    """The per-cluster ``np.where`` packing that the argsort replaced."""
    sizes = np.bincount(a, minlength=n_clusters)
    cap = -(-max(int(sizes.max()), 1) // lane) * lane
    ids = np.full((n_clusters, cap), -1, np.int32)
    for c in range(n_clusters):
        mem = np.where(a == c)[0]
        ids[c, :len(mem)] = mem
    return ids, sizes


def _loop_flat_order(ids, sizes):
    return np.concatenate([ids[c, :sizes[c]] for c in range(len(sizes))])


def test_argsort_packing_equals_the_loop(x):
    index, a = ivf_mod.build_assigned(jax.random.key(6), x, C, n_iter=4)
    ids, sizes = _loop_member_table(np.asarray(a), C)
    np.testing.assert_array_equal(np.asarray(index.member_ids), ids)
    np.testing.assert_array_equal(np.asarray(index.member_valid), ids >= 0)
    np.testing.assert_array_equal(np.asarray(index.cluster_sizes), sizes)
    lay = ivf_mod.flat_layout(index)
    n = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(lay.order)[:n],
                                  _loop_flat_order(ids, sizes))
    np.testing.assert_array_equal(np.asarray(lay.cluster_of)[:n],
                                  np.repeat(np.arange(C), sizes))
    np.testing.assert_array_equal(np.asarray(lay.offsets),
                                  np.concatenate([[0], np.cumsum(sizes)]))
    assert not np.asarray(lay.valid)[n:].any()
    # ivf.build is the same table without the assignment
    again = ivf_mod.build(jax.random.key(6), x, C, n_iter=4)
    np.testing.assert_array_equal(np.asarray(again.member_ids), ids)


def test_chunked_pq_encode_equals_one_chunk(x, monkeypatch):
    cb = pq_mod.train(jax.random.key(7), x, D // 4, 4, n_iter=4)
    one = np.asarray(pq_mod.encode(cb, x))
    _small_chunks(monkeypatch, 512, cb.n_sub * cb.n_codes)  # 11 + a tail
    np.testing.assert_array_equal(np.asarray(pq_mod.encode(cb, x)), one)
    assert one.dtype == np.uint8 and one.shape == (N, D // 4)


@pytest.mark.parametrize("n_cand", [1000, 2000])   # compaction, dense
def test_chunked_fused_scan_ids_identical(x, monkeypatch, n_cand):
    """The fused PQ searcher scanning its stream SCAN_CHUNK lanes per call
    returns what one call over the whole stream returns, in both regimes
    (4 * n_cand against the 6,016-lane stream)."""
    index = search.build_pq_index(jax.random.key(8), x, C, n_iter=4)
    lay = ivf_mod.flat_layout(index.ivf)
    qs = jnp.asarray(synthetic.queries_from(np.random.default_rng(9),
                                            np.asarray(x), 8))

    def run():
        return search.ivf_pq_search_batch(
            index, qs, lay, k=300, n_probe=12, n_cand=n_cand, use_bbc=True,
            m=128, fused=True)
    one = run()
    monkeypatch.setattr(search, "SCAN_CHUNK", 1024)   # 5 chunks + a tail
    jax.clear_caches()
    try:
        chunked = run()
    finally:
        jax.clear_caches()
    for a, b in zip(one, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rabitq_codes_are_relative_to_their_own_list(x):
    """Every row is encoded against the centroid of the list it is a
    member of, the one the searchers use (``RabitqStream.cl``)."""
    index = search.build_rabitq_index(jax.random.key(10), x, C, n_iter=4)
    ids = np.asarray(index.ivf.member_ids)
    owner = np.empty(N, np.int64)
    rows, cols = np.nonzero(ids >= 0)
    owner[ids[rows, cols]] = rows
    r = np.asarray(x) - np.asarray(index.ivf.centroids)[owner]
    np.testing.assert_allclose(np.asarray(index.rq.norm_o),
                               np.linalg.norm(r, axis=1), rtol=1e-5)
    # and the stream's owning cluster is that list
    lay = ivf_mod.flat_layout(index.ivf)
    st = search.rabitq_stream(index, lay)
    n = N
    np.testing.assert_array_equal(np.asarray(st.cl)[:n],
                                  owner[np.asarray(lay.order)[:n]])


def test_deep_shaped_served_path_against_brute_force(monkeypatch):
    """A small DEEP-shaped deployment (d=96, M=24, many small lists, every
    build phase in several chunks) served through ServingState and
    Server.run_trace: recall against brute-force exact top-k meets the
    configuration's target, and the farthest served distance is that row's
    exact f32 distance."""
    n, d, c, k, n_probe, batch = 12000, 96, 160, 400, 24, 8
    rng = np.random.default_rng(11)
    x = jnp.asarray(synthetic.clustered(rng, n, d, n_centers=40))
    qs = synthetic.queries_from(rng, np.asarray(x), 16)
    _small_chunks(monkeypatch, 2048, c)
    # k-means over the lists 6 chunks, PQ training 3, PQ encoding 9
    assert [km.n_chunks(n, kk) for kk in (c, 16, d // 4 * 16)] == [6, 3, 9]
    index = search.build_pq_index(jax.random.key(12), x, c, n_sub=d // 4)
    point = OperatingPoint(
        method="ivfpq", k=k, recall_target=0.9,
        knobs=KnobConfig(n_probe=n_probe, n_cand=4 * k),
        recall=float("nan"), cost_units=0.0, feasible=True,
        corpus={"fingerprint": "deep-small"})
    state = ServingState(index, use_bbc=True, tuned=PointStore([point]))
    srv = sv_server.Server(state, ceilings=(k,), batch=batch,
                           admission=False, max_wait=0.0)
    reqs = [Request(rid=i, q=qs[i], k=k, n_probe=n_probe, arrival=0.0,
                    deadline=600.0) for i in range(len(qs))]
    outs = srv.run_trace(reqs)
    assert all(o.completed for o in outs)

    xn = np.asarray(x, np.float64)
    recalls = []
    for o in outs:
        q = np.asarray(o.request.q, np.float64)
        exact = np.sqrt(np.maximum(((xn - q) ** 2).sum(1), 0.0))
        want = np.argsort(exact, kind="stable")[:k]
        recalls.append(len(set(o.ids.tolist()) & set(want.tolist())) / k)
        far = int(np.argmax(o.dists))
        row = np.asarray(x)[int(o.ids[far])]
        qf = np.asarray(o.request.q, np.float32)
        ref = jnp.sqrt(jnp.maximum(
            jnp.sum(row * row) - 2.0 * jnp.matmul(
                row[None], qf[:, None], precision="highest")[0, 0]
            + jnp.sum(qf * qf), 0.0))
        assert abs(float(o.dists[far]) - float(ref)) <= 4e-7 * float(ref)
    assert np.mean(recalls) >= 0.9, recalls
