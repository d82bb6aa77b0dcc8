"""The fused IVF+PQ searcher in its dense regime (``_pq_dense_regime``:
``4 * n_cand >= n_flat``, or ``n_cand`` covers a query's expected probed
lanes, ``n_cand * n_clusters >= n_probe * n_flat``).

There the fused scan exacts every live lane and the n_cand cut is a
full-width mask.  The result must equal a NumPy reference built from the
kernel's own outputs: the top-k by exact distance (ties by stream position)
over each query's ``n_cand`` smallest (estimate, stream position) live
lanes, or over every live lane when fewer exist.  Where the probed tiles
are at most a quarter of the stream (``_pq_probed_final``) the final top-k
runs over them, bit for bit as over the stream.  Runs the Pallas kernel in
interpret mode on the CPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rerank
from repro.data import synthetic
from repro.index import ivf as ivf_mod
from repro.index import pq as pq_mod
from repro.index import search
from repro.kernels import ops

N, D, C, M, K = 2000, 32, 16, 128, 300
# DEEP-shaped: many small lists (cap 128, one lane width), so n_probe 8
# probes 225-537 lanes, a tenth of the 5,120-lane stream
N_DEEP, C_DEEP = 5000, 128


def _corpus(duplicated: bool, n: int = N, n_clusters: int = C):
    rng = np.random.default_rng(21)
    x = synthetic.clustered(rng, n // 2 if duplicated else n, D,
                            n_centers=C)
    if duplicated:
        # every vector twice: twins share codes, so estimates tie in pairs
        x = np.concatenate([x, x])
    qs = synthetic.queries_from(rng, x, 16)
    x = jnp.asarray(x)
    index = search.build_pq_index(jax.random.key(0), x, n_clusters,
                                  n_iter=2)
    return index, ivf_mod.flat_layout(index.ivf), jnp.asarray(qs)


@pytest.fixture(scope="module")
def corpora():
    """Corpora by name, each built on first use."""
    shapes = {"plain": (False,), "twins": (True,),
              "deep": (False, N_DEEP, C_DEEP),
              "deep_twins": (True, N_DEEP, C_DEEP)}
    return functools.cache(lambda name: _corpus(*shapes[name]))


@pytest.fixture(scope="module")
def plain(corpora):
    return corpora("plain")


@functools.partial(jax.jit, static_argnames=("n_probe", "n_cand"))
def _kernel_outputs(index, layout, qs, n_probe, n_cand, live):
    """The searcher's plan and fused scan, stage for stage: (est, bucket,
    hist, exact, lane validity), each (B, n_flat) but hist (B, m+1)."""
    ivf = index.ivf
    probed, lane_valid, _ = search._routing(ivf, layout, qs, n_probe)
    lane_valid = lane_valid & live[None, :]
    codes = index.codes[layout.order]
    luts = jax.vmap(lambda q: pq_mod.adc_table(index.pq, q))(qs)
    sample = search._pq_sample_est(layout, probed, codes, luts,
                                   min(4, n_probe), ivf.cap)
    plans = jax.vmap(lambda s: rerank.early_rerank_plan(
        s, n_cand=n_cand, n_sample=s.shape[0],
        n_total=n_probe * ivf.cap, m=M))(sample)
    est, bucket, hist, early, _ = ops.fused_scan_batch(
        codes, index.vectors[layout.order], lane_valid, luts, qs,
        plans.cb.d_min, plans.cb.delta, plans.cb.ew_map, M,
        jnp.full((qs.shape[0],), M, jnp.int32), backend="pallas")
    return est, bucket, hist, early, lane_valid


def _search(corpus, qs, n_probe, n_cand, k=K, live=None):
    index, layout, _ = corpus
    return search.ivf_pq_search_batch(
        index, qs, layout, k=k, n_probe=n_probe, n_cand=n_cand,
        use_bbc=True, m=M, backend="pallas", fused=True, live=live)


def _search_full_width(monkeypatch, *args, **kwargs):
    """``_search`` with the dense final top-k forced over the stream."""
    monkeypatch.setattr(search, "_pq_probed_final", lambda *a: False)
    jax.clear_caches()              # no trace at the probed width is reused
    try:
        return _search(*args, **kwargs)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _reference(est, early, valid, order, n_cand, k):
    """(dists, ids, selected count) per row, from the kernel's outputs."""
    dists, ids, counts = [], [], []
    for e, x, v in zip(est, early, valid):
        lanes = np.flatnonzero(v)
        sel = lanes[np.lexsort((lanes, e[lanes]))][:n_cand]
        top = sel[np.lexsort((sel, x[sel]))][:k]
        pad = k - len(top)
        dists.append(np.concatenate([x[top], np.full(pad, np.inf,
                                                     np.float32)]))
        ids.append(np.concatenate([order[top], np.full(pad, -1, np.int32)]))
        counts.append(len(sel))
    return np.stack(dists), np.stack(ids), np.array(counts)


def _cut_rows(est, valid, n_cand):
    """Rows with more live lanes than n_cand, and among them the rows whose
    n_cand-th and next lane (by estimate, then position) tie in estimate."""
    over, straddle = [], []
    for r, (e, v) in enumerate(zip(est, valid)):
        lanes = np.flatnonzero(v)
        if len(lanes) > n_cand:
            over.append(r)
            srt = np.sort(e[lanes])
            if srt[n_cand - 1] == srt[n_cand]:
                straddle.append(r)
    return over, straddle


# (corpus, n_probe, n_cand, k, tombstones, queries): every live lane fits;
# more live lanes than n_cand (the in-bucket cut); tied estimates at the
# cut (odd n_cand splits twin pairs); a tombstone live mask with the cut.
# The probed_* cases are DEEP-shaped: 4 * n_cand < n_flat, but n_cand
# covers the expected probed lanes, and the final top-k runs over the
# probed tiles; every query, so rows fall over and under n_cand and k.
CASES = {
    "fits": ("plain", 4, 1024, K, False, 4),
    "cut": ("plain", 12, 512, K, False, 4),
    "ties": ("twins", 12, 513, K, False, 4),
    "tombstones": ("plain", 12, 512, K, True, 4),
    "probed_fits": ("deep", 8, 1024, 400, False, 16),
    "probed_cut": ("deep", 8, 320, K, False, 16),
    "probed_ties": ("deep_twins", 8, 321, K, False, 16),
    "probed_tombstones": ("deep", 8, 320, K, True, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_result_matches_reference(corpora, monkeypatch, case):
    name, n_probe, n_cand, k, tombstones, n_q = CASES[case]
    corpus = corpora(name)
    index, layout, qs = corpus
    qs = qs[:n_q]
    n_flat, cap = layout.n_flat, index.ivf.cap
    assert search._pq_dense_regime(n_cand, n_probe, n_flat,
                                   index.ivf.n_clusters)
    probed_width = case.startswith("probed")
    assert (4 * n_cand >= n_flat) != probed_width
    assert search._pq_probed_final(k, n_probe, cap, n_flat) == probed_width
    live = jnp.asarray(
        np.random.default_rng(5).random(n_flat) > 0.3) \
        if tombstones else jnp.ones((n_flat,), bool)
    res = _search(corpus, qs, n_probe, n_cand, k,
                  live=live if tombstones else None)
    est, _, _, early, valid = (np.asarray(a) for a in _kernel_outputs(
        index, layout, qs, n_probe, n_cand, live))
    order = np.asarray(layout.order)
    want_d, want_i, want_n = _reference(est, early, valid, order, n_cand, k)

    over, straddle = _cut_rows(est, valid, n_cand)
    live_count = valid.sum(axis=1)
    if case.endswith("fits"):
        assert not over
    elif probed_width:
        assert over and len(over) < len(qs)
    else:
        assert len(over) == len(qs)
    if case.endswith("ties"):
        assert straddle
    if probed_width:
        assert (live_count < k).any()           # rows padded with -1
    np.testing.assert_array_equal(np.asarray(res.ids), want_i)
    np.testing.assert_array_equal(np.asarray(res.dists), want_d)
    np.testing.assert_array_equal(np.asarray(res.n_reranked), want_n)
    np.testing.assert_array_equal(np.asarray(res.n_reranked),
                                  np.minimum(live_count, n_cand))
    assert not np.asarray(res.n_second_pass).any()
    if tombstones:
        dead = np.asarray(layout.order)[~np.asarray(live)
                                        & np.asarray(layout.valid)]
        assert not np.isin(np.asarray(res.ids), dead).any()
    if probed_width:
        full = _search_full_width(monkeypatch, corpus, qs, n_probe, n_cand,
                                  k, live=live if tombstones else None)
        for a, b in zip(res, full):
            np.testing.assert_array_equal(_bits(a), _bits(b))


def _singleton_parity(corpus, n_probe):
    """A batch mixing queries over and under n_cand (the median of the
    probed lanes, raised to the dense regime's least n_cand): each row is
    bit-identical to the query's own B=1 call."""
    index, layout, qs = corpus
    lanes = np.asarray(ivf_mod.probe_mask(
        layout, ivf_mod.route_batch(index.ivf, qs, n_probe),
        index.ivf.n_clusters)).sum(axis=1)
    least = min(-(-layout.n_flat // 4),
                -(-n_probe * layout.n_flat // index.ivf.n_clusters))
    n_cand = max(int(np.sort(lanes)[len(lanes) // 2]), least)
    assert search._pq_dense_regime(n_cand, n_probe, layout.n_flat,
                                   index.ivf.n_clusters)
    assert (lanes > n_cand).any() and (lanes <= n_cand).any()
    batch = _search(corpus, qs, n_probe, n_cand)
    for i in range(qs.shape[0]):
        one = _search(corpus, qs[i:i + 1], n_probe, n_cand)
        for a, b in zip(batch, one):
            np.testing.assert_array_equal(np.asarray(a)[i], np.asarray(b)[0])
    np.testing.assert_array_equal(np.asarray(batch.n_reranked),
                                  np.minimum(lanes, n_cand))
    return n_cand


def test_dense_rows_identical_to_singleton_calls(plain):
    n_cand = _singleton_parity(plain, 8)
    assert 4 * n_cand >= plain[1].n_flat


def test_probed_width_rows_identical_to_singleton_calls(corpora):
    """The same at the DEEP shape, with the final top-k over the probed
    tiles."""
    index, layout, _ = corpus = corpora("deep")
    n_cand = _singleton_parity(corpus, 8)
    assert 4 * n_cand < layout.n_flat
    assert search._pq_probed_final(K, 8, index.ivf.cap, layout.n_flat)


# (n_cand, n_probe, n_flat, n_clusters, k, cap) -> dense, probed final:
# the benchmark's configurations (bench/configs; SIFT's cap is any one at
# or above its 1,000-row mean list, DEEP's 4,096 from its ``assumed``) and
# tests/test_tpu_compile.py's compaction case
SHAPES = {
    "sift1m-ivfpq4.k100k": ((400_000, 300, 1_000_064, 1000, 100_000, 1024),
                            True, False),
    "sift1m-ivfpq4.k5k": ((80_000, 300, 1_000_064, 1000, 5000, 1024),
                          False, False),
    "deep10m-ivfpq4.k100k": ((400_000, 512, 10_000_000, 16_384, 100_000,
                              4096), True, True),
    "tpu_compile.ivfpq_sparse": ((1000, 8, 4096, 32, 500, 256),
                                 False, False),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_regime_and_final_width_by_shape(shape):
    (n_cand, n_probe, n_flat, n_clusters, k, cap), dense, probed = \
        SHAPES[shape]
    assert search._pq_dense_regime(n_cand, n_probe, n_flat,
                                   n_clusters) == dense
    assert (dense and search._pq_probed_final(k, n_probe, cap,
                                              n_flat)) == probed


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_select_is_the_est_position_cut(seed):
    """``_dense_select`` on synthetic bucketed estimates with heavy ties:
    the n_cand smallest (estimate, position) valid lanes of each row, rows
    over and under n_cand in one batch."""
    rng = np.random.default_rng(seed)
    b, n, m, n_cand = 6, 512, 16, 150
    est = rng.choice(np.linspace(0.0, 3.0, 40).astype(np.float32),
                     size=(b, n))
    valid = rng.random((b, n)) < np.array([0.2, 0.25, 0.5, 0.8, 0.9, 1.0]
                                          )[:, None]
    # a monotone bucketing, with the overflow bucket above 2.5
    bucket = np.where(est > 2.5, m, np.minimum(est * 6, m - 1)).astype(
        np.int32)
    est = np.where(valid, est, np.inf).astype(np.float32)
    bucket = np.where(valid, bucket, m)
    hist = np.stack([np.bincount(bk[v], minlength=m + 1)
                     for bk, v in zip(bucket, valid)]).astype(np.int32)
    got = np.asarray(search._dense_select(
        jnp.asarray(est), jnp.asarray(bucket), jnp.asarray(hist),
        jnp.asarray(valid), n_cand))
    for r in range(b):
        lanes = np.flatnonzero(valid[r])
        want = np.zeros(n, bool)
        want[lanes[np.lexsort((lanes, est[r, lanes]))][:n_cand]] = True
        np.testing.assert_array_equal(got[r], want)
