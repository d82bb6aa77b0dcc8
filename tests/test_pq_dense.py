"""The fused IVF+PQ searcher in its dense regime (``4 * n_cand >= n_flat``).

There the fused scan exacts every live lane and the n_cand cut is a
full-width mask.  The result must equal a NumPy reference built from the
kernel's own outputs: the top-k by exact distance (ties by stream position)
over each query's ``n_cand`` smallest (estimate, stream position) live
lanes, or over every live lane when fewer exist.  Runs the Pallas kernel in
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


def _corpus(duplicated: bool):
    rng = np.random.default_rng(21)
    x = synthetic.clustered(rng, N // 2 if duplicated else N, D,
                            n_centers=C)
    if duplicated:
        # every vector twice: twins share codes, so estimates tie in pairs
        x = np.concatenate([x, x])
    qs = synthetic.queries_from(rng, x, 16)
    x = jnp.asarray(x)
    index = search.build_pq_index(jax.random.key(0), x, C, n_iter=2)
    return index, ivf_mod.flat_layout(index.ivf), jnp.asarray(qs)


@pytest.fixture(scope="module")
def plain():
    return _corpus(duplicated=False)


@pytest.fixture(scope="module")
def twins():
    return _corpus(duplicated=True)


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


def _search(corpus, qs, n_probe, n_cand, live=None):
    index, layout, _ = corpus
    return search.ivf_pq_search_batch(
        index, qs, layout, k=K, n_probe=n_probe, n_cand=n_cand,
        use_bbc=True, m=M, backend="pallas", fused=True, live=live)


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


# (corpus, n_probe, n_cand, tombstones): every live lane fits; more live
# lanes than n_cand (the in-bucket cut); tied estimates at the cut (odd
# n_cand splits twin pairs); a tombstone live mask with the cut
CASES = {
    "fits": ("plain", 4, 1024, False),
    "cut": ("plain", 12, 512, False),
    "ties": ("twins", 12, 513, False),
    "tombstones": ("plain", 12, 512, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_result_matches_reference(plain, twins, case):
    name, n_probe, n_cand, tombstones = CASES[case]
    corpus = plain if name == "plain" else twins
    index, layout, qs = corpus
    qs = qs[:4]
    assert 4 * n_cand >= layout.n_flat          # the dense regime
    live = jnp.asarray(
        np.random.default_rng(5).random(layout.n_flat) > 0.3) \
        if tombstones else jnp.ones((layout.n_flat,), bool)
    res = _search(corpus, qs, n_probe, n_cand,
                  live=live if tombstones else None)
    est, _, _, early, valid = (np.asarray(a) for a in _kernel_outputs(
        index, layout, qs, n_probe, n_cand, live))
    order = np.asarray(layout.order)
    want_d, want_i, want_n = _reference(est, early, valid, order, n_cand, K)

    over, straddle = _cut_rows(est, valid, n_cand)
    if case == "fits":
        assert not over
    else:
        assert len(over) == len(qs)
    if case == "ties":
        assert straddle
    np.testing.assert_array_equal(np.asarray(res.ids), want_i)
    np.testing.assert_array_equal(np.asarray(res.dists), want_d)
    live_count = valid.sum(axis=1)
    np.testing.assert_array_equal(np.asarray(res.n_reranked), want_n)
    np.testing.assert_array_equal(np.asarray(res.n_reranked),
                                  np.minimum(live_count, n_cand))
    assert not np.asarray(res.n_second_pass).any()
    if tombstones:
        dead = np.asarray(layout.order)[~np.asarray(live)
                                        & np.asarray(layout.valid)]
        assert not np.isin(np.asarray(res.ids), dead).any()


def test_dense_rows_identical_to_singleton_calls(plain):
    """A batch mixing queries over and under n_cand: each row is
    bit-identical to the query's own B=1 call."""
    index, layout, qs = plain
    n_probe = 8
    lanes = np.asarray(ivf_mod.probe_mask(
        layout, ivf_mod.route_batch(index.ivf, qs, n_probe),
        index.ivf.n_clusters)).sum(axis=1)
    n_cand = int(np.sort(lanes)[len(lanes) // 2])
    assert 4 * n_cand >= layout.n_flat
    assert (lanes > n_cand).any() and (lanes <= n_cand).any()
    batch = _search(plain, qs, n_probe, n_cand)
    for i in range(qs.shape[0]):
        one = _search(plain, qs[i:i + 1], n_probe, n_cand)
        for a, b in zip(batch, one):
            np.testing.assert_array_equal(np.asarray(a)[i], np.asarray(b)[0])
    np.testing.assert_array_equal(np.asarray(batch.n_reranked),
                                  np.minimum(lanes, n_cand))


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
