"""Parity tests: the natively batched searchers must return the same top-k
as the single-query paths (and hence as vmap-of-single-query) for all three
index types, plus edge cases (B=1, k larger than a cluster's population,
under-filled results) and the fused Pallas path in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic
from repro.index import engine, ivf as ivf_mod, search


N, D, NQ = 8000, 64, 6
K, N_PROBE = 200, 12


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = synthetic.clustered(rng, N, D, n_centers=64)
    qs = synthetic.queries_from(rng, x, NQ)
    return jnp.asarray(x), jnp.asarray(qs)


@pytest.fixture(scope="module")
def ivf_index(corpus):
    x, _ = corpus
    return ivf_mod.build(jax.random.key(2), x, 32, n_iter=4)


@pytest.fixture(scope="module")
def pq_index(corpus):
    x, _ = corpus
    return search.build_pq_index(jax.random.key(0), x, 32, n_iter=4)


@pytest.fixture(scope="module")
def rq_index(corpus):
    x, _ = corpus
    return search.build_rabitq_index(jax.random.key(0), x, 32, n_iter=4)


def _assert_parity(batch_res, single_results, min_overlap=1.0):
    """Top-k id sets equal (up to min_overlap) and sorted dists allclose."""
    bids = np.asarray(batch_res.ids)
    bd = np.asarray(batch_res.dists)
    for bi, r1 in enumerate(single_results):
        sids = np.asarray(r1.ids)
        got, want = set(bids[bi].tolist()), set(sids.tolist())
        k = len(sids)
        overlap = len(got & want) / k
        assert overlap >= min_overlap, (bi, overlap)
        if min_overlap >= 1.0:
            assert got == want, (bi, got ^ want)
        np.testing.assert_allclose(
            np.sort(bd[bi]), np.sort(np.asarray(r1.dists)),
            rtol=2e-4, atol=2e-4)


# ---------------------------- layout ---------------------------------------

def test_flat_layout_covers_corpus(ivf_index):
    lay = ivf_mod.flat_layout(ivf_index)
    order = np.asarray(lay.order)
    valid = np.asarray(lay.valid)
    assert sorted(order[valid].tolist()) == list(range(N))
    # cluster_of consistent with offsets
    cl = np.asarray(lay.cluster_of)
    offs = np.asarray(lay.offsets)
    for c in range(ivf_index.n_clusters):
        seg = cl[offs[c]:offs[c + 1]]
        assert (seg == c).all()
    assert (cl[offs[-1]:] == ivf_index.n_clusters).all()  # padding tail


def test_probe_mask_matches_membership(ivf_index, corpus):
    _, qs = corpus
    lay = ivf_mod.flat_layout(ivf_index)
    probed = ivf_mod.route_batch(ivf_index, qs, 4)
    mask = np.asarray(ivf_mod.probe_mask(lay, probed, ivf_index.n_clusters))
    cl = np.asarray(lay.cluster_of)
    for bi in range(qs.shape[0]):
        want = np.isin(cl, np.asarray(probed[bi])) & np.asarray(lay.valid)
        np.testing.assert_array_equal(mask[bi], want)


# ---------------------------- parity ---------------------------------------

@pytest.mark.parametrize("use_bbc", [False, True])
def test_ivf_batch_parity(ivf_index, corpus, use_bbc):
    x, qs = corpus
    lay = ivf_mod.flat_layout(ivf_index)
    br = search.ivf_search_batch(ivf_index, x, qs, lay, k=K, n_probe=N_PROBE,
                                 use_bbc=use_bbc)
    singles = [search.ivf_search(ivf_index, x, q, k=K, n_probe=N_PROBE,
                                 use_bbc=use_bbc) for q in qs]
    _assert_parity(br, singles)


@pytest.mark.parametrize("use_bbc", [False, True])
def test_pq_batch_parity(pq_index, corpus, use_bbc):
    _, qs = corpus
    lay = ivf_mod.flat_layout(pq_index.ivf)
    br = search.ivf_pq_search_batch(pq_index, qs, lay, k=K, n_probe=N_PROBE,
                                    n_cand=8 * K, use_bbc=use_bbc)
    singles = [search.ivf_pq_search(pq_index, q, k=K, n_probe=N_PROBE,
                                    n_cand=8 * K, use_bbc=use_bbc)
               for q in qs]
    _assert_parity(br, singles)


@pytest.mark.parametrize("use_bbc", [False, True])
def test_rabitq_batch_parity(rq_index, corpus, use_bbc,
                             check_rabitq_reported):
    _, qs = corpus
    lay = ivf_mod.flat_layout(rq_index.ivf)
    br = search.ivf_rabitq_search_batch(rq_index, qs, lay, k=K,
                                        n_probe=N_PROBE, use_bbc=use_bbc)
    singles = [search.ivf_rabitq_search(rq_index, q, k=K, n_probe=N_PROBE,
                                        use_bbc=use_bbc) for q in qs]
    # The batched estimator decomposes P(q-c) = Pq - Pc, so bounds differ
    # from the per-cluster matvec at float accumulation level; plan masks can
    # flip for boundary items.  Demand near-perfect set agreement.
    # With BBC, certain-in members report their estimate and re-ranked ones
    # their exact distance; the single-query path builds its codebook from
    # the probed set's upper bounds, the batched one from the sample prefix.
    if use_bbc:
        check_rabitq_reported(rq_index, qs, zip(br.ids, br.dists),
                              [(r.ids, r.dists) for r in singles],
                              min_overlap=0.99, atol=2e-4, rtol=2e-4)
    else:
        _assert_parity(br, singles)


def test_pq_batch_fused_interpret_matches_unfused(pq_index, corpus):
    """The fused Pallas kernel path (interpret mode on CPU) must agree with
    the jnp fallback path."""
    _, qs = corpus
    lay = ivf_mod.flat_layout(pq_index.ivf)
    rf = search.ivf_pq_search_batch(pq_index, qs[:4], lay, k=K,
                                    n_probe=N_PROBE, n_cand=8 * K,
                                    use_bbc=True, fused=True,
                                    backend="pallas")
    rn = search.ivf_pq_search_batch(pq_index, qs[:4], lay, k=K,
                                    n_probe=N_PROBE, n_cand=8 * K,
                                    use_bbc=True, fused=False)
    for bi in range(4):
        assert (set(np.asarray(rf.ids[bi]).tolist())
                == set(np.asarray(rn.ids[bi]).tolist()))
    np.testing.assert_allclose(np.sort(np.asarray(rf.dists), axis=1),
                               np.sort(np.asarray(rn.dists), axis=1),
                               rtol=1e-4, atol=1e-4)
    # the fused kernel's inline early re-rank must cover most of the
    # selection (the Alg. 4 story): stragglers only in the second pass
    assert int(jnp.sum(rf.n_second_pass)) < int(jnp.sum(rf.n_reranked))


# ---------------------------- edge cases ------------------------------------

@pytest.mark.parametrize("use_bbc", [False, True])
def test_batch_of_one(pq_index, corpus, use_bbc):
    _, qs = corpus
    lay = ivf_mod.flat_layout(pq_index.ivf)
    br = search.ivf_pq_search_batch(pq_index, qs[:1], lay, k=K,
                                    n_probe=N_PROBE, n_cand=8 * K,
                                    use_bbc=use_bbc)
    assert br.ids.shape == (1, K)
    r1 = search.ivf_pq_search(pq_index, qs[0], k=K, n_probe=N_PROBE,
                              n_cand=8 * K, use_bbc=use_bbc)
    _assert_parity(br, [r1])


def test_k_exceeds_cluster_population(ivf_index, corpus):
    """n_probe=1 with k larger than any single cluster: the result is the
    whole probed cluster plus (+inf, -1) padding — identical to the
    single-query path."""
    x, qs = corpus
    lay = ivf_mod.flat_layout(ivf_index)
    k = int(np.asarray(ivf_index.cluster_sizes).max()) + 64
    br = search.ivf_search_batch(ivf_index, x, qs, lay, k=k, n_probe=1)
    for bi, q in enumerate(qs):
        r1 = search.ivf_search(ivf_index, x, q, k=k, n_probe=1)
        np.testing.assert_array_equal(np.asarray(br.ids[bi]),
                                      np.asarray(r1.ids))
        np.testing.assert_allclose(np.asarray(br.dists[bi]),
                                   np.asarray(r1.dists), rtol=2e-4,
                                   atol=2e-4)
        n_valid = int(np.asarray(ivf_index.cluster_sizes)[
            int(ivf_mod.route(ivf_index, q, 1)[0])])
        assert (np.asarray(br.ids[bi])[n_valid:] == -1).all()
        assert np.isinf(np.asarray(br.dists[bi])[n_valid:]).all()


def test_all_invalid_tail_lanes(ivf_index, corpus):
    """Stream-tail padding lanes (the all-invalid-tile analogue of the
    compact layout) must never be selected."""
    x, qs = corpus
    lay = ivf_mod.flat_layout(ivf_index)
    br = search.ivf_search_batch(ivf_index, x, qs, lay, k=K,
                                 n_probe=ivf_index.n_clusters)
    ids = np.asarray(br.ids)
    assert (ids >= 0).all() and (ids < N).all()
    # exhaustive probe == exact search
    from repro.index import flat
    for bi in range(2):
        gd, gi = flat.search(x, qs[bi], K)
        assert set(ids[bi].tolist()) == set(np.asarray(gi).tolist())


# ---------------------------- engine ----------------------------------------

def test_engine_dispatch(pq_index, rq_index, ivf_index, corpus):
    x, qs = corpus
    for index, kwargs in ((pq_index, {}), (rq_index, {}),
                          (ivf_index, {"vectors": x})):
        eng = engine.SearchEngine.build(index, k=64, n_probe=8, use_bbc=True,
                                        **kwargs)
        rb_ = eng.search(qs[:3])
        assert rb_.ids.shape == (3, 64)
        r1 = eng.search(qs[0])
        assert r1.ids.shape == (64,)
        assert set(np.asarray(rb_.ids[0]).tolist()) \
            == set(np.asarray(r1.ids).tolist())


# ------------------------ deterministic tie-breaking -------------------------

def test_tie_broken_cut_is_order_invariant():
    """The selection SET of the (est, global-id) cut must be a function of
    the (value, id) multiset alone — identical for the batched stream order
    and any sharded gathered-pool permutation — even when PQ estimates tie
    exactly at the cut boundary (shared codes make such ties common)."""
    rng = np.random.default_rng(3)
    b, n, width = 4, 256, 41
    # few distinct levels -> boundary ties guaranteed
    vals = rng.choice(np.linspace(0.2, 2.0, 9).astype(np.float32),
                      size=(b, n))
    vals[:, -13:] = np.inf
    gids = rng.permutation(np.arange(n, dtype=np.int32))
    gids[-13:] = -1

    def kept_set(v_row, i_row):
        keep = search._kth_value_mask(jnp.asarray(v_row[None]),
                                      jnp.asarray(i_row[None]), width)
        sel = np.flatnonzero(np.asarray(keep)[0] & np.isfinite(v_row))
        return set(i_row[sel].tolist())

    neg, pos = search._topk_est_id(jnp.asarray(vals), jnp.asarray(gids),
                                   width)
    neg, pos = np.asarray(neg), np.asarray(pos)
    for bi in range(b):
        base = kept_set(vals[bi], gids)
        # lexicographic (value, id) oracle
        order = np.lexsort((gids.astype(np.int64) & 0x7FFFFFFF, vals[bi]))
        pick = order[:width]
        want = set(gids[pick[np.isfinite(vals[bi][pick])]].tolist())
        assert base == want
        # mask set survives any pool permutation (the sharded gather order)
        perm = rng.permutation(n)
        assert kept_set(vals[bi][perm], gids[perm]) == want
        # batched top_k-with-repair selects the same set
        got = set(gids[pos[bi][np.isfinite(-neg[bi])]].tolist())
        assert got == want


def test_topk_est_id_matches_topk_without_ties():
    """Tie-free rows must pay (and return) exactly the plain top_k."""
    rng = np.random.default_rng(4)
    vals = (rng.standard_normal((5, 128)).astype(np.float32)) ** 2
    gids = np.arange(128, dtype=np.int32)
    neg, pos = search._topk_est_id(jnp.asarray(vals), jnp.asarray(gids), 17)
    rneg, rpos = jax.lax.top_k(-jnp.asarray(vals), 17)
    assert np.array_equal(np.asarray(neg), np.asarray(rneg))
    assert np.array_equal(np.asarray(pos), np.asarray(rpos))
