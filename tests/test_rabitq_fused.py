"""Bound-fused RaBitQ scan parity suite.

Three layers of agreement, per the fused-kernel contract:

  * kernel oracle      — ``ops.fused_rabitq_scan_batch`` on the Pallas
    backend (interpret mode on CPU) vs the pure-jnp mirror in kernels/ref.py:
    identical bucket ids / histograms / certified masks / miss counts, and
    allclose float lanes (the kernel's per-tile matmuls associate
    differently from the full-stream matmul).
  * searcher parity    — the fused batch searcher (ref AND pallas backends)
    vs the two-phase reference path (``fused=False``): identical top-k id
    sets for any inline gate (the band always covers the bound-straddle
    set), with the ref-backend variants sharing one float source so
    cold / warm / static runs stay bitwise comparable.
  * accounting         — ``n_second_pass`` is the MEASURED straggler count:
    it must equal the model formula re-derived from the kernel's own
    outputs (band ∩ ~certified), collapse to the whole band when the
    predictor is cold, vanish under a maximal prediction, and shrink as
    the predictor warms.

The sharded multidevice case (forced 8-host-device mesh, subprocess like
the other sharded suites) checks fused-vs-two-phase id parity and the
psum'd measured straggler counters on the distributed path.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buffer as rb
from repro.core import rerank
from repro.data import synthetic
from repro.index import ivf as ivf_mod, search
from repro.kernels import ops

N, D, NQ = 8000, 64, 6
K, N_PROBE = 200, 12
M_BUCKETS = 128
EPS0 = 3.0


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = synthetic.clustered(rng, N, D, n_centers=64)
    qs = synthetic.queries_from(rng, x, NQ)
    return jnp.asarray(x), jnp.asarray(qs)


@pytest.fixture(scope="module")
def rq_index(corpus):
    x, _ = corpus
    return search.build_rabitq_index(jax.random.key(0), x, 32, n_iter=4)


@pytest.fixture(scope="module")
def scan_inputs(rq_index, corpus):
    """Shared high-level inputs of the fused scan: routing, stream, sample
    codebook and the static inline gate — exactly what the searcher feeds
    the ops wrapper."""
    x, qs = corpus
    lay = ivf_mod.flat_layout(rq_index.ivf)
    stream = search.rabitq_stream(rq_index, lay)
    probed, lane_valid, d2 = search._routing(rq_index.ivf, lay, qs, N_PROBE)
    st = min(4, N_PROBE)
    sample_ub, sok = search._rabitq_sample_ub(
        stream.codes, stream.norm_o, stream.f_o, stream.cl,
        rq_index.ivf.centroids, rq_index.rq.rot, lay, probed, qs, d2, st,
        rq_index.ivf.cap, EPS0)
    cbs, tau_static = search._rabitq_sample_plan(sample_ub, K, K, st,
                                                 N_PROBE, M_BUCKETS)
    return lay, stream, lane_valid, d2, cbs, tau_static


def _scan(rq_index, qs, si, tau, backend):
    lay, stream, lane_valid, d2, cbs, _ = si
    return ops.fused_rabitq_scan_batch(
        stream.codes, stream.vectors, stream.norm_o, stream.f_o, stream.cl,
        rq_index.ivf.centroids, rq_index.rq.rot, qs, d2, lane_valid,
        cbs.d_min, cbs.delta, cbs.ew_map, M_BUCKETS, tau, eps0=EPS0,
        backend=backend)


# ---------------------------- kernel oracle ---------------------------------

def test_kernel_matches_ref_mirror(rq_index, corpus, scan_inputs):
    _, qs = corpus
    tau = scan_inputs[5]
    kp = _scan(rq_index, qs, scan_inputs, tau, "pallas")
    kr = _scan(rq_index, qs, scan_inputs, tau, "ref")
    names = ("est", "lb", "ub", "bucket_lb", "bucket_ub", "hist_lb",
             "hist_ub", "exact", "certified", "nmiss")
    for name, a, b in zip(names, kp, kr):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind in "ib":
            np.testing.assert_array_equal(a, b, err_msg=name)
            continue
        np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b),
                                      err_msg=f"{name} inf pattern")
        fin = np.isfinite(a)
        np.testing.assert_allclose(a[fin], b[fin], rtol=2e-4, atol=2e-4,
                                   err_msg=name)


def test_kernel_certified_semantics(rq_index, corpus, scan_inputs):
    """certified == valid & (bucket_lb <= tau_inline); exact finite exactly
    on certified lanes; nmiss counts the uncovered valid lanes."""
    _, qs = corpus
    lay, stream, lane_valid, d2, cbs, tau = scan_inputs
    (_, _, _, bucket_lb, _, _, _, exact, certified,
     nmiss) = _scan(rq_index, qs, scan_inputs, tau, "ref")
    want = np.asarray(lane_valid & (bucket_lb <= tau[:, None]))
    np.testing.assert_array_equal(np.asarray(certified), want)
    np.testing.assert_array_equal(np.isfinite(np.asarray(exact)), want)
    np.testing.assert_array_equal(
        np.asarray(nmiss),
        np.sum(np.asarray(lane_valid) & ~want, axis=1).astype(np.int32))


def test_kernel_cold_gate_certifies_nothing(rq_index, corpus, scan_inputs):
    _, qs = corpus
    cold = jnp.full((NQ,), -1, jnp.int32)
    outs = _scan(rq_index, qs, scan_inputs, cold, "pallas")
    assert not bool(jnp.any(outs[8]))
    assert not bool(jnp.any(jnp.isfinite(outs[7])))


def test_batch_rows_match_singleton_batch(rq_index, corpus, scan_inputs):
    """Every row of a batched scan is bit-identical to the same query scanned
    as a singleton batch: the kernel's per-query matmuls run on fixed-width
    query chunks, so a query's arithmetic does not depend on its batch."""
    _, qs = corpus
    lay, stream, lane_valid, d2, cbs, tau = scan_inputs
    full = _scan(rq_index, qs, scan_inputs, tau, "pallas")
    args = (stream.codes, stream.vectors, stream.norm_o, stream.f_o,
            stream.cl, rq_index.ivf.centroids, rq_index.rq.rot)
    for bi in (0, NQ - 1):
        sl = slice(bi, bi + 1)
        one = ops.fused_rabitq_scan_batch(
            *args, qs[sl], d2[sl], lane_valid[sl], cbs.d_min[sl],
            cbs.delta[sl], cbs.ew_map[sl], M_BUCKETS, tau[sl], eps0=EPS0,
            backend="pallas")
        for a, b in zip(full, one):
            np.testing.assert_array_equal(np.asarray(a)[bi],
                                          np.asarray(b)[0])


# ---------------------------- searcher parity -------------------------------

def _idsets_equal(ra, rb_):
    a, b = np.asarray(ra.ids), np.asarray(rb_.ids)
    for i in range(a.shape[0]):
        sa, sb = set(a[i].tolist()), set(b[i].tolist())
        assert sa == sb, (i, len(sa - sb), len(sb - sa))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fused_matches_two_phase(rq_index, corpus, backend,
                                 check_rabitq_reported):
    _, qs = corpus
    lay = ivf_mod.flat_layout(rq_index.ivf)
    if backend == "pallas":
        qs = qs[:4]
    rf = search.ivf_rabitq_search_batch(rq_index, qs, lay, k=K,
                                        n_probe=N_PROBE, use_bbc=True,
                                        fused=True, backend=backend)
    rt = search.ivf_rabitq_search_batch(rq_index, qs, lay, k=K,
                                        n_probe=N_PROBE, use_bbc=True,
                                        fused=False)
    _idsets_equal(rf, rt)
    # the fused path builds its bucket codebook from the nearest-tile
    # sample, the two-phase path from the full-stream upper bounds, so a
    # boundary lane can be certain-in (estimate reported) on one and
    # re-ranked (exact reported) on the other
    check_rabitq_reported(rq_index, qs, zip(rf.ids, rf.dists),
                          zip(rt.ids, rt.dists), eps0=EPS0)
    # the fused static gate covers most of the band inline: the measured
    # second pass must be well below the band the two-phase path gathers
    assert int(jnp.sum(rf.n_second_pass)) < int(jnp.sum(rt.n_second_pass))


def test_fused_ref_variants_bitwise_stable(rq_index, corpus):
    """On the ref backend every variant (static / cold / maximal gate)
    draws band exact distances from one shared matmul, so reported rows
    are bitwise identical whenever the certain-in classification agrees —
    the property the strict id-set assertions of the predictive suite
    rely on."""
    _, qs = corpus
    lay = ivf_mod.flat_layout(rq_index.ivf)
    static = search.ivf_rabitq_search_batch(
        rq_index, qs, lay, k=K, n_probe=N_PROBE, use_bbc=True, fused=True)
    cold, _ = search.ivf_rabitq_search_batch(
        rq_index, qs, lay, k=K, n_probe=N_PROBE, use_bbc=True, fused=True,
        pred_state=rerank.predictor_init(M_BUCKETS))
    np.testing.assert_array_equal(np.asarray(static.ids),
                                  np.asarray(cold.ids))
    np.testing.assert_array_equal(np.asarray(static.dists),
                                  np.asarray(cold.dists))


def test_fused_engine_default(rq_index, corpus):
    """The engine serves the fused path by default with the build-time
    stream cache; pinning fused=False must reproduce the same id sets."""
    from repro.index import engine
    _, qs = corpus
    ef = engine.SearchEngine.build(rq_index, k=K, n_probe=N_PROBE)
    et = engine.SearchEngine.build(rq_index, k=K, n_probe=N_PROBE,
                                   fused=False)
    assert ef.stream_cache is not None
    rf, rt = ef.search(qs), et.search(qs)
    _idsets_equal(rf, rt)


# ---------------------------- accounting ------------------------------------

def test_measured_straggler_count_matches_model(rq_index, corpus):
    """Regression guard against wiring drift: the searcher's reported
    ``n_second_pass`` must equal the model formula (band ∩ ~certified)
    re-derived from the kernel's own outputs for the same gate."""
    x, qs = corpus
    lay = ivf_mod.flat_layout(rq_index.ivf)
    stream = search.rabitq_stream(rq_index, lay)
    state = rerank.predictor_init(M_BUCKETS)
    for _ in range(2):
        res, state = search.ivf_rabitq_search_batch(
            rq_index, qs, lay, k=K, n_probe=N_PROBE, use_bbc=True,
            fused=True, pred_state=state)
    # re-derive the warm gate and the band exactly as the searcher does
    probed, lane_valid, d2 = search._routing(rq_index.ivf, lay, qs, N_PROBE)
    st = min(4, N_PROBE)
    spos, sok = ivf_mod.tile_positions(lay, probed[:, :st], rq_index.ivf.cap)
    _, _, ub = search._rabitq_batch_bounds(rq_index, stream, qs, lane_valid,
                                           EPS0, d2=d2)
    sample_ub = jnp.where(sok, jnp.take_along_axis(ub, spos, axis=1),
                          jnp.inf)
    cbs, _ = search._rabitq_sample_plan(sample_ub, K, K, st, N_PROBE,
                                        M_BUCKETS)
    count_s = max(1, -(-K // search._PRED_HIST_STRIDE))
    # ``state`` above has absorbed the second batch's histogram; the warm
    # run we model used the state AFTER batch 1, so replay it
    s1 = rerank.predictor_init(M_BUCKETS)
    _, s1 = search.ivf_rabitq_search_batch(
        rq_index, qs, lay, k=K, n_probe=N_PROBE, use_bbc=True, fused=True,
        pred_state=s1)
    tau_pred = jnp.full(
        (NQ,), rerank.predict_tau(s1, count_s,
                                  margin=search._PRED_GATE_MARGIN),
        jnp.int32)
    outs = ops.fused_rabitq_scan_batch(
        stream.codes, stream.vectors, stream.norm_o, stream.f_o, stream.cl,
        rq_index.ivf.centroids, rq_index.rq.rot, qs, d2, lane_valid,
        cbs.d_min, cbs.delta, cbs.ew_map, M_BUCKETS, tau_pred, eps0=EPS0,
        backend="ref")
    _, _, _, bucket_lb, bucket_ub, _, _, _, certified, _ = outs
    taus = search._tau_bucket_search(
        jnp.concatenate([bucket_ub, bucket_lb], axis=0),
        jnp.concatenate([lane_valid, lane_valid], axis=0), K, M_BUCKETS)
    tau_ub, tau_lb = taus[:NQ], taus[NQ:]
    certain_in = lane_valid & (bucket_ub < tau_lb[:, None])
    band = lane_valid & (bucket_lb <= tau_ub[:, None]) & ~certain_in
    modeled = jnp.sum(band & ~certified, axis=1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(res.n_second_pass),
                                  np.asarray(modeled))
    np.testing.assert_array_equal(
        np.asarray(res.n_reranked),
        np.asarray(jnp.sum(band, axis=1).astype(jnp.int32)))


def test_tau_bucket_search_equals_threshold_bucket():
    rng = np.random.default_rng(5)
    m = 32
    bucket = jnp.asarray(rng.integers(0, m + 1, (3, 500)), jnp.int32)
    valid = jnp.asarray(rng.random((3, 500)) < 0.8)
    for count in (1, 40, 200, 450):
        got = search._tau_bucket_search(bucket, valid, count, m)
        want = [rb.threshold_bucket(rb.histogram(bucket[i], m, valid[i]),
                                    count)[0] for i in range(3)]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.stack(want)))


# ---------------------------- sharded (multidevice) -------------------------

SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import rerank
    from repro.data import synthetic
    from repro.index import engine, search

    rng = np.random.default_rng(0)
    n, d, C = 12000, 32, 48
    k, n_probe, B = 500, 24, 8
    x = jnp.asarray(synthetic.clustered(rng, n, d, n_centers=48))
    qs = jnp.asarray(synthetic.queries_from(rng, np.asarray(x), B))
    mesh = jax.make_mesh((8,), ("model",))
    rq = search.build_rabitq_index(jax.random.key(0), x, C)

    def idsets_equal(ra, rb, name):
        for b in range(B):
            sa = set(np.asarray(ra.ids[b]).tolist()) - {-1}
            sb = set(np.asarray(rb.ids[b]).tolist()) - {-1}
            assert sa == sb, (name, b, len(sa - sb), len(sb - sa))
        print(name, "OK", flush=True)

    ef = engine.SearchEngine.build(rq, k=k, n_probe=n_probe, mesh=mesh)
    et = engine.SearchEngine.build(rq, k=k, n_probe=n_probe, mesh=mesh,
                                   fused=False)
    rf, rt = ef.search(qs), et.search(qs)
    idsets_equal(rf, rt, "sharded_fused_vs_two_phase")
    # the fused static gate certifies most survivors on-shard: the
    # measured straggler-survivor collective volume is well below the
    # full survivor count the two-phase path gathers
    assert int(jnp.sum(rf.n_second_pass)) < int(jnp.sum(rf.n_reranked)), (
        np.asarray(rf.n_second_pass), np.asarray(rf.n_reranked))
    assert int(jnp.sum(rt.n_second_pass)) == 0

    # predictive: cold gate certifies nothing (every survivor is a
    # straggler), the warm gate shrinks the measured second pass, and id
    # sets never move
    state = ef.predictor_init()
    cold, state = ef.search(qs, pred_state=state)
    idsets_equal(rf, cold, "sharded_pred_cold_vs_static")
    np.testing.assert_array_equal(np.asarray(cold.n_second_pass),
                                  np.asarray(cold.n_reranked))
    warm, state = ef.search(qs, pred_state=state)
    idsets_equal(rf, warm, "sharded_pred_warm_vs_static")
    assert int(jnp.sum(warm.n_second_pass)) < int(jnp.sum(cold.n_second_pass))

    # batched engine agreement (same index, single-device deployment)
    eb = engine.SearchEngine.build(rq, k=k, n_probe=n_probe)
    rb_ = eb.search(qs)
    for b in range(B):
        sa = set(np.asarray(rb_.ids[b]).tolist()) - {-1}
        sb = set(np.asarray(rf.ids[b]).tolist()) - {-1}
        overlap = len(sa & sb) / max(len(sa), 1)
        assert overlap >= 0.99, (b, overlap)
    print("RABITQ_FUSED_SHARDED_OK")
    """
)


@pytest.mark.multidevice
def test_sharded_fused_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT], capture_output=True,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "RABITQ_FUSED_SHARDED_OK" in out.stdout, (
        out.stdout[-2000:] + "\n" + out.stderr[-3000:])
