"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buffer as rb
from repro.kernels import ops, ref


def _row0(outs):
    """The first query's row of every output of a batched op."""
    return tuple(o[0] for o in outs)


@pytest.mark.parametrize("n", [256, 1000, 4096])
@pytest.mark.parametrize("m_sub,k_codes", [(16, 16), (32, 16), (33, 16)])
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int32])
def test_pq_adc(rng, n, m_sub, k_codes, dtype):
    codes = jnp.asarray(rng.integers(0, k_codes, (n, m_sub)), dtype)
    lut = jnp.asarray(rng.random((m_sub, k_codes)), jnp.float32)
    got = ops.pq_adc_batch(codes, lut[None], backend="pallas")[0]
    want = ref.pq_adc(codes, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [512, 2000, 8192])
@pytest.mark.parametrize("m", [16, 64, 128])
def test_bucket_hist(rng, n, m):
    dists = jnp.asarray(rng.random(n) * 10 + 1, jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    dists = jnp.where(valid, dists, jnp.inf)
    cb = rb.build_codebook(dists, k=min(n // 2, 1000), m=m)
    got_b, got_h = _row0(ops.bucket_hist_batch(
        dists[None], valid[None], cb.d_min[None], cb.delta[None],
        cb.ew_map[None], m, backend="pallas"))
    want_b, want_h = ref.bucket_hist(dists, valid, cb.d_min, cb.delta,
                                     cb.ew_map, m)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    # kernel bucketize also agrees with the core-library bucketize
    core_b = rb.bucketize(cb, dists)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(core_b))


@pytest.mark.parametrize("n,d,m_sub", [(512, 64, 16), (1000, 128, 32),
                                       (256, 96, 24)])
def test_fused_scan(rng, n, d, m_sub):
    k_codes, m = 16, 64
    codes = jnp.asarray(rng.integers(0, k_codes, (n, m_sub)), jnp.uint8)
    vectors = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(d), jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.95)
    lut = jnp.asarray(rng.random((m_sub, k_codes)) * 2, jnp.float32)
    est_ref = jnp.sqrt(jnp.maximum(ref.pq_adc(codes, lut), 0.0))
    cb = rb.build_codebook(jnp.where(valid, est_ref, jnp.inf),
                           k=min(n // 2, 500), m=m)
    tau = jnp.int32(m // 3)
    got = _row0(ops.fused_scan_batch(
        codes, vectors, valid[None], lut[None], q[None], cb.d_min[None],
        cb.delta[None], cb.ew_map[None], m, tau[None], backend="pallas"))
    want = ref.fused_scan(codes, vectors, valid, lut, q, cb.d_min, cb.delta,
                          cb.ew_map, m, tau)
    names = ["est", "bucket", "hist", "early", "nmiss"]
    for name, g, w in zip(names, got, want):
        if name == "est":
            # masked lanes are +inf in the kernel; oracle masks identically
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)
        elif name in ("bucket", "hist", "nmiss"):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
    # the miss count is the complement of the predicted lanes
    n_pred = int(jnp.sum(jnp.isfinite(got[3])))
    assert int(got[4]) == int(jnp.sum(valid)) - n_pred


@pytest.mark.parametrize("n,d", [(256, 64), (999, 1536), (4096, 96)])
def test_l2_exact(rng, n, d):
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(d), jnp.float32)
    got = ops.l2_exact_batch(x, q[None], backend="pallas")[0]
    want = ref.l2_exact(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ------------------------- batched kernels ---------------------------------

@pytest.mark.parametrize("b", [1, 5, 8, 19])
@pytest.mark.parametrize("n,m_sub", [(512, 16), (1000, 33)])
def test_pq_adc_batch(rng, b, n, m_sub):
    k_codes = 16
    codes = jnp.asarray(rng.integers(0, k_codes, (n, m_sub)), jnp.uint8)
    luts = jnp.asarray(rng.random((b, m_sub, k_codes)), jnp.float32)
    want = ref.pq_adc_batch(codes, luts)
    for backend in ("pallas", "ref"):
        got = ops.pq_adc_batch(codes, luts, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # rows are bit-identical to a singleton batch of the same query
    got = ops.pq_adc_batch(codes, luts, backend="pallas")
    for bi in (0, b - 1):
        one = ops.pq_adc_batch(codes, luts[bi][None], backend="pallas")[0]
        np.testing.assert_array_equal(np.asarray(got[bi]), np.asarray(one))


def _batch_codebooks(rng, est_rows, k, m):
    cbs = [rb.build_codebook(jnp.asarray(e), k=k, m=m) for e in est_rows]
    d_min = jnp.stack([c.d_min for c in cbs])
    delta = jnp.stack([c.delta for c in cbs])
    ew = jnp.stack([c.ew_map for c in cbs])
    return d_min, delta, ew


@pytest.mark.parametrize("b", [1, 4, 8, 11])
@pytest.mark.parametrize("n", [512, 1000])
def test_bucket_hist_batch(rng, b, n):
    m = 64
    dists = np.asarray(rng.random((b, n)) * 10 + 1, np.float32)
    valid = rng.random((b, n)) < 0.9
    dists = np.where(valid, dists, np.inf).astype(np.float32)
    d_min, delta, ew = _batch_codebooks(rng, dists, k=min(n // 2, 400), m=m)
    for backend in ("pallas", "ref"):
        got_b, got_h = ops.bucket_hist_batch(
            jnp.asarray(dists), jnp.asarray(valid), d_min, delta, ew, m,
            backend=backend)
        want_b, want_h = ref.bucket_hist_batch(
            jnp.asarray(dists), jnp.asarray(valid), d_min, delta, ew, m)
        np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
        np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    # and each row agrees with a singleton batch of the same query
    got_b, got_h = ops.bucket_hist_batch(
        jnp.asarray(dists), jnp.asarray(valid), d_min, delta, ew, m,
        backend="pallas")
    for bi in range(b):
        srow, shist = _row0(ops.bucket_hist_batch(
            jnp.asarray(dists[bi:bi + 1]), jnp.asarray(valid[bi:bi + 1]),
            d_min[bi:bi + 1], delta[bi:bi + 1], ew[bi:bi + 1], m,
            backend="pallas"))
        np.testing.assert_array_equal(np.asarray(got_b[bi]), np.asarray(srow))
        np.testing.assert_array_equal(np.asarray(got_h[bi]),
                                      np.asarray(shist))


@pytest.mark.parametrize("b,n,d,m_sub", [(4, 512, 64, 16), (8, 768, 96, 24),
                                         (3, 512, 128, 32),
                                         (13, 512, 64, 16)])
def test_fused_scan_batch(rng, b, n, d, m_sub):
    k_codes, m = 16, 64
    codes = jnp.asarray(rng.integers(0, k_codes, (n, m_sub)), jnp.uint8)
    vectors = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    qs = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    valid = jnp.asarray(rng.random((b, n)) < 0.95)
    luts = jnp.asarray(rng.random((b, m_sub, k_codes)) * 2, jnp.float32)
    est_rows = np.stack([
        np.where(np.asarray(valid[i]),
                 np.sqrt(np.maximum(np.asarray(ref.pq_adc(codes, luts[i])),
                                    0.0)), np.inf)
        for i in range(b)])
    d_min, delta, ew = _batch_codebooks(rng, est_rows, k=n // 2, m=m)
    tau = jnp.asarray(rng.integers(0, m, b), jnp.int32)
    want = ref.fused_scan_batch(codes, vectors, valid, luts, qs, d_min,
                                delta, ew, m, tau)
    got = ops.fused_scan_batch(codes, vectors, valid, luts, qs, d_min,
                               delta, ew, m, tau, backend="pallas")
    names = ["est", "bucket", "hist", "early", "nmiss"]
    for name, g, w in zip(names, got, want):
        if name in ("bucket", "hist", "nmiss"):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
    # rows are bit-identical to a singleton batch of the same query
    for bi in (0, b - 1):
        sl = slice(bi, bi + 1)
        single = _row0(ops.fused_scan_batch(
            codes, vectors, valid[sl], luts[sl], qs[sl], d_min[sl],
            delta[sl], ew[sl], m, tau[sl], backend="pallas"))
        for name, g, one in zip(names, got, single):
            np.testing.assert_array_equal(np.asarray(g[bi]), np.asarray(one),
                                          err_msg=name)


@pytest.mark.parametrize("b,n,d", [(4, 512, 64), (9, 999, 96), (1, 256, 128),
                                   (24, 512, 128)])
def test_l2_exact_batch(rng, b, n, d):
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    qs = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    want = ref.l2_exact_batch(x, qs)
    for backend in ("pallas", "ref"):
        got = ops.l2_exact_batch(x, qs, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    # rows are bit-identical to a singleton batch of the same query
    got = ops.l2_exact_batch(x, qs, backend="pallas")
    for bi in (0, b - 1):
        one = ops.l2_exact_batch(x, qs[bi][None], backend="pallas")[0]
        np.testing.assert_array_equal(np.asarray(got[bi]), np.asarray(one))


def test_fused_scan_matches_search_semantics(rng):
    """The fused kernel's (est, hist) must agree with the core result-buffer
    pipeline so the searcher can swap implementations freely."""
    n, d, m_sub, m = 1024, 64, 16, 64
    k_codes = 16
    codes = jnp.asarray(rng.integers(0, k_codes, (n, m_sub)), jnp.uint8)
    vectors = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(d), jnp.float32)
    valid = jnp.ones((n,), bool)
    lut = jnp.asarray(rng.random((m_sub, k_codes)) * 2, jnp.float32)
    est = jnp.sqrt(jnp.maximum(ref.pq_adc(codes, lut), 0.0))
    cb = rb.build_codebook(est, k=256, m=m)
    _, bucket, hist, _, _ = _row0(ops.fused_scan_batch(
        codes, vectors, valid[None], lut[None], q[None], cb.d_min[None],
        cb.delta[None], cb.ew_map[None], m, jnp.int32(m)[None],
        backend="pallas"))
    core_hist = rb.histogram(rb.bucketize(cb, est), m, valid)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(core_hist))
    tau_k, _ = rb.threshold_bucket(jnp.asarray(hist), 256)
    tau_c, _ = rb.threshold_bucket(core_hist, 256)
    assert int(tau_k) == int(tau_c)
