"""Fused shard-collect kernel (bucketize + histogram + speculative
compaction): Pallas (interpret=True) vs pure-jnp oracle, and the
three-tier speculative survivor selection in
``core.distributed.bbc_survivors_batch`` vs the unfused exact path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buffer as rb
from repro.core import distributed as dist
from repro.kernels import ops, ref


def _stream(rng, b, n, m, frac=0.7):
    d = (rng.standard_normal((b, n)).astype(np.float32)) ** 2 + 0.05
    valid = rng.random((b, n)) < frac
    d = np.where(valid, d, np.inf).astype(np.float32)
    dj, vj = jnp.asarray(d), jnp.asarray(valid)
    k_cb = max(8, min(n // 2, 512))
    cbs = jax.vmap(lambda s: rb.build_codebook(s, k=k_cb, m=m))(dj)
    return dj, vj, cbs


@pytest.mark.parametrize("b,n", [(8, 512), (4, 1024), (16, 256)])
@pytest.mark.parametrize("m", [32, 128])
def test_shard_collect_parity(rng, b, n, m):
    dj, vj, cbs = _stream(rng, b, n, m)
    budget = 48
    for tau_spec in (
        jnp.full((b,), -1, jnp.int32),                        # cold
        jnp.full((b,), m, jnp.int32),                         # everything
        jnp.asarray(rng.integers(-1, m + 1, b), jnp.int32),   # mixed
    ):
        want = ref.shard_collect_batch(dj, vj, cbs.d_min, cbs.delta,
                                       cbs.ew_map, m, tau_spec, budget)
        for backend in ("ref", "pallas"):
            got = ops.shard_collect_batch(dj, vj, cbs.d_min, cbs.delta,
                                          cbs.ew_map, m, tau_spec, budget,
                                          backend=backend)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("b,n,budget", [(8, 512, 32), (3, 768, 96)])
def test_spec_compact_parity(rng, b, n, budget):
    m = 64
    dj, vj, cbs = _stream(rng, b, n, m)
    bucket = ref.bucket_hist_batch(dj, vj, cbs.d_min, cbs.delta,
                                   cbs.ew_map, m)[0]
    tau_spec = jnp.asarray(rng.integers(-1, m + 1, b), jnp.int32)
    want = ref.spec_compact_batch(bucket, vj, tau_spec, budget)
    for backend in ("ref", "pallas"):
        got = ops.spec_compact_batch(bucket, vj, tau_spec, budget,
                                     backend=backend)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_spec_compact_stream_order_and_overflow(rng):
    """The buffer holds the FIRST ``budget`` at-or-below-tau lanes in
    stream order; the count is the true total (the overflow signal)."""
    b, n, m, budget = 4, 512, 16, 16
    dj, vj, cbs = _stream(rng, b, n, m, frac=0.9)
    bucket = ref.bucket_hist_batch(dj, vj, cbs.d_min, cbs.delta,
                                   cbs.ew_map, m)[0]
    tau_spec = jnp.full((b,), m, jnp.int32)
    pos, ok, cnt = ops.spec_compact_batch(bucket, vj, tau_spec, budget,
                                          backend="pallas")
    bucket_np, v_np = np.asarray(bucket), np.asarray(vj)
    for q in range(b):
        match = np.nonzero(v_np[q])[0]
        assert int(cnt[q]) == len(match)
        take = min(len(match), budget)
        np.testing.assert_array_equal(np.asarray(pos[q][:take]),
                                      match[:take])
        assert bool(np.all(np.asarray(ok[q][:take])))
        assert not np.any(np.asarray(ok[q][take:]))


def _idsets(pos, ok, n):
    return [set(np.asarray(p)[np.asarray(o)].tolist())
            for p, o in zip(pos, ok)]


@pytest.mark.parametrize("count,budget", [(60, 96), (60, 24), (400, 64)])
def test_bbc_survivors_spec_tiers_match_unfused(rng, count, budget):
    """Speculative compaction never changes the survivor id SET: covered
    (warm tau_pred at/above tau), undershoot (bounded correction pass),
    overflow and cold (exact fallback) all reproduce the unfused path,
    including the degenerate count > n_probed regime (tau == m)."""
    b, n, m = 8, 512, 32
    dj, vj, cbs = _stream(rng, b, n, m)
    bucket, hist = ref.bucket_hist_batch(dj, vj, cbs.d_min, cbs.delta,
                                         cbs.ew_map, m)
    key = jnp.where(vj, dj, jnp.inf)

    def run(spec):
        return dist.bbc_survivors_batch(bucket, key, vj, hist, count,
                                        budget, axis_name=(), spec=spec)

    pos0, ok0, tau0, _, _ = run(None)
    want = _idsets(pos0, ok0, n)
    taus = {
        "warm_exact": tau0,
        "cold": jnp.full((b,), -1, jnp.int32),
        "overshoot": jnp.minimum(tau0 + 3, m),
        "undershoot": jnp.maximum(tau0 - 1, -1),
        "max": jnp.full((b,), m, jnp.int32),
    }
    for name, ts in taus.items():
        _, _, spos, sok, scnt = ref.shard_collect_batch(
            dj, vj, cbs.d_min, cbs.delta, cbs.ew_map, m, ts, budget)
        pos1, ok1, tau1, _, _ = run((spos, sok, scnt, ts))
        np.testing.assert_array_equal(np.asarray(tau0), np.asarray(tau1))
        assert _idsets(pos1, ok1, n) == want, name


def test_budget_exceeds_stream_clamps(rng):
    """satellite fix: budget > stream length F no longer crashes top_k —
    outputs keep the static (B, budget) shape, padded invalid."""
    b, n, m, budget = 4, 128, 16, 512
    dj, vj, cbs = _stream(rng, b, n, m)
    bucket, hist = ref.bucket_hist_batch(dj, vj, cbs.d_min, cbs.delta,
                                         cbs.ew_map, m)
    key = jnp.where(vj, dj, jnp.inf)
    pos, ok, tau, n_surv, _ = dist.bbc_survivors_batch(
        bucket, key, vj, hist, 64, budget, axis_name=())
    assert pos.shape == (b, budget) and ok.shape == (b, budget)
    assert int(jnp.sum(ok)) == int(jnp.sum(n_surv))


HIER_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed as dist

    mesh = jax.make_mesh((2, 4), ("host", "model"))
    x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)

    def body(xs):
        s = dist.hier_psum(jnp.sum(xs, axis=0), ("host", "model"))
        (g,) = dist.gather_survivors(("host", "model"), xs)
        return s, g

    s, g = jax.shard_map(body, mesh=mesh,
                         in_specs=(P(("host", "model"), None),),
                         out_specs=(P(), P()), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(x.sum(axis=0)))
    # hierarchical gather is a permutation of the flat concat; every row
    # of x appears exactly once
    got = np.asarray(g).reshape(-1, 6)
    want = np.asarray(x)
    got_rows = {tuple(r) for r in got.tolist()}
    assert got_rows == {tuple(r) for r in want.tolist()}
    print("HIER_COLLECTIVES_OK")
    """
)


@pytest.mark.multidevice
def test_hierarchical_collectives_on_2d_mesh():
    """hier_psum / gather_survivors over a ("host", "model") 2-D mesh
    reduce and gather exactly (subprocess with 8 forced host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", HIER_SCRIPT], capture_output=True, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert "HIER_COLLECTIVES_OK" in out.stdout, (
        out.stdout[-2000:] + "\n" + out.stderr[-3000:])
