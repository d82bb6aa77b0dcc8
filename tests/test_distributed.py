"""Distributed BBC search: shard_map correctness on a host-device mesh.

Runs in a subprocess with XLA_FLAGS forcing 8 host devices so the single-CPU
test environment can exercise real psum/all_gather lowering (the 512-device
production mesh is exercised by launch/dryrun.py).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from functools import partial
    shard_map = partial(jax.shard_map, check_vma=False)

    from repro.core import buffer as rb
    from repro.core import distributed as dist

    rng = np.random.default_rng(0)
    n_shards, per_shard, k = 8, 4096, 777
    n = n_shards * per_shard
    q = rng.standard_normal(64).astype(np.float32)
    x = rng.standard_normal((n, 64)).astype(np.float32)
    d = np.linalg.norm(x - q, axis=1).astype(np.float32)
    d += rng.random(n).astype(np.float32) * 1e-5
    ids = np.arange(n, dtype=np.int32)
    valid = np.ones(n, bool); valid[:100] = False
    dv = np.where(valid, d, np.inf).astype(np.float32)

    cb = rb.build_codebook(jnp.asarray(dv[: 4 * per_shard]), k=k, m=128)
    mesh = jax.make_mesh((n_shards,), ("model",))

    def body(ld, li, lv):
        r = dist.bbc_shard_search(ld, li, lv, cb, k=k, n_shards=n_shards)
        return r.topk_dists, r.topk_ids

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("model"), P("model"), P("model")),
        out_specs=(P(), P()),
    )
    got_d, got_i = jax.jit(fn)(jnp.asarray(dv), jnp.asarray(ids), jnp.asarray(valid))
    oracle = np.sort(d[valid])[:k]
    np.testing.assert_allclose(np.sort(np.asarray(got_d)), oracle, rtol=1e-6)
    assert set(np.asarray(got_i).tolist()) == set(np.argsort(dv)[:k].tolist())

    # naive baseline agrees too
    def body2(ld, li, lv):
        return dist.naive_shard_search(ld, li, lv, k=k)
    fn2 = shard_map(body2, mesh=mesh,
                    in_specs=(P("model"), P("model"), P("model")),
                    out_specs=(P(), P()))
    nd, ni = jax.jit(fn2)(jnp.asarray(dv), jnp.asarray(ids), jnp.asarray(valid))
    np.testing.assert_allclose(np.sort(np.asarray(nd)), oracle, rtol=1e-6)

    # cost model sanity: BBC moves far fewer bytes than naive for large k
    cm = dist.collective_cost_model(k=100_000, m=128, n_shards=16)
    assert cm["ratio"] > 4.0

    # shard_rows: row-split replicated work == running it replicated, for
    # row counts both divisible by S and requiring wrap padding, and for
    # pytree (tuple) outputs
    for b in (16, 11, 3):
        a = jnp.asarray(rng.standard_normal((b, 97)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((b, 97)).astype(np.float32))

        def rowfn(x2, y2):
            s = jnp.sort(x2, axis=1)
            return s, jnp.sum(x2 * y2, axis=1)

        def body3(x2, y2):
            return dist.shard_rows("model", (n_shards,), rowfn, x2, y2)

        fn3 = shard_map(body3, mesh=mesh, in_specs=(P(), P()),
                        out_specs=(P(), P()))
        gs, gr = jax.jit(fn3)(a, w)
        es, er = rowfn(a, w)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(es), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(gr), np.asarray(er), rtol=1e-5)
    print("DIST_OK")
    """
)


@pytest.mark.multidevice
def test_distributed_bbc_search():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "DIST_OK" in out.stdout, out.stderr[-3000:]
