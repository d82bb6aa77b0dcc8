"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip (no chip needed: the TPU compiler is installed, and it compiles
for a topology that is described, not attached).

Interpret mode cannot show what Mosaic refuses — unaligned blocks, slices
it cannot lower, scoped-VMEM overflow — so every kernel the served search
path runs is compiled here with ``interpret=False`` at real widths (SIFT's
d=128 and GIST's d=960), the serving batch (B=32) and a singleton batch,
TILE=256 (512 for the bucket histogram, its own tile) and the engine's
bucket count.  Shapes are padded exactly as the
``kernels.ops`` wrappers pad them before calling the kernels.

The topology is described inside a fixture (never at import): only one
process may load the TPU library at a time, and only the test worker that
runs this file should.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bucket_hist as bh
from repro.kernels import fused_scan as fs
from repro.kernels import l2_rerank as l2
from repro.kernels import pq_adc as adc
from repro.kernels import rabitq_fused as rqf
from repro.kernels import shard_collect as sc

TILE = 256
M_BUCKETS = 128          # SearchEngine's default bucket count
N_EW = 256               # equal-width bins of buffer.build_codebook
N_ROWS = 16 * TILE       # rows per compile; the grid length is not a shape
BUDGET = 2048            # sharded survivor budget
K_CODES = 16             # 4-bit PQ
I32, F32, BOOL = jnp.int32, jnp.float32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _padded(n: int, mult: int) -> int:
    return n + (-n) % mult


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


WIDTHS = [128, 960]
BATCHES = [1, 32]


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("d", WIDTHS)
def test_l2_exact_batch(one_chip, d, b):
    dp, bp = _padded(d, 128), _padded(b, l2.BQ)
    _compile(one_chip,
             lambda x, q: l2.l2_batch_pallas(x, q, tile=TILE,
                                             interpret=False),
             ((N_ROWS, dp), F32), ((bp, dp), F32))


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("d", WIDTHS)
def test_pq_adc_batch(one_chip, d, b):
    mp, bp = _padded(d // 4, adc.MC), _padded(b, adc.BQ)
    _compile(one_chip,
             lambda c, lut: adc.adc_batch_pallas(c, lut, tile=TILE,
                                                 interpret=False),
             ((N_ROWS, mp), I32), ((bp, mp, K_CODES), F32))


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("d", WIDTHS)
def test_fused_scan_batch(one_chip, d, b):
    dp, mp, bp = _padded(d, 128), _padded(d // 4, fs.MC), _padded(b, fs.BQ)

    def fn(codes, vecs, valid, luts, qs, d_min, delta, ew, tau):
        return fs.fused_scan_batch_pallas(
            codes, vecs, valid, luts, qs, d_min, delta, ew, M_BUCKETS, tau,
            tile=TILE, interpret=False)

    _compile(one_chip, fn, ((N_ROWS, mp), I32), ((N_ROWS, dp), F32),
             ((N_ROWS, bp), BOOL), ((bp, mp, K_CODES), F32), ((bp, dp), F32),
             ((bp,), F32), ((bp,), F32), ((bp, N_EW), I32), ((bp,), I32))


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("d", WIDTHS)
def test_fused_rabitq_scan_batch(one_chip, d, b):
    dp, bp = _padded(d, 128), _padded(b, rqf.BQ)

    def fn(codes, vecs, s2, norm_o, f_o, valid, nq, g, qs, d_min, delta, ew,
           tau):
        return rqf.fused_rabitq_scan_batch_pallas(
            codes, vecs, s2, norm_o, f_o, valid, nq, g, qs, d_min, delta, ew,
            M_BUCKETS, tau, d_logical=d, tile=TILE, interpret=False)

    _compile(one_chip, fn, ((N_ROWS, dp), F32), ((N_ROWS, dp), F32),
             ((N_ROWS,), F32), ((N_ROWS,), F32), ((N_ROWS,), F32),
             ((N_ROWS, bp), BOOL), ((N_ROWS, bp), F32), ((bp, dp), F32),
             ((bp, dp), F32), ((bp,), F32), ((bp,), F32), ((bp, N_EW), I32),
             ((bp,), I32))


@pytest.mark.parametrize("b", BATCHES)
def test_bucket_hist_batch(one_chip, b):
    bp = _padded(b, bh.BQ)
    _compile(one_chip,
             lambda dd, v, lo, dl, ew: bh.bucket_hist_batch_pallas(
                 dd, v, lo, dl, ew, M_BUCKETS, tile=bh.TILE, interpret=False),
             ((bp, N_ROWS), F32), ((bp, N_ROWS), BOOL), ((bp,), F32),
             ((bp,), F32), ((bp, N_EW), I32))


@pytest.mark.parametrize("b", BATCHES)
def test_shard_collect_batch(one_chip, b):
    bp = _padded(b, sc.BQ)
    _compile(one_chip,
             lambda dd, v, lo, dl, ew, tau: sc.shard_collect_batch_pallas(
                 dd, v, lo, dl, ew, M_BUCKETS, tau, BUDGET, tile=TILE,
                 interpret=False),
             ((bp, N_ROWS), F32), ((bp, N_ROWS), BOOL), ((bp,), F32),
             ((bp,), F32), ((bp, N_EW), I32), ((bp,), I32))


@pytest.mark.parametrize("b", BATCHES)
def test_spec_compact_batch(one_chip, b):
    _compile(one_chip,
             lambda bk, v, tau: sc.spec_compact_batch_pallas(
                 bk, v, tau, BUDGET, tile=TILE, interpret=False),
             ((b, N_ROWS), I32), ((b, N_ROWS), BOOL), ((b,), I32))
