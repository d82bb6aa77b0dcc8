"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip (no chip needed: the TPU compiler is installed, and it compiles
for a topology that is described, not attached).

Interpret mode cannot show what Mosaic refuses — unaligned blocks, slices
it cannot lower, scoped-VMEM overflow — so every kernel the served search
path runs is compiled here with ``interpret=False`` at real widths (SIFT's
d=128, DEEP's d=96 and GIST's d=960), the serving batch (B=32) and a
singleton batch, TILE=256 (512 for the bucket histogram, its own tile) and
the engine's bucket count.  Shapes are padded exactly as the
``kernels.ops`` wrappers pad them before calling the kernels.

The topology is described inside a fixture (never at import): only one
process may load the TPU library at a time, and only the test worker that
runs this file should.
"""
import os
import re

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


WIDTHS = [96, 128, 960]
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


# --------------------------------------------------------------------------
# whole searchers: stage scopes survive the TPU compiler
# --------------------------------------------------------------------------

STAGES = {"bbc.route", "bbc.plan", "bbc.scan", "bbc.collect", "bbc.rerank",
          "bbc.final"}
SEARCH_OPS = ("fusion", "sort", "gather", "scatter", "custom-call")
# compiler-made custom calls (buffer allocation, a relayout): no source op
COMPILER_CALLS = ("AllocateBuffer", "ConcatBitcast")
KERNELS = ("fused_scan_batch.", "fused_rabitq_scan_batch.", "l2_exact_batch.")
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line)
    return comps


def _source_names(line: str, comps: dict) -> list[str]:
    """The op_name of an instruction; for one the compiler made without
    one (a fusion rooted in a bitcast, say), those of the ops inside it."""
    own = re.search(r'op_name="([^"]*)"', line)
    if own:
        return [own.group(1)]
    out, stack = [], _CALLS.findall(line)
    while stack:
        for inner in comps.get(stack.pop(), []):
            name = re.search(r'op_name="([^"]*)"', inner)
            out += [name.group(1)] if name else []
            stack += _CALLS.findall(inner)
    return out


def _unstaged(hlo: str) -> list[str]:
    """Fusions, sorts, gathers, scatters and custom calls outside the fused
    computations that lie outside every ``bbc.*`` stage.  Sorts, gathers,
    scatters, kernels and custom fusions (the emitters of gathers and
    scatters) need a stage of their own; a loop fusion the compiler rooted
    in an op with no source (a bitcast, say) is judged by the ops inside
    it; only known compiler-made calls and fusions have no source op."""
    comps = _computations(hlo)
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line for c in _CALLS.findall(line)}
    bad = []
    for cname, lines in comps.items():
        if cname in fused:
            continue
        for line in lines:
            inst = _INST.match(line)
            if not inst or inst.group(2) not in SEARCH_OPS:
                continue
            op = inst.group(2)
            target = re.search(r'custom_call_target="([^"]*)"', line)
            if op == "custom-call" and target and \
                    target.group(1) in COMPILER_CALLS:
                continue
            loop = op == "fusion" and "kind=kLoop" in line
            names = _source_names(line, comps) if loop else re.findall(
                r'op_name="([^"]*)"', line)
            if (names or not loop) and not (
                    names and all("/bbc." in n for n in names)):
                bad.append(inst.group(1))
    return bad


# IVF+PQ's (lists, n_probe, n_cand) over a 4096-lane stream: 2000 is its
# dense regime (4 * n_cand >= n_flat, full-width selection), 1000 its
# compaction path (1000 * 32 < 8 * 4096); over 128 lists n_cand 512 covers
# the ~256 probed lanes, so it is dense, and the final top-k runs over the
# 8 probed tiles of 128 lanes (a quarter of the stream)
PQ_CASES = {"ivfpq": (32, 8, 2000), "ivfpq_sparse": (32, 8, 1000),
            "ivfpq_probed": (128, 8, 512)}


@pytest.mark.parametrize("method", ["ivfpq", "ivfpq_sparse", "ivfrabitq",
                                    "ivfpq_probed"])
def test_searcher_stages_on_tpu(one_chip, monkeypatch, method):
    """The served searcher (``backend="pallas"``, ``fused=True``, d=128,
    B=16) compiled for the chip: every fusion, sort, gather, scatter and
    custom call that comes from the program lies in a ``bbc.*`` stage, all
    six stages are there, and the Pallas kernels keep their wrappers'
    names, which the benchmark's roofline readers match."""
    import numpy as np

    from repro.data import synthetic
    from repro.index import ivf as ivf_mod
    from repro.index import search
    from repro.kernels import ops
    d, b, n, k = 128, 16, 4096, 500
    c, n_probe, n_cand = PQ_CASES.get(method, (32, 8, None))
    x = jnp.asarray(synthetic.clustered(np.random.default_rng(0), n, d,
                                        n_centers=c))
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    qs = jax.ShapeDtypeStruct((b, d), F32, sharding=one_chip)
    jax.clear_caches()          # no CPU trace of a kernel wrapper is reused
    try:
        if method in PQ_CASES:
            index = search.build_pq_index(jax.random.key(0), x, c,
                                          n_sub=d // 4, n_bits=4, n_iter=2)
            lay = ivf_mod.flat_layout(index.ivf)
            assert search._pq_dense_regime(n_cand, n_probe, lay.n_flat, c) \
                == (method != "ivfpq_sparse")
            assert search._pq_probed_final(k, n_probe, index.ivf.cap,
                                           lay.n_flat) \
                == (method == "ivfpq_probed")
            lowered = search.ivf_pq_search_batch.lower(
                shapes(index), qs, shapes(lay), k=k, n_probe=n_probe,
                n_cand=n_cand, use_bbc=True, m=M_BUCKETS,
                backend="pallas", fused=True)
            kernels = {"fused_scan_batch."}
        else:
            index = search.build_rabitq_index(jax.random.key(0), x, c,
                                              n_iter=2)
            lay = ivf_mod.flat_layout(index.ivf)
            lowered = search.ivf_rabitq_search_batch.lower(
                shapes(index), qs, shapes(lay), k=k, n_probe=n_probe,
                use_bbc=True, m=M_BUCKETS, backend="pallas", fused=True,
                stream=shapes(search.rabitq_stream(index, lay)))
            kernels = {"fused_rabitq_scan_batch.", "l2_exact_batch."}
        hlo = lowered.compile().as_text()
    finally:
        jax.clear_caches()      # nor is this compile's trace reused on CPU
    assert "tpu_custom_call" in hlo
    assert _unstaged(hlo) == []
    assert set(re.findall(r"bbc\.[a-z]+", hlo)) == STAGES
    calls = [_INST.match(line).group(1) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert {k for k in KERNELS if any(c.startswith(k) for c in calls)} \
        == kernels
