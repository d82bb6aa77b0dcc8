"""The program's own profiler instrumentation.

Stage scopes: the batched searchers wrap each stage in ``jax.named_scope``,
so every op of the compiled search program carries its stage
(``ALL``) in its HLO ``op_name`` metadata, on every branch.

Serving spans: ``Server`` marks each batch's host steps with
``jax.profiler.TraceAnnotation`` spans that share the batch's ``batch_id``;
they appear in a recorded profile and change no outcome.
"""
import glob
import os
import re
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rerank
from repro.data import synthetic
from repro.index import ivf as ivf_mod
from repro.index import search
from repro.serving import queue as rq
from repro.serving import server as sv
from repro.serving.state import ServingState

N, D, C = 2000, 32, 16
K, N_PROBE, N_CAND, M = 50, 4, 200, 128
N_CAND_DENSE = 600       # 4 * N_CAND_DENSE >= n_flat: the dense regime
ALL = {"bbc.route", "bbc.plan", "bbc.scan", "bbc.collect", "bbc.rerank",
       "bbc.final"}
SPANS = ("serving.assemble", "serving.dispatch", "serving.wait",
         "serving.finish", "serving.fetch", "serving.trim")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    x = jnp.asarray(synthetic.clustered(rng, N, D, n_centers=C))
    qs = jnp.asarray(synthetic.queries_from(rng, np.asarray(x), 4))
    pq = search.build_pq_index(jax.random.key(0), x, C, n_iter=2)
    rbq = search.build_rabitq_index(jax.random.key(0), x, C, n_iter=2)
    return dict(x=x, qs=qs, pq=pq, rq=rbq,
                lay_pq=ivf_mod.flat_layout(pq.ivf),
                lay_rq=ivf_mod.flat_layout(rbq.ivf))


def _pq(d, n_cand=N_CAND, **kw):
    return search.ivf_pq_search_batch.lower(
        d["pq"], d["qs"], d["lay_pq"], k=K, n_probe=N_PROBE, n_cand=n_cand,
        m=M, **kw)


def _rq(d, **kw):
    return search.ivf_rabitq_search_batch.lower(
        d["rq"], d["qs"], d["lay_rq"], k=K, n_probe=N_PROBE, m=M, **kw)


def _ivf(d, **kw):
    return search.ivf_search_batch.lower(
        d["pq"].ivf, d["x"], d["qs"], d["lay_pq"], k=K, n_probe=N_PROBE,
        m=M, **kw)


def _pred():
    return rerank.predictor_init(M)


# (lowering, the stages its branch has)
BRANCHES = {
    "pq.bbc": (lambda d: _pq(d, use_bbc=True), ALL),
    "pq.bbc_fused": (lambda d: _pq(d, use_bbc=True, fused=True), ALL),
    "pq.bbc_fused_dense": (lambda d: _pq(d, n_cand=N_CAND_DENSE,
                                         use_bbc=True, fused=True), ALL),
    "pq.no_bbc": (lambda d: _pq(d), ALL),
    "pq.predictive": (lambda d: _pq(d, use_bbc=True, pred_state=_pred()),
                      ALL),
    "pq.predictive_fused": (lambda d: _pq(d, use_bbc=True, fused=True,
                                          pred_state=_pred()), ALL),
    "rabitq.bbc_fused": (lambda d: _rq(d, use_bbc=True), ALL),
    "rabitq.two_phase": (lambda d: _rq(d, use_bbc=True, fused=False), ALL),
    "rabitq.no_bbc": (lambda d: _rq(d),
                      {"bbc.route", "bbc.scan", "bbc.collect",
                       "bbc.rerank"}),
    "rabitq.predictive": (lambda d: _rq(d, use_bbc=True,
                                        pred_state=_pred()), ALL),
    "ivf.bbc": (lambda d: _ivf(d, use_bbc=True),
                {"bbc.route", "bbc.scan", "bbc.collect", "bbc.final"}),
    "ivf.predictive": (lambda d: _ivf(d, use_bbc=True, pred_state=_pred()),
                       {"bbc.route", "bbc.plan", "bbc.scan", "bbc.collect",
                        "bbc.final"}),
}


def _op_names(hlo: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_every_op_of_each_branch_carries_a_stage(data, branch):
    lower, stages = BRANCHES[branch]
    names = _op_names(lower(data).compile().as_text())
    seen = {s for n in names for s in re.findall(r"bbc\.[a-z]+", n)}
    assert seen == stages
    # parameters are named by their argument path; every op the program
    # traced (named from the jit root) sits inside a stage
    traced = [n for n in names if n.startswith("jit(")]
    assert traced and all("/bbc." in n for n in traced), \
        sorted({n for n in traced if "/bbc." not in n})[:5]


# --------------------------------------------------------------------------
# serving spans
# --------------------------------------------------------------------------

CEILS, BATCH = (64, 128), 4


def _serve(pq_index, qs, log_dir=None):
    trace = rq.make_trace(np.random.default_rng(5), qs, (50, 120),
                          rate=500.0, deadline=30.0, n_probe=N_PROBE)
    srv = sv.Server(ServingState(pq_index, use_bbc=True), CEILS, BATCH,
                    service_time_fn=lambda b: 0.01)
    srv.warmup(trace)
    if log_dir is None:
        return srv.run_trace(trace, warmup=False)
    with jax.profiler.trace(log_dir):
        return srv.run_trace(trace, warmup=False)


def _read_spans(log_dir: str) -> list[tuple]:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("serving.")]


@pytest.fixture(scope="module")
def served(data, tmp_path_factory):
    rng = np.random.default_rng(1)
    qs = synthetic.queries_from(rng, np.asarray(data["x"]), 24)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    return dict(off=_serve(data["pq"], qs),
                on=_serve(data["pq"], qs, log_dir),
                spans=_read_spans(log_dir))


def test_serving_spans_share_their_batch_id(served):
    by_batch = defaultdict(lambda: defaultdict(list))
    for name, a, b, stats in served["spans"]:
        assert stats["bucket_k"] in CEILS
        by_batch[stats["batch_id"]][name].append((a, b, stats))
    assert {n for spans in by_batch.values() for n in spans} == set(SPANS)
    finished = [bid for bid, spans in by_batch.items()
                if "serving.finish" in spans]
    completed = sum(o.completed for o in served["on"])
    assert completed and sum(
        by_batch[bid]["serving.dispatch"][0][2]["n_real"]
        for bid in finished) == completed
    for bid in finished:
        spans = by_batch[bid]
        # one of each per batch, all of one bucket
        assert sorted(spans) == sorted(SPANS)
        assert all(len(v) == 1 for v in spans.values())
        assert len({v[0][2]["bucket_k"] for v in spans.values()}) == 1
        (fa, fb, _), = spans["serving.finish"]
        # fetch, then trim, both inside finish
        (ga, gb, _), = spans["serving.fetch"]
        (ta, tb, _), = spans["serving.trim"]
        assert fa <= ga <= gb <= ta <= tb <= fb
        # the batch is assembled, dispatched, waited on, then finished
        assert (spans["serving.assemble"][0][0]
                <= spans["serving.dispatch"][0][0]
                <= spans["serving.wait"][0][0] <= fa)
        assert 1 <= spans["serving.dispatch"][0][2]["n_real"] <= BATCH


def test_outcomes_identical_with_the_profiler_on(served):
    off, on = served["off"], served["on"]
    assert len(off) == len(on) and any(o.completed for o in on)
    for a, b in zip(off, on):
        assert (a.request.rid, a.status, a.bucket, a.t_done,
                a.k_effective) == (b.request.rid, b.status, b.bucket,
                                   b.t_done, b.k_effective)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)


# --------------------------------------------------------------------------
# build spans
# --------------------------------------------------------------------------

BUILD_SPANS = {"index.kmeans": {"rows", "chunks", "n_clusters"},
               "index.assign": {"rows", "chunks", "n_clusters"},
               "index.pack": {"rows", "n_clusters", "cap"},
               "pq.train": {"rows", "chunks", "n_clusters"},
               "pq.encode": {"rows", "chunks"}}


def _read_build_spans(log_dir: str) -> list[tuple]:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.split(".")[0] in ("index", "pq")]


@pytest.mark.parametrize("method", ["ivfpq", "ivfrabitq"])
def test_build_phases_carry_spans_under_index_build(data, tmp_path, method):
    """A profiled build marks each phase with a host span nested under
    ``index.build``, carrying its counts."""
    x = data["x"]
    with jax.profiler.trace(str(tmp_path)):
        if method == "ivfpq":
            index = search.build_pq_index(jax.random.key(3), x, C, n_iter=2)
        else:
            index = search.build_rabitq_index(jax.random.key(3), x, C,
                                              n_iter=2)
    spans = _read_build_spans(str(tmp_path))
    (_, a, b, top), = [s for s in spans if s[0] == "index.build"]
    assert top["rows"] == N and top["n_clusters"] == C
    want = (BUILD_SPANS if method == "ivfpq" else
            {n: v for n, v in BUILD_SPANS.items() if n.startswith("index")})
    inner = {name: stats for name, *_, stats in spans if name in want}
    assert set(inner) == set(want)
    for name, sa, sb, stats in spans:
        if name in want:
            assert a <= sa <= sb <= b, name
            assert want[name] <= set(stats), (name, stats)
            assert stats["rows"] == N
    assert inner["index.pack"]["cap"] == index.ivf.cap
    assert inner["index.kmeans"]["n_clusters"] == C
    assert inner["index.kmeans"]["chunks"] == 1
    if method == "ivfpq":
        assert inner["pq.train"]["n_clusters"] == 16
