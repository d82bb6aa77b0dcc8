"""``correct`` on a CPU-sized cell: sound runs pass, the control and
planted faults fail.

The harness runs whole, minus its look for a chip: corpus and index from
the seed, warm-up, the closed-loop window through ``serving.Server``, the
direct parity calls and the comparison with the reference.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from small import small_cell

CONFIGS = ("sift1m-ivfpq4", "sift1m-ivfrabitq")


def _run(cell, seed=3):
    return harness.run(cell, seed, 0.5, False, time.perf_counter(),
                       require_chip=False)


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    out = _run(small_cell(config), seed=2**33 + 7)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out["checks"]) == ["mean_recall", "median_dist_gap",
                                   "parity_mismatch"]


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_control_is_not_correct(seed):
    """The reference at three-pass bf16 precision in the program's place
    fails the distance check."""
    cell = small_cell()
    x, qs = harness.make_data(cell.config, seed)
    qs = np.asarray(qs)[:cell.traffic["sample_requests"]]
    numbers = harness.control_numbers(x, qs, cell.traffic["k"])
    checks = harness.judge(numbers, 0, cell.config["checks"])
    assert not all(c["ok"] for c in checks.values()), checks
    assert not checks["median_dist_gap"]["ok"]


def _broken(monkeypatch, alter):
    from repro.index import engine
    real = engine.SearchEngine.search_batch

    def search_batch(self, qs, pred_state=None):
        res = real(self, qs, pred_state=pred_state)
        return res._replace(**alter(res))
    monkeypatch.setattr(engine.SearchEngine, "search_batch", search_batch)


def _answer_altered(res):
    # every query's answer names the next corpus row instead of its own
    return {"ids": jnp.where(res.ids >= 0, res.ids + 1, res.ids)}


def _half_batch_left_out(res):
    b = res.ids.shape[0]
    keep = (jnp.arange(b) < max(1, b // 2))[:, None]
    return {"ids": jnp.where(keep, res.ids, -1),
            "dists": jnp.where(keep, res.dists, jnp.inf)}


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out])
def test_fault_is_not_correct(monkeypatch, config, fault):
    _broken(monkeypatch, fault)
    out = _run(small_cell(config))
    assert not out["correct"], out["checks"]


def test_no_chip_no_result(capsys):
    import run
    assert run.main(["--workload", "sift1m-ivfpq4.k100k", "--seed", "1",
                     "--seconds", "1"]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err
