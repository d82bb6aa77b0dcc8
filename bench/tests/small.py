"""A cell small enough for the CPU, shaped like the benchmark's cells."""
from __future__ import annotations

import copy
import json
import os
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_cell(config: str = "sift1m-ivfpq4", traffic: str = "closed16-k5k",
               k: int = 300) -> SimpleNamespace:
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "workloads", traffic + ".json")) as f:
        tr = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg.update(n=6000)
    cfg["queries"]["pool"] = 64
    cfg["index"].update(n_clusters=32, n_probe=24, max_batch=8)
    if "pq_m" in cfg["index"]:
        cfg["index"]["pq_m"] = cfg["d"] // 4
        cfg["index"]["n_cand"] = {str(k): 10 * k}
    tr = dict(tr, k=k, clients=8, sample_requests=12)
    return SimpleNamespace(name=f"small.{config}", chips=1, config=cfg,
                           traffic=tr, end_to_end=[], per_layer=[])
