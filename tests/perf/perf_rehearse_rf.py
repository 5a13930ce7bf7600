"""Test helper: the CPU rehearsal of the forest cell at a tiny size.

`tiny_root(tmp)` copies the benchmark (`perf_rehearse.plain_root`) and ADDS
a tiny configuration (d=64, 6 trees, 16 bins, depth 3) and its cell as new
files and appended entries, the way `perf_rehearse_logreg.tiny_root` adds
the tiny logistic cell; the cell reports what BENCHMARK.json lists for the
admitted cell it stands for. `run` is `perf_rehearse.run`."""

from __future__ import annotations

import json
import os

import perf_rehearse

ADMITTED = "rf_reg_d3000.levels_cached"
CELL = "tiny_rf.levels_cached"
SIZES = {"n_cols": 64, "num_trees": 6, "max_bins": 16, "max_depth": 3,
         "daemon_pass_cache_mb": 4, "forest_hist_budget_mb": 4}
PARAMS = {"batch_rows": 512, "cached_batches": 4, "partitions": 2, "compare_trees": 2}
CACHED_ROWS = PARAMS["batch_rows"] * PARAMS["cached_batches"]

run = perf_rehearse.run
reports = perf_rehearse.reports


def tiny_root(tmp: str) -> str:
    root = perf_rehearse.plain_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(root, "perf", "configs", "rf_reg_d3000.json"),
              encoding="utf-8") as f:
        config = {**json.load(f), **SIZES, "name": "tiny_rf"}
    path = "perf/configs/tiny_rf.json"
    with open(os.path.join(root, path), "w", encoding="utf-8") as f:
        json.dump(config, f)
    bench["configs"].append({"name": "tiny_rf", "source": "test", "file": path,
                             "reduced": [], "why": "CPU rehearsal"})
    cell = {"config": "tiny_rf", "traffic": "levels_cached", "chips": 1,
            "why": "CPU rehearsal", "params": PARAMS}
    with open(os.path.join(root, "perf", "cells", CELL + ".json"), "w",
              encoding="utf-8") as f:
        json.dump(cell, f)
    bench["workloads"].append({"name": CELL, **{k: cell[k] for k in (
        "config", "traffic", "chips", "why")}})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if ADMITTED in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root
