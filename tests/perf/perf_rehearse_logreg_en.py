"""Test helper: the CPU rehearsal of the elastic-net logistic cell at a tiny
size.

`tiny_root(tmp)` copies the benchmark (`perf_rehearse.plain_root`) and ADDS
a tiny configuration (d=64, six passes, a regParam that selects columns at
2,048 rows) and its cell as new files and appended entries, the way
`perf_rehearse_logreg.tiny_root` adds the tiny L2 cell; the cell reports
what BENCHMARK.json lists for the admitted cell it stands for. `run` is
`perf_rehearse.run`."""

from __future__ import annotations

import json
import os

import perf_rehearse

ADMITTED = "logreg_en_d3000.prox_newton_cached"
CELL = "tiny_logreg_en.prox_newton_cached"
SIZES = {"n_cols": 64, "max_iter": 6, "daemon_pass_cache_mb": 4, "reg": 0.03}
PARAMS = {"batch_rows": 256, "cached_batches": 8, "partitions": 4, "trace_s": 1.0}
CACHED_ROWS = PARAMS["batch_rows"] * PARAMS["cached_batches"]

run = perf_rehearse.run
reports = perf_rehearse.reports


def tiny_root(tmp: str) -> str:
    root = perf_rehearse.plain_root(tmp)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(root, "perf", "configs", "logreg_en_d3000.json"),
              encoding="utf-8") as f:
        config = {**json.load(f), **SIZES, "name": "tiny_logreg_en"}
    path = "perf/configs/tiny_logreg_en.json"
    with open(os.path.join(root, path), "w", encoding="utf-8") as f:
        json.dump(config, f)
    bench["configs"].append({"name": "tiny_logreg_en", "source": "test", "file": path,
                             "reduced": [], "why": "CPU rehearsal"})
    cell = {"config": "tiny_logreg_en", "traffic": "prox_newton_cached", "chips": 1,
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
