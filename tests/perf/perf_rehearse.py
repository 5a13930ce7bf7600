"""Test helper: the CPU rehearsal of the benchmark at a tiny size.

`tiny_root(tmp)` copies BENCHMARK.json and perf/ into `tmp` and ADDS tiny
configurations and cells as new files and new entries — the way a later PR
adds a cell, editing no file that was there. `run(root, cell)` runs one
through `perf.harness.runner.run_cell(platform=None)`; perf/run.py itself
has no way to leave the TPU.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIGS = {
    "tiny_pca": ("pca_d2048_k32", {"n_cols": 64, "k": 4}),
}
#: name: (the admitted cell whose metrics it reports, config, traffic,
#: chips, params laid over the mix's)
TINY_CELLS = {
    "tiny_pca.fold_resident": ("pca_d2048_k32.fold_resident", "tiny_pca",
                               "fold_resident", 1, {
        "global_batch_rows": 256, "ring_batches": 2, "folds_per_fit": 4}),
    "tiny_pca.fold_resident_x4": ("pca_d2048_k32.fold_resident_x4", "tiny_pca",
                                  "fold_resident_x4", 4, {
        "global_batch_rows": 512, "ring_batches": 2, "folds_per_fit": 4}),
    # a mix a later PR adds as a data file: the same generator, a deeper fit
    "tiny_pca.fold_resident_deep": ("pca_d2048_k32.fold_resident", "tiny_pca",
                                    "fold_resident_deep", 1, {}),
}
TINY_MIXES = {
    "fold_resident_deep": {"generator": "fold_resident", "params": {
        "global_batch_rows": 128, "ring_batches": 4, "folds_per_fit": 16,
        "trace_s": 1.0}},
}


def tiny_root(tmp: str) -> str:
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for name, (base, sizes) in TINY_CONFIGS.items():
        with open(os.path.join(root, "perf", "configs", base + ".json"),
                  encoding="utf-8") as f:
            config = {**json.load(f), **sizes, "name": name}
        path = f"perf/configs/{name}.json"
        with open(os.path.join(root, path), "w", encoding="utf-8") as f:
            json.dump(config, f)
        bench["configs"].append({"name": name, "source": "test", "file": path,
                                 "reduced": [], "why": "CPU rehearsal"})
    for name, mix in TINY_MIXES.items():
        with open(os.path.join(root, "perf", "traffic", name + ".json"), "w",
                  encoding="utf-8") as f:
            json.dump(mix, f)
    for name, (like, config, traffic, chips, params) in TINY_CELLS.items():
        cell = {"config": config, "traffic": traffic, "chips": chips,
                "why": "CPU rehearsal", "params": params}
        with open(os.path.join(root, "perf", "cells", name + ".json"), "w",
                  encoding="utf-8") as f:
            json.dump(cell, f)
        bench["workloads"].append({"name": name, **{k: cell[k] for k in (
            "config", "traffic", "chips", "why")}})
        for kind in ("end_to_end", "per_layer"):
            for metric in bench[kind]:
                if like in metric.get("workloads", ()):
                    metric["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def run(root: str, cell: str, seconds: float = 2.0, trace: bool = False,
        seed: int = 7):
    """(result object, the lines the run printed before it)."""
    from perf.harness import runner
    from spark_rapids_ml_tpu import config

    lines = []
    # On the TPU the finalize is float64 LAPACK on the host; take that path
    # here too (the device path builds a new `pca.finalize` jit every call).
    with config.option("finalize", "host"):
        result = runner.run_cell(root, cell, seed, seconds, trace, platform=None,
                                 say=lines.append)
    return result, lines
