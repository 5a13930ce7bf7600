"""Test helper: the CPU rehearsal of the benchmark at a tiny size.

`plain_root(tmp)` copies BENCHMARK.json and perf/ into `tmp`;
`tiny_root(tmp)` also ADDS tiny configurations and cells as new files and
new entries — the way a later PR adds a cell, editing no file that was
there. `run(root, cell)` runs one through
`perf.harness.runner.run_cell(platform=None)`; perf/run.py itself has no
way to leave the TPU. `reports(root, cell, kind)` says which metrics such a
run has to print.
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


def plain_root(tmp: str) -> str:
    """A copy of the benchmark as it is: BENCHMARK.json and perf/."""
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perf"), os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


NEXT_PR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "next_pr")


def add_next_pr(root: str) -> str:
    """Lays `fixtures/next_pr` over the tree at `root` the way the next
    `model_config` PR will: its files are new (one that is there already is
    an error), its entries are appended to BENCHMARK.json's lists, its cell's
    name to the end of accepted metrics' `workloads`. Returns the cell."""
    with open(os.path.join(NEXT_PR, "entries.json"), encoding="utf-8") as f:
        entries = json.load(f)
    overlay = os.path.join(NEXT_PR, "perf")
    for folder, _, files in os.walk(overlay):
        for name in files:
            src = os.path.join(folder, name)
            dst = os.path.join(root, "perf", os.path.relpath(src, overlay))
            if os.path.exists(dst):
                raise FileExistsError(f"{dst}: an addition may not replace a file")
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(src, dst)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    (cell,) = [w["name"] for w in entries["workloads"]]
    for kind, names in entries["appended_to_workloads_of"].items():
        for metric in bench[kind]:
            if metric["name"] in names:
                metric["workloads"].append(cell)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key].extend(entries[key])
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return cell


def only_grew(before, after, where="BENCHMARK.json"):
    """Problems (empty = none) with `after` as an addition to `before`: every
    entry that was there is there unchanged, lists only grew at their ends."""
    if isinstance(before, list) and isinstance(after, list):
        if len(after) < len(before):
            return [f"{where}: {len(before) - len(after)} entries went"]
        return [p for i, (a, b) in enumerate(zip(before, after))
                for p in only_grew(a, b, f"{where}[{i}]")]
    if isinstance(before, dict) and isinstance(after, dict):
        if set(before) != set(after):
            return [f"{where}: keys {sorted(set(before) ^ set(after))} came or went"]
        return [p for key in before
                for p in only_grew(before[key], after[key], f"{where}.{key}")]
    return [] if before == after else [f"{where}: {before!r} became {after!r}"]


def reports(root: str, cell: str, kind: str) -> set:
    """The metrics of `kind` that a run of `cell` off the TPU prints: what
    `<root>/BENCHMARK.json` lists for it — for a tiny cell, what it lists for
    the admitted cell it stands for — less what needs a TPU trace."""
    from perf.harness import layout

    entries = layout.metric_entries(layout.load_benchmark(root), kind, cell)
    return {m["name"] for m in entries if m["source"] != "device_trace"}


def tiny_root(tmp: str) -> str:
    root = plain_root(tmp)
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
