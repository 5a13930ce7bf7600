"""Where the benchmark's files are, found by the names in BENCHMARK.json.

A cell is `perf/cells/<cell>.json`; it names a configuration (its file is
the `file` of the `configs` entry), a traffic mix (`perf/traffic/<mix>.json`,
parameters for one general generator, `perf/generators/<generator>.py`) and
its chips. Which metrics a cell reports is read from BENCHMARK.json: an entry
of `end_to_end` / `per_layer` belongs to the cells its `workloads` key lists,
or to all when it has none. A metric is computed by the reader of its own
name, `perf/end_to_end/<name>.py` or `perf/layer_metrics/<name>.py`. So a
later PR adds a cell, a mix, a configuration or a metric by adding files
and appending entries; it edits no file that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(PERF_DIR)

READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class LayoutError(Exception):
    """BENCHMARK.json and the files under perf/ do not fit together."""


def read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise LayoutError(f"cannot read {path}: {e}") from e


def load_benchmark(root: str) -> Dict[str, Any]:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry.get("name") == name:
            return entry
    raise LayoutError(f"BENCHMARK.json has no {what} named {name!r}")


def load_cell(root: str, bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """The `workloads` entry merged with `perf/cells/<workload>.json`; the
    two must agree on configuration, traffic, chips and why."""
    entry = _named(bench["workloads"], workload, "workload")
    cell = read_json(os.path.join(root, "perf", "cells", workload + ".json"))
    for key in ("config", "traffic", "chips", "why"):
        if cell.get(key) != entry.get(key):
            raise LayoutError(
                f"perf/cells/{workload}.json says {key}={cell.get(key)!r}, "
                f"BENCHMARK.json says {entry.get(key)!r}")
    return {**cell, "name": workload}


def load_config(root: str, bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    entry = _named(bench["configs"], name, "configuration")
    return read_json(os.path.join(root, entry["file"]))


def load_traffic(root: str, name: str) -> Dict[str, Any]:
    mix = read_json(os.path.join(root, "perf", "traffic", name + ".json"))
    if "generator" not in mix or not isinstance(mix.get("params"), dict):
        raise LayoutError(
            f"perf/traffic/{name}.json needs 'generator' and 'params'")
    return mix


def resolve(root: str, workload: str):
    """(BENCHMARK.json, cell, configuration, traffic mix, the mix's params
    with the cell's laid over them) for one workload name."""
    bench = load_benchmark(root)
    cell = load_cell(root, bench, workload)
    config = load_config(root, bench, cell["config"])
    traffic = load_traffic(root, cell["traffic"])
    return bench, cell, config, traffic, {**traffic["params"], **cell.get("params", {})}


def metric_entries(bench: Dict[str, Any], kind: str, workload: str
                   ) -> List[Dict[str, Any]]:
    """The `kind` ('end_to_end' | 'per_layer') metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(root: str, directory: str, name: str):
    """`<root>/perf/<directory>/<name>.py`, imported under a name of its
    own — by path, so a copy of the tree with files added runs as it is."""
    path = os.path.join(root, "perf", directory, name + ".py")
    if not os.path.isfile(path):
        raise LayoutError(f"no such file: perf/{directory}/{name}.py")
    mod_name = f"_perf_{directory}_{name}_{abs(hash(os.path.abspath(path)))}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return module
