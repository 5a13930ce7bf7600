"""The benchmark's contract as functions of a tree.

`check(root)` holds BENCHMARK.json against the builder's contract and every
name in it against the files under `<root>/perf/`. The tests run it on the
repo (`layout.REPO_ROOT`) and on a copy to which the next PR's files and
entries have been added (`test_perf_rehearse_fold.py`): a check that would
refuse an addition made by the rules fails there, in the PR that wrote it.

Nothing here knows how many metrics or cells there are, or in what order:
an entry that is in BENCHMARK.json is held by its name.
"""

from __future__ import annotations

import os
import re

from perf.harness import layout

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head|expan"
                   r"|n_cols|^k$|width|arrow_batch_rows")

ONE_CHIP = "pca_d2048_k32.fold_resident"
#: the finalize split and the dispatch counter (PR 24): each names at least
#: the one-chip cell
SPLIT = {"finalize_fetch_ms", "finalize_center_ms", "finalize_lapack_ms",
         "finalize_post_ms", "finalize_self_ms", "fold_dispatch_ms"}
#: every per-layer metric the benchmark was accepted with: name →
#: (source, layer, moves, unit, better). A later PR may append cells to an
#: entry's `workloads` and entries to the list; it may not change these.
ACCEPTED_PER_LAYER = {
    "fold_device_ms": ("device_trace", "kernels", "fold_rows_per_s", "ms", "lower"),
    "fold_roofline": ("device_trace", "kernels", "fold_rows_per_s", "%", "higher"),
    "collective_ms_per_fold": ("device_trace", "collectives", "fold_rows_per_s", "ms",
                               "lower"),
    "collective_exposed_share": ("device_trace", "collectives", "fold_rows_per_s", "%",
                                 "lower"),
    # what the finalize moves end to end is the whole fit (PR 35: `finalize_s`
    # itself, which no bound of 10% holds from run to run, stands per layer)
    "finalize_eig_ms": ("program_span", "finalize", "fit_rows_per_s", "ms", "lower"),
    "finalize_s": ("host_clock", "finalize", "fit_rows_per_s", "s", "lower"),
    "device_idle_share": ("device_trace", "device", "fit_rows_per_s", "%", "lower"),
    "compiles_in_window": ("program_counter", "model_programs", "setup_s", "programs",
                           "lower"),
    **{name: ("program_span", "finalize", "fit_rows_per_s", "ms", "lower")
       for name in SPLIT - {"fold_dispatch_ms"}},
    "fold_dispatch_ms": ("program_counter", "model_programs", "fold_rows_per_s", "ms",
                         "lower"),
    # the cells on a daemon job's cached pass (PRs 28, 32), as PR 35 left
    # them: their end-to-end rate is `pass_rows_per_s`
    "pass_cached_share": ("program_counter", "daemon", "pass_rows_per_s", "%", "higher"),
    "rescan_dispatch_ms": ("program_span", "daemon", "pass_rows_per_s", "ms", "lower"),
    "lloyd_boundary_ms": ("program_span", "daemon", "pass_rows_per_s", "ms", "lower"),
    "lloyd_fold_dispatch_ms": ("program_counter", "model_programs", "pass_rows_per_s",
                               "ms", "lower"),
    "newton_boundary_ms": ("program_span", "daemon", "pass_rows_per_s", "ms", "lower"),
    "newton_solve_ms": ("program_span", "model_programs", "pass_rows_per_s", "ms", "lower"),
    "newton_fold_dispatch_ms": ("program_counter", "model_programs", "pass_rows_per_s",
                                "ms", "lower"),
    "pass_fold_device_ms": ("device_trace", "kernels", "pass_rows_per_s", "ms", "lower"),
    "pass_fold_roofline": ("device_trace", "kernels", "pass_rows_per_s", "%", "higher"),
    "median_pass_rows_per_s": ("host_clock", "daemon", "pass_rows_per_s", "rows/s",
                               "higher"),
    "late_pass_share": ("host_clock", "daemon", "pass_rows_per_s", "%", "lower"),
}


def cells(bench):
    return [w["name"] for w in bench["workloads"]]


def keys_and_limits(root):
    bench = layout.load_benchmark(root)
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert bench["command"] == ["python3", "perf/run.py"]
    assert bench["paths"] == ["perf", "tests/perf"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["configs"]) <= 24 and 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in bench[key]]
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    assert all(NAME.match(w[key]) for w in bench["workloads"] for key in ("config", "traffic"))
    for key in ("configs", "workloads"):
        for e in bench[key]:
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]


def a_full_check_fits_its_time_with_all_24_cells(root):
    bench = layout.load_benchmark(root)
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def chips_and_the_share_of_four_chip_cells(root):
    bench = layout.load_benchmark(root)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}, "a config no cell uses"


def configurations_name_their_file_source_and_cuts(root):
    bench = layout.load_benchmark(root)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    sources = [c["source"] for c in bench["configs"]]
    assert len(sources) == len(set(sources)), "two deployments need sources that differ"
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perf/configs/") and c["source"].startswith("https://")
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        config = layout.read_json(os.path.join(root, c["file"]))
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert config["algo"] and NAME.match(config["algo"])
        assert sorted(c["reduced"]) == sorted(config["reduced"])
        assert not any(WIDTH.search(key) for key in c["reduced"]), "a width was cut"
        assert set(config["tolerances"]) <= set(config["tolerance_reasons"])
        assert config["guarantees"]


def cell_resolves(root, bench, cell_name):
    """One cell: its files exist and agree with its entry, its readers load,
    and it reports `setup_s`, another end-to-end metric and a per-layer one."""
    cell = layout.load_cell(root, bench, cell_name)  # raises where they differ
    config = layout.load_config(root, bench, cell["config"])
    traffic = layout.load_traffic(root, cell["traffic"])
    generator = layout.load_module(root, "generators", traffic["generator"])
    assert callable(generator.run)
    assert set(cell.get("params", {})) <= set(traffic["params"]), \
        "a cell overrides a parameter its traffic mix does not have"
    assert config["algo"] and config["n_cols"] > 0
    end_to_end = layout.metric_entries(bench, "end_to_end", cell_name)
    per_layer = layout.metric_entries(bench, "per_layer", cell_name)
    e2e_names = {m["name"] for m in end_to_end}
    assert "setup_s" in e2e_names and len(e2e_names) >= 2 and per_layer
    for kind, entries in (("end_to_end", end_to_end), ("per_layer", per_layer)):
        for m in entries:
            reader = layout.load_module(root, layout.READER_DIRS[kind], m["name"])
            assert callable(reader.read) and reader.__doc__
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e_names for m in per_layer), \
        [m["name"] for m in per_layer if m["moves"] not in e2e_names]
    if any(m["name"] == "fold_roofline" for m in per_layer):
        # the fold's cost is a file named for the configuration's `algo`
        assert callable(layout.load_module(root, "costs", config["algo"]).fold)
        assert config["fold_program"]


def every_cell_resolves(root):
    bench = layout.load_benchmark(root)
    for cell_name in cells(bench):
        cell_resolves(root, bench, cell_name)


def metrics_follow_the_contract(root):
    bench = layout.load_benchmark(root)
    names = cells(bench)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower", "higher")
        assert UNIT.match(m["unit"])
        assert set(m.get("workloads", names)) <= set(names)
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] == 0.1 and "workloads" not in setup[0]
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
        assert m["better"] in ("lower", "higher") and UNIT.match(m["unit"])
        assert set(m.get("workloads", names)) <= set(names)
        assert len(m.get("workloads", [])) == len(set(m.get("workloads", [])))
        assert 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    assert {"model_programs", "kernels", "collectives", "finalize", "device"} <= layers


def the_accepted_per_layer_metrics_are_what_they_were(root):
    """By name, not by place: each exists once, with the source, layer,
    `moves`, unit and direction it was accepted with, and each of the
    finalize split's six is still read in the one-chip cell. Which other
    cells list them, where they stand and what follows them is free."""
    bench = layout.load_benchmark(root)
    for name, fields in ACCEPTED_PER_LAYER.items():
        found = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(found) == 1, f"{name}: {len(found)} entries"
        m = found[0]
        assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == fields, name
        if name in SPLIT:
            assert ONE_CHIP in m["workloads"], name


def every_file_has_a_plain_name_and_every_reader_is_listed(root):
    bench = layout.load_benchmark(root)
    names = cells(bench)
    listed = {kind: {m["name"] for m in bench[kind]} for kind in ("end_to_end", "per_layer")}
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), root)
                assert PLAIN_PATH.match(rel), rel
    for cell in os.listdir(os.path.join(root, "perf", "cells")):
        assert cell[:-len(".json")] in names, f"{cell} is in no workloads entry"
    used = {w["traffic"] for w in bench["workloads"]}
    generators = set()
    for mix in os.listdir(os.path.join(root, "perf", "traffic")):
        assert mix[:-len(".json")] in used, f"{mix} is the mix of no cell"
        generators.add(layout.load_traffic(root, mix[:-len(".json")])["generator"])
    assert _modules(root, "generators") == generators, "a generator no mix names"
    algos = {layout.read_json(os.path.join(root, c["file"]))["algo"]
             for c in bench["configs"]}
    assert _modules(root, "costs") <= algos, "a cost file of no configuration's algo"
    for kind, directory in layout.READER_DIRS.items():
        assert _modules(root, directory) == listed[kind], \
            "a reader without an entry, or the reverse"


def _modules(root, directory):
    return {f[:-3] for f in os.listdir(os.path.join(root, "perf", directory))
            if f.endswith(".py") and f != "__init__.py"}


CHECKS = (
    keys_and_limits,
    a_full_check_fits_its_time_with_all_24_cells,
    chips_and_the_share_of_four_chip_cells,
    configurations_name_their_file_source_and_cuts,
    every_cell_resolves,
    metrics_follow_the_contract,
    the_accepted_per_layer_metrics_are_what_they_were,
    every_file_has_a_plain_name_and_every_reader_is_listed,
)


def check(root):
    """Every check, on the tree at `root`; the first that fails raises."""
    for one in CHECKS:
        one(root)

