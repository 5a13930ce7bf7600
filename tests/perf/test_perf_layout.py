"""BENCHMARK.json against the builder's contract, and every name in it
against the files under perf/."""

import json
import os
import re

import pytest

from perf.harness import layout

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_has_exactly_the_contracts_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert BENCH["command"] == ["python3", "perf/run.py"]
    assert BENCH["paths"] == ["perf", "tests/perf"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24 and 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[key]]
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200 for key in ("configs", "workloads")
               for e in BENCH[key])


def test_a_full_check_fits_its_time_with_all_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_cells_chips_and_the_share_of_four_chip_cells():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}, "a config no cell uses"


WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head|expan"
                   r"|n_cols|^k$|width|arrow_batch_rows")


def test_configurations_name_their_file_source_and_cuts():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(sources) == len(set(sources)), "two deployments need sources that differ"
    for c in BENCH["configs"]:
        assert c["file"].startswith("perf/configs/") and c["source"].startswith("https://")
        config = layout.read_json(os.path.join(ROOT, c["file"]))
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert sorted(c["reduced"]) == sorted(config["reduced"])
        assert not any(WIDTH.search(key) for key in c["reduced"]), "a width was cut"
        assert set(config["tolerances"]) <= set(config["tolerance_reasons"])
        assert config["guarantees"]


def test_published_widths_are_what_the_files_state():
    pca = layout.load_config(ROOT, BENCH, "pca_d2048_k32")
    assert (pca["n_cols"], pca["k"], pca["rows"]) == (2048, 32, 100_000_000)
    assert pca["arrow_batch_rows"] == 65536
    assert pca["tolerances"] == {"min_cos": 1 - 1e-4, "explained_variance_rel": 2.0**-9,
                                 "mean_abs": 2.0**-10}
    for name, rows_per_fit in (("pca_d2048_k32.fold_resident", 25_165_824),
                               ("pca_d2048_k32.fold_resident_x4", 100_663_296)):
        _, cell, _, _, p = layout.resolve(ROOT, name)
        assert p["folds_per_fit"] * p["global_batch_rows"] == rows_per_fit == cell["rows_per_fit"]
        # the shape the daemon puts on a chip at the documented Arrow batch
        assert p["global_batch_rows"] // cell["chips"] == pca["arrow_batch_rows"]
        assert p["folds_per_fit"] % p["ring_batches"] == 0


def test_every_resident_cell_fills_a_quarter_of_a_chip_with_rows_its_folds_read():
    """The contract's floor: 25% of a chip's memory, held by what the
    traffic uses. Here it is the ring, every batch of which each fit folds."""
    from perf.harness import device

    hbm = device.peaks_for("TPU v5 lite")["hbm_bytes"]
    for name in CELLS:
        _, cell, config, traffic, p = layout.resolve(ROOT, name)
        if traffic["generator"] != "fold_resident":
            continue  # a later generator fills the chip with something of its own
        ring = p["ring_batches"] * p["global_batch_rows"] * config["n_cols"] * 4
        assert 0.25 * 2**34 <= ring / cell["chips"] <= 0.6 * hbm


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_files_that_exist_and_matches_its_entry(cell_name):
    cell = layout.load_cell(ROOT, BENCH, cell_name)  # raises where they differ
    config = layout.load_config(ROOT, BENCH, cell["config"])
    traffic = layout.load_traffic(ROOT, cell["traffic"])
    generator = layout.load_module(ROOT, "generators", traffic["generator"])
    assert callable(generator.run)
    assert set(cell.get("params", {})) <= set(traffic["params"]), \
        "a cell overrides a parameter its traffic mix does not have"
    assert config["algo"] and config["n_cols"] > 0
    end_to_end = layout.metric_entries(BENCH, "end_to_end", cell_name)
    per_layer = layout.metric_entries(BENCH, "per_layer", cell_name)
    e2e_names = {m["name"] for m in end_to_end}
    assert "setup_s" in e2e_names and len(e2e_names) >= 2 and per_layer
    for kind, entries in (("end_to_end", end_to_end), ("per_layer", per_layer)):
        for m in entries:
            reader = layout.load_module(ROOT, layout.READER_DIRS[kind], m["name"])
            assert callable(reader.read) and reader.__doc__
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e_names for m in per_layer)


def test_a_cell_file_that_contradicts_benchmark_json_is_refused(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["chips"] = 4
    with pytest.raises(layout.LayoutError, match="chips"):
        layout.load_cell(ROOT, bench, bench["workloads"][0]["name"])
    with pytest.raises(layout.LayoutError, match="no workload"):
        layout.load_cell(ROOT, BENCH, "no_such.cell")
    with pytest.raises(layout.LayoutError, match="no such file"):
        layout.load_module(ROOT, "layer_metrics", "no_such_metric")


def test_metrics_follow_the_contract():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower", "higher")
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] == 0.1 and "workloads" not in setup[0]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    assert {"model_programs", "kernels", "collectives", "finalize", "device"} <= layers


def test_every_file_under_paths_has_a_plain_name_and_every_reader_is_listed():
    listed = {kind: {m["name"] for m in BENCH[kind]} for kind in ("end_to_end", "per_layer")}
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PLAIN_PATH.match(rel), rel
    for cell in os.listdir(os.path.join(ROOT, "perf", "cells")):
        assert cell[:-len(".json")] in CELLS, f"{cell} is in no workloads entry"
    used = {w["traffic"] for w in BENCH["workloads"]}
    generators = set()
    for mix in os.listdir(os.path.join(ROOT, "perf", "traffic")):
        assert mix[:-len(".json")] in used, f"{mix} is the mix of no cell"
        generators.add(layout.load_traffic(ROOT, mix[:-len(".json")])["generator"])
    here = {f[:-3] for f in os.listdir(os.path.join(ROOT, "perf", "generators"))
            if f.endswith(".py") and f != "__init__.py"}
    assert here == generators, "a generator no mix names"
    for kind, directory in layout.READER_DIRS.items():
        here = {f[:-3] for f in os.listdir(os.path.join(ROOT, "perf", directory))
                if f.endswith(".py") and f != "__init__.py"}
        assert here == listed[kind], "a reader without an entry, or the reverse"
