"""BENCHMARK.json against the builder's contract, and every name in it
against the files under perf/. The checks themselves are functions of a tree
(`contract.py`); here they run on the repo, and
`test_perf_rehearse_fold.py` runs them again on a copy to which the next PR's
files have been added. What is specific to the admitted PCA cells (the
published widths, the ring's size) is held here only."""

import json

import pytest

import contract
from perf.harness import layout

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELLS = contract.cells(BENCH)


def test_benchmark_json_has_exactly_the_contracts_keys_and_limits():
    contract.keys_and_limits(ROOT)


def _rename(key, index, name):
    def edit(bench):
        bench[key][index]["name"] = name
    return edit


@pytest.mark.parametrize("edit", [
    # no two of configs, workloads and metrics share a name, across the lists
    _rename("configs", 0, "fold_rows_per_s"),
    _rename("per_layer", 0, "pca_d2048_k32.fold_resident"),
    _rename("end_to_end", 0, "fold_device_ms"),
    # a name starts with a letter or a digit
    _rename("per_layer", 0, "_fold_device_ms"),
    _rename("workloads", 0, ".fold_resident"),
], ids=["config_like_a_metric", "metric_like_a_cell", "two_metrics", "underscore_first",
        "dot_first"])
def test_a_name_used_twice_or_badly_begun_is_refused(tmp_path, edit):
    bench = json.loads(json.dumps(BENCH))
    edit(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench), encoding="utf-8")
    with pytest.raises(AssertionError):
        contract.keys_and_limits(str(tmp_path))


def test_a_configuration_without_a_width_does_not_resolve(monkeypatch):
    config = dict(layout.load_config(ROOT, BENCH, "pca_d2048_k32"), n_cols=0)
    monkeypatch.setattr(layout, "load_config", lambda *a: config)
    with pytest.raises(AssertionError):
        contract.cell_resolves(ROOT, BENCH, CELLS[0])


def test_a_full_check_fits_its_time_with_all_24_cells():
    contract.a_full_check_fits_its_time_with_all_24_cells(ROOT)


def test_cells_chips_and_the_share_of_four_chip_cells():
    contract.chips_and_the_share_of_four_chip_cells(ROOT)


def test_configurations_name_their_file_source_and_cuts():
    contract.configurations_name_their_file_source_and_cuts(ROOT)


def test_published_widths_are_what_the_files_state():
    pca = layout.load_config(ROOT, BENCH, "pca_d2048_k32")
    assert (pca["n_cols"], pca["k"], pca["rows"]) == (2048, 32, 100_000_000)
    assert pca["arrow_batch_rows"] == 65536
    assert pca["tolerances"] == {"min_cos": 1 - 2.0**-25, "explained_variance_rel": 2.0**-12,
                                 "mean_abs": 2.0**-14}
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
    contract.cell_resolves(ROOT, BENCH, cell_name)


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
    contract.metrics_follow_the_contract(ROOT)


def test_every_file_under_paths_has_a_plain_name_and_every_reader_is_listed():
    contract.every_file_has_a_plain_name_and_every_reader_is_listed(ROOT)


def test_the_small_fold_cell_is_the_one_chip_fit_in_16384_row_folds():
    """`pca_d2048_k32.fold_resident_small` (PR 27): the one-chip cell's ring
    and fit, in the folds a Spark job at its default Arrow batch sends."""
    _, big, _, _, p1 = layout.resolve(ROOT, "pca_d2048_k32.fold_resident")
    _, cell, _, traffic, p = layout.resolve(ROOT, "pca_d2048_k32.fold_resident_small")
    assert (cell["chips"], traffic["generator"]) == (1, "fold_resident")
    assert p == {"global_batch_rows": 16384, "ring_batches": 64, "folds_per_fit": 1536,
                 "trace_s": 5.0}
    assert p["folds_per_fit"] * p["global_batch_rows"] == cell["rows_per_fit"] \
        == big["rows_per_fit"] == 25_165_824
    assert p["ring_batches"] * p["global_batch_rows"] \
        == p1["ring_batches"] * p1["global_batch_rows"]
    assert p["folds_per_fit"] % p["ring_batches"] == 0
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, cell["name"])}
                for kind in ("end_to_end", "per_layer")}
    # not `fit_rows_per_s` (no finalize is timed apart here), and so none of the metrics that move it
    assert reported == {"end_to_end": {"fold_rows_per_s", "setup_s"},
                        "per_layer": {"fold_device_ms", "fold_roofline", "fold_dispatch_ms",
                                      "compiles_in_window"}}
