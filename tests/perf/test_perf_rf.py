"""The forest cell `rf_reg_d3000.levels_cached` (PR 36): its files and
entries, the plain reference against a scatter-add written out and against
the program (through a CPU rehearsal of a tiny cell, end to end and traced),
the whole-fits-only rule of `obs.passes`, planted faults through whole
rehearsal runs, and the bfloat16 control at a size a test can hold."""

import importlib.util
import os
import re

import numpy as np
import pytest

import contract
import perf_rehearse_rf as rehearse
from perf.harness import agree_rf, cost, layout, observe, rf_data
from perf.reference import control_rf
from perf.reference import rf as ref_rf

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELL = "rf_reg_d3000.levels_cached"
NEW_PER_LAYER = {"forest_boundary_ms", "forest_score_ms", "forest_fold_dispatch_ms",
                 "levels_root_pass_ms", "levels_deepest_pass_ms"}
#: what the cell lists of the cached cells' metrics: a fit's levels are not
#: equal passes, so the two pass statistics that assume they are stay out
CACHED_PER_LAYER = {"pass_cached_share", "rescan_dispatch_ms", "pass_fold_device_ms",
                    "pass_fold_roofline"}
COMPARED = {"count_mismatch", "rows_miscounted", "fits_differ", "hist_rel", "split_gain_rel",
            "split_equal_share", "leaf_rel", "pred_rel", "rows_refed_in_window",
            "compiles_in_window"}


@pytest.fixture(scope="module")
def config():
    return layout.load_config(ROOT, BENCH, "rf_reg_d3000")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(tmp_path_factory.mktemp("rf"))


def _control_lines(root, seed, with_reference=False):
    spec = importlib.util.spec_from_file_location(
        "_control_rf", os.path.join(root, "perf", "control_rf.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.lines(root, rehearse.CELL, seed, lambda m: None, with_reference)


def test_the_cell_is_the_deployment_cut_to_one_chips_rows(config):
    contract.check(ROOT)
    _, cell, cfg, traffic, p = layout.resolve(ROOT, CELL)
    assert cfg == config and traffic["generator"] == "levels_cached" and cell["chips"] == 1
    # the widths the upstream suite's forest regressor run states, none cut
    assert (cfg["algo"], cfg["n_cols"], cfg["n_classes"], cfg["dtype"]) == (
        "rf", 3000, 0, "float32")
    assert (cfg["num_trees"], cfg["max_bins"], cfg["max_depth"]) == (30, 128, 6)
    assert (cfg["feature_subset_strategy"], cfg["bootstrap"],
            cfg["min_instances_per_node"]) == ("auto", True, 1)
    assert "NVIDIA/spark-rapids-ml" in cfg["source"] and "num_cols 3000" in cfg["source"]
    assert "--numTrees 30 --maxBins 128 --maxDepth 6" in cfg["source"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "rf_reg_d3000")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == list(cfg["reduced"]) == ["rows"]
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert cfg["arrow_batch_rows"] == 65536 and cfg["fold_program"] == "jit_hist_update_group"
    assert set(cfg["assumed"]) == {"data", "arrow_batch_rows", "forest_seed_sample_rows", "seed",
                                   "daemon_pass_cache_mb", "forest_hist_budget_mb"}
    assert "from memory" not in str(cfg)
    assert p == {"batch_rows": 65536, "cached_batches": 6, "partitions": 6, "compare_trees": 4}
    rows = p["batch_rows"] * p["cached_batches"]
    assert rows == 393_216 and rows * 8 == cfg["rows"]
    assert cell["rows_per_fit"] == rows * cfg["max_depth"]
    # the cached pass — rows, labels, bag keys, masks — holds over 4 GiB, a
    # quarter of the chip, and fits the job's budget; a seventh batch would not
    held = rows * cfg["n_cols"] * 4 + 3 * rows * 4
    assert held == 4_723_310_592 and rows * cfg["n_cols"] * 4 >= 4 << 30
    assert 0.25 * 16e9 <= held <= cfg["daemon_pass_cache_mb"] << 20 < held * 7 / 6
    # the deepest frontier histogram fits its stated budget, a level more would not
    deepest = 30 * 32 * 3000 * 128 * 3 * 4
    assert deepest == 4_423_680_000 <= cfg["forest_hist_budget_mb"] << 20 < 2 * deepest
    assert held + deepest < 16e9
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, CELL)}
                for kind in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == {"pass_rows_per_s", "setup_s"}
    assert reported["per_layer"] == {"compiles_in_window"} | NEW_PER_LAYER | CACHED_PER_LAYER
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_PER_LAYER | CACHED_PER_LAYER:
            assert CELL in m["workloads"] and m["moves"] == "pass_rows_per_s"
        if m["name"] in NEW_PER_LAYER:
            assert m["workloads"] == [CELL]
    assert {m["name"]: (m["layer"], m["source"]) for m in BENCH["per_layer"]
            if m["name"] in NEW_PER_LAYER} == {
        "forest_boundary_ms": ("daemon", "program_span"),
        "forest_score_ms": ("model_programs", "program_span"),
        "forest_fold_dispatch_ms": ("model_programs", "program_counter"),
        "levels_root_pass_ms": ("daemon", "host_clock"),
        "levels_deepest_pass_ms": ("daemon", "host_clock")}
    assert set(cfg["tolerances"]) == set(agree_rf.CEILINGS) | {"split_equal_share"}
    # each limit is written with its reason and the readings that set it
    assert all(len(cfg["tolerance_reasons"][name]) > 200 for name in cfg["tolerances"])
    assert "whole fits" in cell["why"] and "WHOLE FITS" in traffic["about"]


def test_the_folds_cost_is_the_algorithms_and_memory_bound(config):
    rows = 393_216
    flops, nbytes = cost.fold_cost(config, rows)
    assert flops == (2.0 * rows * 30 * 3000 * 3 + rows * 3000 * 7 + rows * 30 * 6)
    frontier = 4.0 * 30 * 3000 * 128 * 3 * 63 / 6  # the mean of a fit's six levels
    assert nbytes == rows * 3000 + 12.0 * rows + 2 * frontier
    line = cost.roofline(flops, nbytes, 5.0, {"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    assert line["bound"] == "memory" and line["least_s"] == pytest.approx(4.99e-3, rel=0.01)
    assert 0.0009 < line["share"] < 0.0011  # a 5 s level reads 0.1%: the distance, not a fault


def test_the_seeded_rows_and_labels_are_the_law_the_configuration_states():
    seed, d = 2147483659, 3000
    planted = rf_data.spec(seed, d)
    cols, weights = planted["columns"], planted["weights"].astype(np.float64)
    assert len(set(cols.tolist())) == rf_data.INFORMATIVE == 10 and cols.max() < d
    np.testing.assert_allclose(np.abs(weights), 100 * 0.8 ** np.arange(10), rtol=1e-6)
    x, y = (np.asarray(a) for a in rf_data.device_rows(planted, d, seed, 3, 4096))
    again = [np.asarray(a) for a in rf_data.device_rows(planted, d, seed, 3, 4096)]
    other = np.asarray(rf_data.device_rows(planted, d, seed, 4, 4096)[0])
    np.testing.assert_array_equal(x, again[0])  # the same seed and index: the same batch
    np.testing.assert_array_equal(y, again[1])
    assert x.dtype == y.dtype == np.float32 and not np.array_equal(x, other)
    assert 0.97 < x.std() < 1.03 and abs(x.mean()) < 0.01
    noise = y - x[:, cols].astype(np.float64) @ weights
    assert 9.0 < noise.std() < 11.0
    # a split on the heaviest column gains far more than one on the next, and
    # a noise column gains next to nothing: the best split is identifiable
    corr = np.asarray([abs(np.corrcoef(x[:, c], y)[0, 1]) for c in cols[:3]])
    assert corr[0] > 1.15 * corr[1] > 1.15 * 1.15 * corr[2]
    silent = next(c for c in range(d) if c not in set(cols.tolist()))
    assert abs(np.corrcoef(x[:, silent], y)[0, 1]) < 0.06 < corr[2]


def _scatter_add_left_sums(x, y, weights, edges, levels, trees, n_levels):
    """The reference's statistics as a histogram by scatter-add, float64:
    bins by `searchsorted`, one `np.add.at` a level, cumulated over bins."""
    n, d = x.shape
    bins = np.stack([np.searchsorted(edges[f], x[:, f], side="left") for f in range(d)], 1)
    e32 = np.asarray(edges, np.float32)
    out = []
    node = np.zeros((len(trees), n), np.int64)
    alive = np.ones((len(trees), n), bool)
    stat = np.stack([np.ones(n), y, y * y], 1).astype(np.float64)
    for level in range(n_levels):
        width = 1 << level
        hist = np.zeros((len(trees), width, d, edges.shape[1] + 1, 3))
        for i, t in enumerate(trees):
            before = np.asarray(levels[level]["feature"])[t]
            take = alive[i] & (before[node[i]] == ref_rf.OPEN)
            rows = np.nonzero(take)[0]
            pos = node[i, rows] - (width - 1)
            for f in range(d):
                np.add.at(hist[i], (pos, f, bins[rows, f]),
                          weights[i, rows, None] * stat[rows])
            feature = np.asarray(levels[-1]["feature"])[t][node[i]]
            threshold = np.asarray(levels[-1]["threshold"])[t][node[i]]
            right = x[np.arange(n), np.clip(feature, 0, d - 1)] > e32[
                np.clip(feature, 0, d - 1), threshold]
            node[i] = np.where(feature >= 0, 2 * node[i] + 1 + right, node[i])
            alive[i] &= feature >= 0
        out.append(np.moveaxis(np.cumsum(hist, axis=3), 4, 2))
    return out


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_the_reference_is_a_scatter_add_histogram_cumulated_over_its_bins(root, seed):
    """`level_statistics` takes the LEFT sums straight from the raw values;
    a float64 scatter-add into bins found by `searchsorted`, cumulated,
    gives the same numbers — under the tables of a real (tiny) fit."""
    lines = _control_lines(root, seed, with_reference=True)
    assert [l["rows"] for l in lines] == ["float32", "bfloat16"]
    cfg = layout.resolve(root, rehearse.CELL)[2]
    generator = layout.load_module(root, "generators", "levels_cached")
    forest = generator.CachedForest(root, cfg, rehearse.PARAMS, seed, 1, lambda m: None)
    _, captured = forest.captured_fit()
    forest.release()
    levels, trees = captured["levels"], forest.trees
    batches = [tuple(np.asarray(a) for a in forest.batch(i)) for i in range(forest.n_batches)]
    keys = [ref_rf.row_keys(p, o, forest.rows) for p, o in forest.placed]
    got = ref_rf.level_statistics(batches, keys, forest.edges, levels, trees, cfg["seed"], 3)
    x = np.concatenate([b[0] for b in batches])
    y = np.concatenate([b[1] for b in batches]).astype(np.float64)
    weights = np.concatenate([ref_rf.bag_weights(k, trees, cfg["seed"]) for k in keys], 1)
    want = _scatter_add_left_sums(
        x, y, weights.astype(np.float64), np.asarray(forest.edges, np.float32), levels, trees, 3)
    assert [a.shape for a in got] == [a.shape for a in want]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])  # counts: exact
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)
    assert want[2][:, :, 0, 0, -1].sum() > 0.5 * len(x) * len(trees)


def test_the_reference_restates_the_bag_weights_and_the_feature_subsets(config):
    """numpy and uint32, from the stated hash: the program's own, bit for bit."""
    from spark_rapids_ml_tpu.models.random_forest import row_identity_keys
    from spark_rapids_ml_tpu.ops import histogram

    keys = ref_rf.row_keys(3, 65536, 4096)
    np.testing.assert_array_equal(keys, row_identity_keys(3, 65536, 4096))
    np.testing.assert_array_equal(
        ref_rf.bag_weights(keys, [0, 5, 29], config["seed"]),
        np.asarray(histogram.bootstrap_weights(keys, 30, config["seed"]))[[0, 5, 29]])
    for depth in (0, 3):
        np.testing.assert_array_equal(
            ref_rf.feature_subset([0, 5, 29], depth, 3000, 1000, config["seed"]),
            np.asarray(histogram.feature_subset_mask(
                30, 1 << depth, depth, 3000, 1000, config["seed"]))[[0, 5, 29]])


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_the_tiny_cell_runs_end_to_end_and_traced(root, trace):
    result, lines = rehearse.run(root, rehearse.CELL, seconds=1.0, trace=trace)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and list(result)[-1] == "compared"
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) == rehearse.reports(root, rehearse.CELL, kind)
    assert f"= {rehearse.CACHED_ROWS} rows" in text and "compiles in window: 0" in text
    assert "from the wire (100% cached)" in text
    assert set(result["compared"]) == COMPARED
    for exact in ("count_mismatch", "rows_miscounted", "fits_differ", "rows_refed_in_window"):
        assert result["compared"][exact] == [0.0, 0.0]
    # off the chip the program computes in float64: it differs from the
    # reference by the reference's float32 alone
    for name in ("hist_rel", "leaf_rel", "pred_rel"):
        assert 0 < result["compared"][name][0] < 1e-6
    assert result["compared"]["split_gain_rel"][0] < 1e-6
    assert result["compared"]["split_equal_share"][0] == 1.0
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        off_the_chip = CACHED_PER_LAYER - {"pass_fold_device_ms", "pass_fold_roofline"}
        assert NEW_PER_LAYER | off_the_chip | {"compiles_in_window"} == set(got)
        assert got["compiles_in_window"] == 0
        assert 0 < got["forest_score_ms"] < got["forest_boundary_ms"]
        assert got["forest_fold_dispatch_ms"] > 0 and got["rescan_dispatch_ms"] > 0
        assert got["pass_cached_share"] == 100.0
        assert got["levels_root_pass_ms"] > 0 and got["levels_deepest_pass_ms"] > 0
        for name in ("pass_fold_device_ms", "pass_fold_roofline"):
            assert f"metric {name}: nothing to read, left out" in text
    else:
        assert {"pass_rows_per_s", "setup_s"} == set(got) and got["pass_rows_per_s"] > 0


def test_only_the_levels_of_whole_fits_are_listed_as_passes(root, monkeypatch):
    """A fit's levels are unequal work: the fit the deadline cuts is run to
    the end of the pass in flight, counted as attempted, and not listed —
    every depth weighs the same in the rate, whatever the program's speed."""
    from perf.harness import runner

    kept = {}
    real = runner.Context

    def keeping(**kw):
        kept["ctx"] = real(**kw)
        return kept["ctx"]

    monkeypatch.setattr(runner, "Context", keeping)
    result, lines = rehearse.run(root, rehearse.CELL, seconds=0.6)
    obs = kept["ctx"].obs
    whole, listed, cut = (int(n) for n in re.search(
        r"(\d+) whole fits \((\d+) level passes listed\), (\d+) cut", "\n".join(lines)).groups())
    assert whole == len(obs.fits) >= 2 and cut == 1
    assert listed == len(obs.passes) == 3 * whole  # depth 3: three levels a fit, all or none
    assert [p["depth"] for p in obs.passes] == [0, 1, 2] * whole
    assert all(p["end"] <= obs.window[1] for p in obs.passes)
    # the cut fit's ops are attempted: set_iterate and 1 to 3 levels of 3 ops
    assert result["attempted"] - 10 * whole in (4, 7, 10)
    # the counters' window ends with the last whole fit: every level's fold
    # program once a whole fit, the cut fit's not among them
    assert obs.counter_delta("srml_xla_calls_total", fn="histogram.update_group") == 3 * whole
    assert obs.counter_delta("srml_daemon_pass_rows_total", source="cache") == (
        3 * whole * rehearse.CACHED_ROWS)
    assert obs.fold_rows_per_chip == rehearse.CACHED_ROWS


def test_a_program_whose_forest_job_keeps_no_pass_fails_at_once_and_makes_no_data(
        root, monkeypatch):
    """The parent commit: the generator asks the table for `cacheable` first."""
    from spark_rapids_ml_tpu.models.random_forest import RandomForestJob

    monkeypatch.setattr(RandomForestJob, "cacheable", False)
    made = []
    data = layout.load_module(root, "harness", "rf_data")
    monkeypatch.setattr(data, "device_rows", lambda *a, **k: made.append(a))
    with pytest.raises(RuntimeError, match="is not `cacheable`"):
        rehearse.run(root, rehearse.CELL, seconds=0.2)
    assert made == []


def test_the_new_readers_find_nothing_in_a_program_without_the_spans_and_counters(config):
    """As on the parent commit: each returns None and does not raise."""
    obs = observe.Observation(config, {}, 1.0, {"kind": "TPU v5 lite"}, ROOT)
    obs.before = obs.after = {"metrics": {}}
    obs.window = (0.0, 1.0)
    for name in NEW_PER_LAYER:
        assert layout.load_module(ROOT, "layer_metrics", name).read(obs) is None


def _a_rescan_that_skips_a_batch(monkeypatch):
    from spark_rapids_ml_tpu.serve import daemon

    real = daemon._Job.rescan

    def skipping(job, *args, **kwargs):
        held = job._cache.batches
        job._cache.batches = held[:-1]
        try:
            return real(job, *args, **kwargs)
        finally:
            job._cache.batches = held

    monkeypatch.setattr(daemon._Job, "rescan", skipping)


def _a_pass_that_is_fed_again(monkeypatch):
    from spark_rapids_ml_tpu.serve import daemon

    real, calls = daemon._Job.rescan, [0]

    def refeeding(job, pass_id=None, **kwargs):
        calls[0] += 1
        if calls[0] % 5:
            return real(job, pass_id, **kwargs)
        for i, (xs, _, ys, _) in enumerate(job._cache.batches):
            job.fold(np.asarray(xs), np.asarray(ys), partition=10 + i, pass_id=pass_id)
            job.commit(10 + i, pass_id=pass_id)
        return {"pass_rows": job.pass_rows}

    monkeypatch.setattr(daemon._Job, "rescan", refeeding)


def _the_tables_altered_where_they_are_produced(monkeypatch):
    from spark_rapids_ml_tpu.models import random_forest

    real = random_forest.grow_level

    def altered(tables, hist, spec):
        out = real(tables, hist, spec)
        split = tables["feature"] >= 0
        tables["threshold"][split] = np.maximum(tables["threshold"][split] - 1, 0)
        return out

    monkeypatch.setattr(random_forest, "grow_level", altered)


def _a_later_fit_that_is_not_the_first(monkeypatch):
    from spark_rapids_ml_tpu.models import random_forest

    real, calls = random_forest.grow_level, [0]

    def drifting(tables, hist, spec):
        out = real(tables, hist, spec)
        calls[0] += 1
        if calls[0] > 3:  # the warm-up fit's three levels are left alone
            tables["value"][:, 0, 1] += 1.0
        return out

    monkeypatch.setattr(random_forest, "grow_level", drifting)


@pytest.mark.parametrize("fault,caught_by", [
    (_a_rescan_that_skips_a_batch, "count_mismatch"),
    (_a_pass_that_is_fed_again, "rows_refed_in_window"),
    (_the_tables_altered_where_they_are_produced, "split_gain_rel"),
    (_a_later_fit_that_is_not_the_first, "fits_differ"),
], ids=["a_rescan_that_skips_a_batch", "a_pass_that_is_fed_again",
        "the_tables_altered_where_they_are_produced", "a_later_fit_that_is_not_the_first"])
def test_a_fit_with_a_planted_fault_is_not_correct(root, monkeypatch, fault, caught_by):
    fault(monkeypatch)
    result, lines = rehearse.run(root, rehearse.CELL, seconds=0.5)
    assert result["correct"] is False
    assert any("DISAGREES" in line for line in lines), "\n".join(lines)
    value, limit = result["compared"][caught_by]
    assert value > limit
    if caught_by == "count_mismatch":
        assert result["compared"]["rows_miscounted"][0] > 0  # the guarantee sees it too
    if caught_by == "rows_refed_in_window":
        assert value % rehearse.CACHED_ROWS == 0 and result["compared"]["count_mismatch"][0] == 0


def test_the_control_rounds_the_rows_to_what_bfloat16_holds():
    import jax.numpy as jnp

    fine = jnp.asarray([1.0, 1.00390625, 1.005859375, -2.76, 9.53125, 0.1], jnp.float32)
    assert control_rf.lower(fine).tolist() == fine.astype(
        jnp.bfloat16).astype(jnp.float32).tolist() != fine.tolist()
    assert control_rf.lower(fine).tolist()[:3] == [1.0, 1.0, 1.0078125]
    assert control_rf.lower(fine).dtype == jnp.float32


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_from_bfloat16_rows_is_not_correct(root, seed):
    """Rows rounded to bfloat16 fall in other bins: the count channel moves,
    which fails the control by the one limit a float32 fold cannot touch; the
    true reference in its place passes."""
    true, control = _control_lines(root, seed, with_reference=True)
    assert true["rows"] == "float32" and true["correct"] is True
    assert true["compared"]["count_mismatch"] == [0.0, 0.0]
    assert control["rows"] == "bfloat16" and control["correct"] is False
    assert control["compared"]["count_mismatch"][0] > 100
    assert control["compared"]["hist_rel"][0] > 10 * true["compared"]["hist_rel"][0]
    assert control["compared"]["rows_miscounted"] == [0.0, 0.0]  # every row still counted
