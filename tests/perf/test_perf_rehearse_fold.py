"""CPU rehearsal of the resident fold at a tiny size (d=64) on one device
and on a mesh over 4 of the 8 virtual devices, and the rehearsal of the next
`model_config` PR: a second algorithm (`fixtures/next_pr`: configuration,
generator, reference, cost file, mix, cell, five metrics) laid over a copy
of the tree as new files and appended entries, run there, and the whole
contract (`contract.py`) checked on the tree AFTER the addition."""

import os

import pytest

import contract
import perf_rehearse
from perf.harness import cost, layout, observe


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return perf_rehearse.tiny_root(tmp_path_factory.mktemp("fold"))


@pytest.mark.parametrize("cell,mesh,rows_per_fit", [
    ("tiny_pca.fold_resident", "{'data': 1, 'model': 1}", 4 * 256),
    ("tiny_pca.fold_resident_x4", "{'data': 4, 'model': 1}", 4 * 512),
    # a mix that is one data file: the same generator, a deeper fit
    ("tiny_pca.fold_resident_deep", "{'data': 1, 'model': 1}", 16 * 128),
])
def test_fold_resident_end_to_end(root, cell, mesh, rows_per_fit):
    result, lines = perf_rehearse.run(root, cell, seconds=1.0)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert set(result) == RESULT_KEYS and set(result["device"]) == DEVICE_KEYS
    assert f"mesh {mesh}" in text and f"= {rows_per_fit} rows" in text
    # what BENCHMARK.json lists for the admitted cell this one stands for
    assert set(result["metrics"]) == perf_rehearse.reports(root, cell, "end_to_end")
    assert {"fold_rows_per_s", "setup_s"} <= set(result["metrics"])
    assert all(m["value"] > 0 and set(m) == {"value", "unit"}
               for m in result["metrics"].values())
    assert result["failed"] == 0 and result["attempted"] > 4
    assert "compiles in window: 0" in text and "agreement over" in text
    assert result["device"]["platform"] == "cpu"  # named for what it is
    # each number that decided `correct`, beside its limit, as the last key
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"rows_not_folded", "min_cos", "explained_variance_rel",
                                       "mean_abs", "compiles_in_window"}
    assert result["compared"]["rows_not_folded"] == [0.0, 0.0]
    value, limit = result["compared"]["min_cos"]
    assert 0.9999 < limit <= value <= 1.0


def test_fold_resident_per_layer_reads_spans_and_leaves_out_the_trace(root):
    result, lines = perf_rehearse.run(root, "tiny_pca.fold_resident_x4", seconds=1.0,
                                      trace=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    got = result["metrics"]
    # the program's own spans and counters; the rest needs a TPU trace
    assert set(got) == perf_rehearse.reports(root, "tiny_pca.fold_resident_x4", "per_layer")
    assert {"finalize_eig_ms", "compiles_in_window"} <= set(got)
    assert got["compiles_in_window"]["value"] == 0 and got["finalize_eig_ms"]["value"] > 0
    for name in ("fold_device_ms", "fold_roofline", "collective_ms_per_fold",
                 "device_idle_share"):
        assert f"metric {name}: nothing to read, left out" in text
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-1] == "compared"


def _a_fold_returns_its_state_unchanged(monkeypatch):
    from spark_rapids_ml_tpu.ops import gram

    real = gram.streaming_update

    def lossy(mesh):
        update, calls = real(mesh), [0]

        def skipping(state, x, mask):
            calls[0] += 1
            return state if calls[0] % 4 == 0 else update(state, x, mask)

        return skipping

    monkeypatch.setattr(gram, "streaming_update", lossy)


def _half_of_a_batch_left_out(monkeypatch):
    from spark_rapids_ml_tpu.ops import gram

    real = gram.streaming_update

    def halving(mesh):
        update = real(mesh)
        return lambda state, x, mask: update(state, x, mask.at[::2].set(0.0))

    monkeypatch.setattr(gram, "streaming_update", halving)


def _the_exchange_between_chips_left_out(monkeypatch):
    from spark_rapids_ml_tpu.ops import gram

    # the fold is traced once a mesh: trace it anew without its psum, and
    # leave no such trace behind (monkeypatch undoes in reverse order)
    monkeypatch.setattr(gram, "_streaming_update_cached",
                        gram._streaming_update_cached.__wrapped__)
    monkeypatch.setattr(gram.mr, "reduce_sum", lambda value, axis: value)


def _the_model_altered_where_it_is_produced(monkeypatch):
    from spark_rapids_ml_tpu.models import pca

    real = pca.finalize_pca_stats

    def altered(*args, **kwargs):
        sol = real(*args, **kwargs)
        return sol._replace(explained_variance=sol.explained_variance * (1 + 2.0**-8))

    monkeypatch.setattr(pca, "finalize_pca_stats", altered)


@pytest.mark.parametrize("fault,cell,caught_by,reads", [
    # at the cells' own size the models would still agree (the ring's batches
    # are alike): the state's own row count is what catches work not done —
    # one fold of four, half of 4 x 256 rows, three of four chips' 4 x 512
    (_a_fold_returns_its_state_unchanged, "tiny_pca.fold_resident", "rows_not_folded", 256),
    (_half_of_a_batch_left_out, "tiny_pca.fold_resident", "rows_not_folded", 512),
    (_the_exchange_between_chips_left_out, "tiny_pca.fold_resident_x4", "rows_not_folded",
     1536),
    (_the_model_altered_where_it_is_produced, "tiny_pca.fold_resident",
     "explained_variance_rel", None),
], ids=["a_fold_returns_its_state_unchanged", "half_of_a_batch_left_out",
        "the_exchange_between_chips_left_out", "the_model_altered_where_it_is_produced"])
def test_a_fit_that_lost_a_fold_is_not_correct(root, monkeypatch, fault, cell, caught_by,
                                               reads):
    """A whole run, off the chip, with the timed path broken underneath:
    `correct` comes out false, and by the number that is to catch the fault."""
    fault(monkeypatch)
    result, lines = perf_rehearse.run(root, cell, seconds=0.5)
    assert result["correct"] is False
    assert any("DISAGREES" in line for line in lines)
    value, limit = result["compared"][caught_by]
    assert value > limit
    if caught_by == "rows_not_folded":
        assert any("the state counts" in line for line in lines)
        assert value == reads


def _files(root):
    out = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[path] = f.read()
    return out


@pytest.fixture(scope="module")
def next_pr(tmp_path_factory):
    """(the copy with the next PR laid over it, its cell, the copy's files
    before, BENCHMARK.json before)."""
    root = perf_rehearse.plain_root(tmp_path_factory.mktemp("next_pr"))
    before = _files(root)
    bench = layout.load_benchmark(root)
    return root, perf_rehearse.add_next_pr(root), before, bench


def test_a_later_pr_adds_a_cell_a_mix_and_metrics_as_files_of_their_own(next_pr):
    root, cell, before, bench_before = next_pr
    added = layout.load_benchmark(root)
    assert layout.load_config(root, added, "colsum_d64")["algo"] == "colsum"

    # (a) the cell runs, end to end and traced
    result, lines = perf_rehearse.run(root, cell, seconds=0.5)
    assert result["correct"] is True, "\n".join(lines)
    assert set(result) == RESULT_KEYS and result["failed"] == 0
    assert set(result["metrics"]) == {"fold_rows_per_s", "colsum_passes_per_s", "setup_s"}
    assert result["metrics"]["colsum_passes_per_s"]["unit"] == "passes/s"
    assert all(m["value"] > 0 for m in result["metrics"].values())
    result, lines = perf_rehearse.run(root, cell, seconds=0.5, trace=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(got) == perf_rehearse.reports(root, cell, "per_layer") == {
        "colsum_scale_ms", "colsum_folds_per_pass", "compiles_in_window"}
    assert got["colsum_folds_per_pass"] == 8 and got["colsum_scale_ms"] > 0
    assert got["compiles_in_window"] == 0
    for name in ("fold_device_ms", "fold_roofline", "colsum_scale_device_ms",
                 "colsum_scale_roofline"):
        assert f"metric {name}: nothing to read, left out" in text

    # (b) every check of the contract passes on the tree after the addition
    contract.check(root)

    # (c) no file that was there was edited; BENCHMARK.json's entries are
    # what they were, and its lists only grew at their ends
    after = _files(root)
    bench_path = os.path.join(root, "BENCHMARK.json")
    assert all(after[path] == content for path, content in before.items()
               if path != bench_path)
    assert len(after) == len(before) + 11
    assert perf_rehearse.only_grew(bench_before, added) == []
    assert [len(added[key]) - len(bench_before[key]) for key in (
        "configs", "workloads", "end_to_end", "per_layer")] == [1, 1, 1, 4]


@pytest.mark.parametrize("check", contract.CHECKS, ids=lambda c: c.__name__)
def test_the_contract_holds_on_the_tree_after_the_addition(next_pr, check):
    """Each check by itself, so that the one that forbids an addition is
    named. Every one of them also runs on the repo (test_perf_layout.py,
    test_perf_finalize_split.py)."""
    check(next_pr[0])


def test_the_device_readers_of_a_second_algorithm_find_its_cost_in_their_own_tree(next_pr):
    """A TPU trace is not to be had here: a reduced trace made by hand, read
    by the copy's readers, with the costs from the copy's `perf/costs/`."""
    root, cell, _, _ = next_pr
    _, _, config, _, params = layout.resolve(root, cell)
    rows, d = params["batch_rows"], config["n_cols"]
    assert cost.fold_cost(config, rows, root) == (rows * d, 4.0 * rows * d + 8.0 * d)
    with pytest.raises(KeyError, match="colsum"):
        cost.fold_cost(config, rows)  # the repo has no such file: found by root only
    obs = observe.Observation(config, params, 1.0, {"kind": "TPU v5 lite"}, root)
    obs.fold_rows_per_chip = rows
    obs.trace = {"devices": {0: {"programs": {
        "jit_colsum_fold": {"count": 8, "seconds": 8 * 1e-6},
        "jit_colsum_scale": {"count": 2, "seconds": 2 * 1e-6}}}}}
    read = {name: layout.load_module(root, "layer_metrics", name).read(obs)
            for name in ("fold_device_ms", "fold_roofline", "colsum_scale_device_ms",
                         "colsum_scale_roofline")}
    assert read["fold_device_ms"] == pytest.approx(1e-3)
    assert read["colsum_scale_device_ms"] == pytest.approx(1e-3)
    # memory-bound, both: bytes ÷ 819 GB/s over a microsecond
    assert obs.notes["fold_roofline_bound"] == "memory"
    assert read["fold_roofline"] == pytest.approx(100 * (4 * rows * d + 8 * d) / 819e9 / 1e-6)
    assert read["colsum_scale_roofline"] == pytest.approx(100 * 8 * d / 819e9 / 1e-6)


def test_an_addition_may_not_replace_a_file_that_is_there(next_pr):
    with pytest.raises(FileExistsError, match="may not replace"):
        perf_rehearse.add_next_pr(next_pr[0])


@pytest.mark.parametrize("edit,problem", [
    (lambda b: b["per_layer"][3].update(layer="other"), "per_layer[3].layer"),
    (lambda b: b["per_layer"].pop(0), "per_layer[0]"),  # every later entry moved up
    (lambda b: b["workloads"].pop(), "workloads: 1 entries went"),
    (lambda b: b["end_to_end"][0]["workloads"].insert(0, "a.cell"),
     "end_to_end[0].workloads[0]"),  # not at the end
    (lambda b: b["end_to_end"][0].update(bound=0.1), "end_to_end[0].bound"),
    (lambda b: b["per_layer"][6].update(workloads=[]), "per_layer[6]: keys ['workloads']"),
], ids=["a_field_edited", "an_entry_taken_out", "a_cell_taken_out", "a_cell_put_in_front",
        "a_bound_loosened", "a_key_added"])
def test_only_grew_names_what_an_addition_may_not_do(edit, problem):
    before = layout.load_benchmark(layout.REPO_ROOT)
    after = layout.load_benchmark(layout.REPO_ROOT)
    assert perf_rehearse.only_grew(before, after) == []
    after["per_layer"].append({"name": "more"})
    after["end_to_end"][0]["workloads"].append("a_later.cell")
    assert perf_rehearse.only_grew(before, after) == []
    edit(after)
    assert any(problem in p for p in perf_rehearse.only_grew(before, after))
