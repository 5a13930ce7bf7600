"""The ten per-layer metrics that read the pass boundary's four children,
the host time of a cached pass outside the ledger's clock, and the buckets of
the span and dispatch histograms (PR 38): unit cases on hand-made snapshots,
the entries' cells in the repo's BENCHMARK.json, and the CPU rehearsal of the
tiny KMeans and PCA cells reporting them."""

import pytest

import contract
import perf_rehearse
import perf_rehearse_kmeans
from perf.harness import layout, observe
from perf.layer_metrics import (fold_dispatches_over_50ms, lloyd_boundary_read_ms,
                                lloyd_boundary_reads_over_50ms, lloyd_boundary_self_ms,
                                lloyd_boundary_snapshot_ms, lloyd_boundary_state_ms,
                                lloyd_boundary_update_ms, lloyd_fold_dispatches_over_50ms,
                                lloyd_rescan_self_ms, lloyd_rescans_over_50ms)

KMEANS = "kmeans_d256_k100.lloyd_cached"
PCA = ["pca_d2048_k32.fold_resident", "pca_d2048_k32.fold_resident_x4"]
NEW_KMEANS = {
    **{f"lloyd_boundary_{part}_ms": ("ms", "program_span", "daemon")
       for part in ("update", "state", "read", "snapshot", "self")},
    "lloyd_rescan_self_ms": ("ms", "program_span", "daemon"),
    "lloyd_rescans_over_50ms": ("calls", "program_span", "daemon"),
    "lloyd_boundary_reads_over_50ms": ("calls", "program_span", "daemon"),
    "lloyd_fold_dispatches_over_50ms": ("calls", "program_counter", "model_programs"),
}
NEW_PCA = "fold_dispatches_over_50ms"
PHASES = "srml_phase_duration_seconds"
DISPATCHES = "srml_xla_dispatch_duration_seconds"
GROUP = "kmeans.streaming_update_group"
BOUNDS = ("0.025", "0.05", "0.1")


def _hist(label, series):
    """{label value: (sum, count, samples at or under each of BOUNDS)} → one
    histogram of a registry snapshot, buckets cumulative as `utils/metrics.py`
    keeps them."""
    return {"type": "histogram", "samples": [
        {"labels": {label: value}, "sum": total, "count": count,
         "buckets": {**dict(zip(BOUNDS, under)), "+Inf": count}}
        for value, (total, count, under) in series.items()]}


def _phases(spans):
    """{phase: (sum, count)}: every sample under the first bound."""
    return {PHASES: _hist("phase", {p: (s, n, (n, n, n)) for p, (s, n) in spans.items()})}


def _observation(before, after):
    obs = observe.Observation({}, {}, 10.0, {"kind": "TPU v5 lite"})
    obs.before, obs.after = {"metrics": before}, {"metrics": after}
    return obs


BEFORE = {"lloyd.boundary": (0.060, 10), "lloyd.boundary.update": (0.012, 10),
          "lloyd.boundary.state": (0.003, 10), "lloyd.boundary.read": (0.040, 10),
          "lloyd.boundary.snapshot": (0.0001, 10)}
# 100 more boundaries of 5.9 ms: 1.3 + 0.3 + 4.2 + 0.01 under children, 0.09 not
AFTER = {"lloyd.boundary": (0.650, 110), "lloyd.boundary.update": (0.142, 110),
         "lloyd.boundary.state": (0.033, 110), "lloyd.boundary.read": (0.460, 110),
         "lloyd.boundary.snapshot": (0.0011, 110)}


@pytest.mark.parametrize("reader,expected", [
    (lloyd_boundary_update_ms, 1.3), (lloyd_boundary_state_ms, 0.3),
    (lloyd_boundary_read_ms, 4.2), (lloyd_boundary_snapshot_ms, 0.01),
    (lloyd_boundary_self_ms, 0.09)])
def test_a_boundary_reader_takes_the_mean_of_the_windows_new_samples(reader, expected):
    obs = _observation(_phases(BEFORE), _phases(AFTER))
    assert reader.read(obs) == pytest.approx(expected)


def test_the_parts_and_the_self_time_add_up_to_the_whole():
    obs = _observation(_phases(BEFORE), _phases(AFTER))
    parts = sum(r.read(obs) for r in (
        lloyd_boundary_update_ms, lloyd_boundary_state_ms, lloyd_boundary_read_ms,
        lloyd_boundary_snapshot_ms, lloyd_boundary_self_ms))
    assert parts == pytest.approx(obs.hist_mean_ms(PHASES, phase="lloyd.boundary")) \
        == pytest.approx(5.9)


@pytest.mark.parametrize("missing", list(BEFORE))
def test_self_time_is_left_out_while_the_parent_or_any_child_has_no_new_sample(missing):
    # never seen (the parent commit's program opens no child) ...
    after = {k: v for k, v in AFTER.items() if k != missing}
    before = {k: v for k, v in BEFORE.items() if k != missing}
    assert lloyd_boundary_self_ms.read(_observation(_phases(before), _phases(after))) is None
    # ... or seen before the window and not inside it
    after = {**AFTER, missing: BEFORE[missing]}
    assert lloyd_boundary_self_ms.read(_observation(_phases(BEFORE), _phases(after))) is None


def test_a_part_has_nothing_to_read_from_a_program_without_the_children():
    old = _observation(_phases({"lloyd.boundary": BEFORE["lloyd.boundary"]}),
                       _phases({"lloyd.boundary": AFTER["lloyd.boundary"]}))
    for reader in (lloyd_boundary_update_ms, lloyd_boundary_state_ms, lloyd_boundary_read_ms,
                   lloyd_boundary_snapshot_ms, lloyd_boundary_self_ms,
                   lloyd_boundary_reads_over_50ms, lloyd_fold_dispatches_over_50ms,
                   fold_dispatches_over_50ms):
        assert reader.read(old) is None
        assert reader.read(_observation({}, {})) is None


COUNTS = [(lloyd_rescans_over_50ms, PHASES, "phase", "pass.rescan"),
          (lloyd_boundary_reads_over_50ms, PHASES, "phase", "lloyd.boundary.read"),
          (lloyd_fold_dispatches_over_50ms, DISPATCHES, "fn", GROUP),
          (fold_dispatches_over_50ms, DISPATCHES, "fn", "gram.streaming_update")]


@pytest.mark.parametrize("reader,name,label,value", COUNTS,
                         ids=[c[0].__name__.rsplit(".", 1)[-1] for c in COUNTS])
def test_a_count_reads_the_buckets_above_50_ms_only(reader, name, label, value):
    # before the window: 1,000 samples, 3 of them over 50 ms (one over 100)
    before = {name: _hist(label, {value: (9.0, 1000, (990, 997, 999)),
                                  "another": (50.0, 10, (0, 0, 0))})}
    # in the window 3,500 more: 40 between 25 and 50 ms, 4 between 50 and 100, 1 over
    after = {name: _hist(label, {value: (48.0, 4500, (4445, 4492, 4498)),
                                 "another": (500.0, 100, (0, 0, 0))})}
    assert reader.read(_observation(before, after)) == 5.0
    # a series born inside the window counts from nothing
    assert reader.read(_observation({}, after)) == 8.0
    # nothing slow: a number, not a gap on the line
    calm = {name: _hist(label, {value: (30.0, 4500, (4497, 4497, 4499))})}
    assert reader.read(_observation(before, calm)) == 0.0
    # ... and so is a window without a new sample, once the series has one
    assert reader.read(_observation(before, before)) == 0.0
    # only a series nobody ever observed (the parent's program) is left out
    other = {name: _hist(label, {"another": (500.0, 100, (0, 0, 0))})}
    assert reader.read(_observation(other, other)) is None
    # a histogram of other bounds has nothing at 50 ms to read
    odd = {name: {"type": "histogram", "samples": [
        {"labels": {label: value}, "sum": 1.0, "count": 5, "buckets": {"1": 5, "+Inf": 5}}]}}
    assert reader.read(_observation({}, odd)) is None


def _rescans(span, dispatched):
    out = _phases({"pass.rescan": span})
    if dispatched is not None:
        out["srml_xla_dispatch_seconds_total"] = {"type": "counter", "samples": [
            {"labels": {"fn": GROUP}, "value": dispatched},
            {"labels": {"fn": "kmeans.streaming_update"}, "value": 1000.0}]}
    return out


def test_rescan_self_time_is_the_span_less_the_ledgers_clock():
    # 1,000 cached passes of 5.0 ms, twelve dispatches of 0.235 ms in each
    obs = _observation(_rescans((0.5, 100), 0.3),
                       _rescans((5.5, 1100), 0.3 + 1000 * 12 * 0.235e-3))
    assert lloyd_rescan_self_ms.read(obs) == pytest.approx(5.0 - 12 * 0.235)


@pytest.mark.parametrize("before,after", [
    ({}, {}),
    (_rescans((0.5, 100), 0.3), _rescans((0.5, 100), 0.3)),  # no pass in the window
    (_rescans((0.5, 100), None), _rescans((5.5, 1100), None)),  # no ledger clock
], ids=["empty", "no_pass_in_window", "no_counter"])
def test_rescan_self_time_is_left_out_when_there_is_nothing_to_read(before, after):
    assert lloyd_rescan_self_ms.read(_observation(before, after)) is None


def test_the_ten_entries_are_there_by_name_and_list_the_cells_that_report_them():
    """Held by name and fields, as `contract.the_accepted_per_layer_metrics_are_
    what_they_were` holds an accepted entry: NOT by position in `per_layer` and
    not by `workloads ==`, so a later PR appends an entry, or a cell to one of
    these lists, without touching this file (PR 27 took such a pin out of
    `test_perf_finalize_split.py`)."""
    bench = layout.load_benchmark(layout.REPO_ROOT)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in NEW_KMEANS.items():
        m = entries[name]
        assert KMEANS in m["workloads"], name
        assert (m["unit"], m["source"], m["layer"], m["moves"], m["better"]) == (
            unit, source, layer, "pass_rows_per_s", "lower"), name
        # a reader of the KMeans job's spans has nothing to read in a PCA cell
        assert not set(PCA) & set(m["workloads"]), name
    m = entries[NEW_PCA]
    assert set(PCA) <= set(m["workloads"])
    assert (m["unit"], m["source"], m["layer"], m["moves"], m["better"]) == (
        "calls", "program_counter", "model_programs", "fold_rows_per_s", "lower")
    # every listed cell reports the end-to-end metric the entry moves
    for name in list(NEW_KMEANS) + [NEW_PCA]:
        for cell in entries[name]["workloads"]:
            reported = {e["name"] for e in layout.metric_entries(bench, "end_to_end", cell)}
            assert entries[name]["moves"] in reported, (name, cell)
    contract.check(layout.REPO_ROOT)


HELD_WITH_EQ = ["logreg_d3000.newton_cached", "rf_reg_d3000.levels_cached",
                "pca_d2048_k32.fold_resident_small"]


@pytest.mark.parametrize("cell", HELD_WITH_EQ)
def test_a_cell_whose_set_a_test_holds_with_eq_lists_none_of_the_ten(cell):
    """ISSUE 38: three cells cannot take a per-layer metric by addition while
    a test under tests/perf holds their set with `==` (ROADMAP D7 (k)). This
    case is that `benchmark` PR's to delete, with the `==`: it appends
    `fold_dispatches_over_50ms` to the small-fold cell."""
    bench = layout.load_benchmark(layout.REPO_ROOT)
    listed = {m["name"] for m in layout.metric_entries(bench, "per_layer", cell)}
    assert not listed & (set(NEW_KMEANS) | {NEW_PCA}), cell


@pytest.fixture(scope="module")
def kmeans_root(tmp_path_factory):
    return perf_rehearse_kmeans.tiny_root(tmp_path_factory.mktemp("boundary_kmeans"))


def test_the_tiny_kmeans_cell_lists_and_reports_the_nine_and_they_add_up(kmeans_root):
    cell = perf_rehearse_kmeans.CELL
    assert set(NEW_KMEANS) <= perf_rehearse_kmeans.reports(kmeans_root, cell, "per_layer")
    result, lines = perf_rehearse_kmeans.run(kmeans_root, cell, seconds=1.0, trace=True)
    assert result["correct"] is True, "\n".join(lines)
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(NEW_KMEANS) <= set(got)
    assert {name: result["metrics"][name]["unit"] for name in NEW_KMEANS} == {
        name: unit for name, (unit, _, _) in NEW_KMEANS.items()}
    parts = [got[f"lloyd_boundary_{part}_ms"] for part in ("update", "state", "read",
                                                           "snapshot")]
    assert all(p > 0 for p in parts)
    # whole = the four children + what `step` does outside them
    assert sum(parts) + got["lloyd_boundary_self_ms"] == pytest.approx(
        got["lloyd_boundary_ms"], rel=1e-9)
    assert 0 <= got["lloyd_boundary_self_ms"] < got["lloyd_boundary_ms"]
    # a cached pass's host time = what lies outside the ledger's clock + its dispatches
    batches = perf_rehearse_kmeans.PARAMS["cached_batches"]
    from spark_rapids_ml_tpu.serve import daemon
    dispatches = -(-batches // daemon._RESCAN_GROUP)
    assert 0 < got["lloyd_rescan_self_ms"] < got["rescan_dispatch_ms"]
    assert got["lloyd_rescan_self_ms"] + dispatches * got["lloyd_fold_dispatch_ms"] \
        == pytest.approx(got["rescan_dispatch_ms"], rel=1e-6)
    for name in ("lloyd_rescans_over_50ms", "lloyd_boundary_reads_over_50ms",
                 "lloyd_fold_dispatches_over_50ms"):
        assert got[name] >= 0 and got[name] == int(got[name])


@pytest.fixture(scope="module")
def pca_root(tmp_path_factory):
    return perf_rehearse.tiny_root(tmp_path_factory.mktemp("boundary_pca"))


@pytest.mark.parametrize("cell", ["tiny_pca.fold_resident", "tiny_pca.fold_resident_x4"])
def test_the_tiny_pca_cells_list_and_report_the_folds_slow_dispatches(pca_root, cell):
    assert NEW_PCA in perf_rehearse.reports(pca_root, cell, "per_layer")
    assert not set(NEW_KMEANS) & perf_rehearse.reports(pca_root, cell, "per_layer")
    result, lines = perf_rehearse.run(pca_root, cell, seconds=0.5, trace=True)
    assert result["correct"] is True, "\n".join(lines)
    assert set(result["metrics"]) == perf_rehearse.reports(pca_root, cell, "per_layer")
    slow = result["metrics"][NEW_PCA]
    # every warm fold of a (256, 64) batch on the CPU is far under 50 ms
    assert slow["unit"] == "calls" and slow["value"] >= 0 and slow["value"] == int(
        slow["value"])
