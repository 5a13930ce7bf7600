"""The six per-layer metrics that read the program's finalize children and
the fold's dispatch counter: unit cases on hand-made snapshots, and the CPU
rehearsal of the one-chip and the x4-like cell reporting them — each what
BENCHMARK.json lists for the admitted cell it stands for."""

import json

import pytest

import contract
import perf_rehearse
from perf.harness import layout, observe
from perf.layer_metrics import (finalize_center_ms, finalize_fetch_ms,
                                finalize_lapack_ms, finalize_post_ms,
                                finalize_self_ms, fold_dispatch_ms)

NEW = {"finalize_fetch_ms", "finalize_center_ms", "finalize_lapack_ms",
       "finalize_post_ms", "finalize_self_ms", "fold_dispatch_ms"}
ONE_CHIP = "pca_d2048_k32.fold_resident"
FOLD = "gram.streaming_update"


def _snapshot(phases, calls=None, dispatch_s=None):
    """{phase: (sum, count)} and the fold's ledger counters → a registry
    snapshot shaped as `utils/metrics.py` makes it."""
    out = {"srml_phase_duration_seconds": {"type": "histogram", "samples": [
        {"labels": {"phase": phase}, "buckets": {}, "sum": total, "count": count}
        for phase, (total, count) in phases.items()]}}
    for name, value in (("srml_xla_calls_total", calls),
                        ("srml_xla_dispatch_seconds_total", dispatch_s)):
        if value is not None:
            out[name] = {"type": "counter", "samples": [
                {"labels": {"fn": FOLD}, "value": value},
                {"labels": {"fn": "gram.init_stats"}, "value": 1000.0}]}
    return out


def _observation(before, after):
    obs = observe.Observation({}, {}, 10.0, {"kind": "TPU v5 lite"})
    obs.before, obs.after = {"metrics": before}, {"metrics": after}
    return obs


BEFORE = {"eig finalize": (1.0, 1), "finalize.wait": (0.001, 1),
          "finalize.fetch": (0.01, 1), "finalize.center": (0.02, 1),
          "finalize.lapack": (0.9, 1), "finalize.post": (0.03, 1)}
# four more finalizes of 1.1 s: 2 + 12 + 30 + 1000 + 36 ms under children, 20 ms not
AFTER = {"eig finalize": (5.4, 5), "finalize.wait": (0.009, 5),
         "finalize.fetch": (0.058, 5), "finalize.center": (0.14, 5),
         "finalize.lapack": (4.9, 5), "finalize.post": (0.174, 5)}


@pytest.mark.parametrize("reader,expected", [
    (finalize_fetch_ms, 12.0), (finalize_center_ms, 30.0),
    (finalize_lapack_ms, 1000.0), (finalize_post_ms, 36.0),
    (finalize_self_ms, 20.0)])
def test_a_finalize_reader_takes_the_mean_of_the_windows_new_samples(reader, expected):
    obs = _observation(_snapshot(BEFORE), _snapshot(AFTER))
    assert reader.read(obs) == pytest.approx(expected)


@pytest.mark.parametrize("missing", ["eig finalize", "finalize.wait", "finalize.fetch",
                                     "finalize.center", "finalize.lapack",
                                     "finalize.post"])
def test_self_time_is_left_out_when_the_parent_or_any_child_has_no_new_sample(missing):
    # never seen (the parent commit's program has no children) ...
    after = {k: v for k, v in AFTER.items() if k != missing}
    before = {k: v for k, v in BEFORE.items() if k != missing}
    assert finalize_self_ms.read(_observation(_snapshot(before), _snapshot(after))) is None
    # ... or seen before the window and not inside it (the device path)
    after = {**AFTER, missing: BEFORE[missing]}
    assert finalize_self_ms.read(_observation(_snapshot(BEFORE), _snapshot(after))) is None


def test_a_child_reader_has_nothing_to_read_from_a_program_without_the_span():
    old = {"eig finalize": AFTER["eig finalize"]}
    obs = _observation(_snapshot({"eig finalize": BEFORE["eig finalize"]}), _snapshot(old))
    for reader in (finalize_fetch_ms, finalize_center_ms, finalize_lapack_ms,
                   finalize_post_ms, finalize_self_ms):
        assert reader.read(obs) is None
        assert reader.read(_observation({}, {})) is None


def test_fold_dispatch_is_the_wrappers_seconds_over_the_folds_called():
    obs = _observation(_snapshot({}, calls=384.0, dispatch_s=0.5),
                       _snapshot({}, calls=384.0 * 15, dispatch_s=0.5 + 384 * 14 * 2.5e-4))
    assert fold_dispatch_ms.read(obs) == pytest.approx(0.25)


@pytest.mark.parametrize("before,after", [
    ({}, {}),  # no ledger at all
    (_snapshot({}, calls=10.0, dispatch_s=0.1), _snapshot({}, calls=10.0, dispatch_s=0.1)),
    (_snapshot({}, calls=10.0), _snapshot({}, calls=20.0)),  # the parent: no such counter
], ids=["empty", "no_call_in_window", "no_counter"])
def test_fold_dispatch_is_left_out_when_there_is_nothing_to_read(before, after):
    assert fold_dispatch_ms.read(_observation(before, after)) is None


def test_the_new_entries_belong_to_the_one_chip_cell_only_and_are_appended(tmp_path):
    """Its name is PR 24's; what it holds since PR 27 is a contract by name:
    each accepted per-layer metric exists once with the fields it was accepted
    with, and each of the six is still read in the one-chip cell. Nothing
    about their place in the list, what follows them, or which other cells
    a later PR appended to their `workloads`."""
    held = contract.the_accepted_per_layer_metrics_are_what_they_were
    assert NEW == contract.SPLIT and ONE_CHIP == contract.ONE_CHIP
    held(layout.REPO_ROOT)

    def write(bench):
        with open(tmp_path / "BENCHMARK.json", "w", encoding="utf-8") as f:
            json.dump(bench, f)
        return str(tmp_path)

    bench = layout.load_benchmark(layout.REPO_ROOT)
    bench["per_layer"].reverse()  # order is free
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append("a_later.cell")  # lists may grow
    bench["per_layer"].append({"name": "a_later_metric", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "a later layer",
                               "moves": "fit_rows_per_s", "workloads": ["a_later.cell"]})
    held(write(bench))
    dispatch = next(m for m in bench["per_layer"] if m["name"] == "fold_dispatch_ms")
    dispatch["moves"] = "setup_s"  # a field of an accepted entry may not change
    with pytest.raises(AssertionError):
        held(write(bench))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return perf_rehearse.tiny_root(tmp_path_factory.mktemp("split"))


def test_the_one_chip_rehearsal_reports_the_six_and_the_split_adds_up(root):
    result, lines = perf_rehearse.run(root, "tiny_pca.fold_resident", seconds=1.0,
                                      trace=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(got) == perf_rehearse.reports(root, "tiny_pca.fold_resident", "per_layer")
    assert NEW | {"finalize_eig_ms", "compiles_in_window"} <= set(got)
    assert all(got[name] >= 0 for name in NEW) and got["compiles_in_window"] == 0
    assert got["finalize_self_ms"] < got["finalize_eig_ms"]
    named = sum(got[n] for n in NEW - {"finalize_self_ms", "fold_dispatch_ms"})
    # the four named children and the self time leave only `finalize.wait`
    assert named + got["finalize_self_ms"] <= got["finalize_eig_ms"] * (1 + 1e-9)
    assert 0 < got["fold_dispatch_ms"] < 1e3
    assert all(m["unit"] == "ms" for n, m in result["metrics"].items() if n in NEW)


def test_the_x4_like_rehearsal_cell_reports_what_it_did(root):
    result, _ = perf_rehearse.run(root, "tiny_pca.fold_resident_x4", seconds=0.5,
                                  trace=True)
    want = perf_rehearse.reports(root, "tiny_pca.fold_resident_x4", "per_layer")
    assert set(result["metrics"]) == want
    # since PR 27 the x4 cell lists the finalize split and the dispatch counter
    assert NEW | {"finalize_eig_ms", "compiles_in_window"} <= want
