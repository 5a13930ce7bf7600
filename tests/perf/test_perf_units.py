"""The benchmark's own arithmetic: rows/s, counter deltas, interval
reduction, costs, peaks, the window's memory, the seeded rows, the reference."""

import numpy as np
import pytest

from perf.harness import agree, cost, data, device, observe, stats, trace
from perf.reference import pca as ref_pca

# -- rows per second and counters ---------------------------------------------


def test_rows_per_s_counts_only_passes_that_completed_in_the_window():
    passes = [
        {"rows": 1000, "start": 0.0, "end": 2.0},
        {"rows": 1000, "start": 5.0, "end": 7.0},   # 3 s of finalize lie between
        {"rows": 1000, "start": 9.0, "end": 11.0},  # ends after the deadline
    ]
    assert stats.rows_per_s(passes, deadline=10.0) == pytest.approx(2000 / 4.0)
    assert stats.rows_per_s(passes, deadline=1.0) is None


def _passes(seconds, rows=1000, start=0.0):
    """Passes back to back from `start`, one for each of `seconds` (and of
    `rows`, where that is a list)."""
    rows = rows if isinstance(rows, list) else [rows] * len(seconds)
    out, at = [], start
    for took, n in zip(seconds, rows):
        out.append({"fit": 0, "pass": len(out), "rows": n, "start": at,
                    "scanned": at + took / 4, "end": at + took})
        at += took
    return out


@pytest.mark.parametrize("seconds,rows,deadline,mean,median,late", [
    # equal passes: the two rates are one, nothing is late
    ([2.0] * 5, 1000, 100.0, 500.0, 500.0, 0.0),
    # unequal rows: rows over (count x the median seconds), not the median pass's own rows
    ([2.0, 2.0, 2.0, 2.0], [1000, 3000, 1000, 3000], 100.0, 1000.0, 1000.0, 0.0),
    # one pass ten times late: the median pass ignores it, the rate over all
    # the seconds does not, and the share reads exactly its excess, 18 of 28 s
    ([2.0, 2.0, 20.0, 2.0, 2.0], 1000, 100.0, 5000 / 28.0, 500.0, 100 * 18.0 / 28.0),
    # a pass just under 1.5 x the median is not late, one just over is: 0.02 of 8.02 s
    ([2.0, 2.98, 2.0, 2.0], 1000, 100.0, 4000 / 8.98, 500.0, 0.0),
    ([2.0, 3.02, 2.0, 1.0], 1000, 100.0, 4000 / 8.02, 500.0, 100 * 1.02 / 8.02),
    # passes past the deadline are left out: the late one ends at 26 s
    ([2.0, 2.0, 2.0, 20.0, 2.0], 1000, 10.0, 500.0, 500.0, 0.0),
], ids=["equal", "unequal_rows", "one_ten_times_late", "under_late", "over_late",
        "past_the_deadline"])
def test_the_two_rates_of_a_windows_passes_and_the_share_of_its_late_ones(
        seconds, rows, deadline, mean, median, late):
    passes = _passes(seconds, rows)
    assert stats.rows_per_s(passes, deadline) == pytest.approx(mean)
    assert stats.median_pass_rows_per_s(passes, deadline) == pytest.approx(median)
    assert stats.late_pass_share(passes, deadline) == pytest.approx(late, abs=1e-12)


def test_no_completed_pass_is_nothing_to_read_for_either_rate_or_the_share():
    passes = _passes([2.0, 2.0], start=5.0)
    for read in (stats.rows_per_s, stats.median_pass_rows_per_s, stats.late_pass_share):
        assert read(passes, 6.0) is None and read([], 6.0) is None
    assert stats.late_pass_share(passes, 9.0) == 0.0  # passes, none late: 0.0, not None


def test_the_pass_readers_are_the_harness_arithmetic_over_the_windows_passes():
    from perf.end_to_end import fold_rows_per_s, pass_rows_per_s
    from perf.layer_metrics import late_pass_share, median_pass_rows_per_s

    obs = _observation(passes=_passes([1.0, 1.0, 4.0, 1.0, 1.0, 9.0], start=100.0))
    # the last pass ends at 117 s, after the deadline of 110 s
    assert pass_rows_per_s.read(obs) == fold_rows_per_s.read(obs) == pytest.approx(5000 / 8.0)
    assert median_pass_rows_per_s.read(obs) == pytest.approx(1000.0)
    assert late_pass_share.read(obs) == pytest.approx(100 * 3.0 / 8.0)
    empty = _observation()
    assert [r.read(empty) for r in (pass_rows_per_s, median_pass_rows_per_s,
                                    late_pass_share)] == [None, None, None]


def test_a_traced_runs_pass_readers_leave_out_the_profilers_interval():
    from perf.layer_metrics import late_pass_share, median_pass_rows_per_s

    # ten passes of 1 s from 100 s; the profiler was on from 102.5 to 105.2 s
    # and the four passes that touch that interval took 3 s under it
    passes = _passes([1.0, 1.0, 3.0, 3.0, 3.0, 3.0, 1.0, 1.0, 1.0, 1.0], start=100.0)
    obs = _observation(passes=passes)
    obs.window = (100.0, 130.0)
    assert late_pass_share.read(obs) == pytest.approx(100 * 8.0 / 18.0)  # untraced: all count
    obs.trace = {"devices": {}, "window_s": 2.0, "profiled": (102.5, 112.2)}
    assert [p["pass"] for p in stats.unprofiled(passes, obs.trace)] == [0, 1, 6, 7, 8, 9]
    assert late_pass_share.read(obs) == 0.0
    assert median_pass_rows_per_s.read(obs) == pytest.approx(1000.0)
    said = []
    stats.say_passes(passes, 130.0, said.append, obs.trace)
    assert said[0].startswith("the profiler was on for 9.70 s (it traced 2.00 s of them)")
    assert "0 of 6 over 1.5 x the median" in said[1]
    # nothing traced, a trace that kept no interval, or one that covers every pass: all count
    assert stats.unprofiled(passes, {"profiled": (99.0, 131.0)}) == passes
    assert stats.unprofiled(passes, None) == passes == stats.unprofiled(passes, {"devices": {}})


def test_say_passes_prints_the_share_the_reader_reads_and_each_late_pass_by_its_calls():
    said = []
    passes = _passes([2.0, 2.0, 20.0, 2.0, 2.0, 50.0])
    stats.say_passes(passes, 30.0, said.append)
    assert len(said) == 2
    assert "1 of 5 over 1.5 x the median" in said[0] and "64.286% of the passes'" in said[0]
    assert "rows/s over all their seconds 178.571, of the median pass 500" in said[0]
    assert said[1] == "  late: fit 0 pass 2: 20000.0 ms = rescan 5000.0 + step 15000.0"
    # a generator that keeps no `scanned` gets the line without the split
    said.clear()
    stats.say_passes([{k: v for k, v in p.items() if k != "scanned"} for p in passes],
                     30.0, said.append)
    assert said[1] == "  late: fit 0 pass 2: 20000.0 ms"
    stats.say_passes([], 30.0, said.append)
    assert len(said) == 2  # no completed pass: nothing said


def _snapshot(eig_sum, eig_count, folds):
    return {
        "srml_phase_duration_seconds": {"type": "histogram", "samples": [
            {"labels": {"phase": "eig finalize"}, "buckets": {}, "sum": eig_sum,
             "count": eig_count},
            {"labels": {"phase": "compute cov"}, "buckets": {}, "sum": 9.0, "count": 9}]},
        "srml_folds_total": {"type": "counter", "samples": [
            {"labels": {"algo": "pca"}, "value": folds},
            {"labels": {"algo": "kmeans"}, "value": 5.0}]},
    }


def test_histogram_and_counter_deltas_across_the_window():
    before, after = _snapshot(2.0, 4, 100.0), _snapshot(8.0, 16, 1100.0)
    name = "srml_phase_duration_seconds"
    assert stats.hist_delta(before, after, name, phase="eig finalize") == (6.0, 12)
    assert stats.hist_mean_ms(before, after, name, phase="eig finalize") == pytest.approx(500.0)
    assert stats.hist_mean_ms(before, after, name, phase="compute cov") is None  # no new sample
    assert stats.hist_mean_ms(before, after, name, phase="step") is None  # never seen
    assert stats.counter_delta(before, after, "srml_folds_total", algo="pca") == 1000.0


def _observation(**fields):
    obs = observe.Observation({}, {}, 10.0, {"kind": "TPU v5 lite"})
    obs.window = (100.0, 110.0)
    for key, value in fields.items():
        setattr(obs, key, value)
    return obs


def test_the_finalize_readers_take_fits_and_spans_of_the_window_only():
    from perf.end_to_end import fit_rows_per_s, fold_rows_per_s
    from perf.layer_metrics import finalize_eig_ms, finalize_s

    fits = [{"finalize_s": 1.0, "end": 103.0}, {"finalize_s": 1.2, "end": 106.0},
            {"finalize_s": 1.1, "end": 109.0},
            {"finalize_s": 9.0, "end": 111.5}]  # its model arrived after the window
    for i, fit in enumerate(fits):
        fit.update(fit=i, rows=500)
    passes = [{"fit": 0, "rows": 500, "start": 100.0, "end": 102.0},
              {"fit": 1, "rows": 500, "start": 103.0, "end": 105.0},
              {"fit": 3, "rows": 500, "start": 109.5, "end": 110.5}]  # ended after the deadline
    obs = _observation(fits=fits, passes=passes,
                       before={"metrics": _snapshot(2.0, 4, 0.0)},
                       after={"metrics": _snapshot(5.3, 7, 0.0)})
    assert finalize_s.read(obs) == pytest.approx(1.1)
    assert fold_rows_per_s.read(obs) == pytest.approx(1000 / 4.0)
    # the whole fit: folds and finalize of the fits whose model arrived in the
    # window and whose passes were kept (fit 2 has none here, fit 3 ended late)
    assert fit_rows_per_s.read(obs) == pytest.approx(1000 / (2.0 + 1.0 + 2.0 + 1.2))
    assert finalize_eig_ms.read(obs) == pytest.approx(1100.0)
    empty = _observation(before={"metrics": {}}, after={"metrics": {}})
    assert finalize_s.read(empty) is None and fold_rows_per_s.read(empty) is None
    assert fit_rows_per_s.read(empty) is None
    assert finalize_eig_ms.read(empty) is None  # nothing to read: left out of the line


def test_spread_is_the_quartile_distance_over_the_median_as_the_driver_takes_it():
    # statistics.quantiles' quartiles (92.5, 107.5), not numpy's (95, 105)
    assert stats.spread([90, 95, 100, 105, 110]) == pytest.approx(0.15)
    # without the run farthest from the median: 150 goes, (92.5, 103) over 100
    assert stats.spread_without_farthest([90, 95, 100, 105, 150, 101]) == pytest.approx(0.105)


def test_the_spread_command_reads_a_set_of_runs_and_both_estimators(tmp_path):
    import json

    from perf import spread

    for i, late in enumerate([0.0, 0.0, 0.3, 0.0, 0.9, 0.1]):
        passes = _passes([0.01] * 50 + [0.01 + late] + [0.01] * 49, rows=100, start=5.0)
        end = passes[-1]["end"]
        passes += _passes([0.01] * 3, rows=100, start=end + 1.0)  # after the deadline
        mean = stats.rows_per_s(passes, end)
        run = {"result": {"correct": True, "metrics": {
            "pass_rows_per_s": {"value": mean, "unit": "rows/s"}}},
            "window": [5.0, end], "passes": passes}
        (tmp_path / f"a.cell.seed{i}.trace0.json").write_text(json.dumps(run))
    said = []
    spread.say_set(str(tmp_path), said.append)
    text = "\n".join(said)
    assert "a.cell, 6 runs, 0 not correct" in said[0]
    # the rate over all the seconds spreads with the late passes, the median pass's does not
    assert "rows/s of the median pass: median 10000, spread 0.000%" in text
    mean_line = [line for line in said if "over all the passes' seconds" in line][0]
    assert "median 9" in mean_line and "spread 0.000%" not in mean_line
    assert said[2] == said[1].replace("pass_rows_per_s", "rows/s over all the passes' seconds")
    assert "seed4.trace0.json: 100 passes, median 10.000 ms, 1 late" in text
    assert spread.main([]) == 2


# -- intervals and the reduction of a trace ------------------------------------


def test_interval_union_subtract_and_gaps():
    merged = trace.union([(0, 2), (1, 3), (5, 6), (6, 6), (5.5, 5.8)])
    assert merged == [(0, 3), (5, 6)]
    assert trace.total(merged) == 4
    assert trace.subtract([(0, 10)], merged) == [(3, 5), (6, 10)]
    assert trace.subtract(merged, [(2, 5.5)]) == [(0, 2), (5.5, 6)]
    assert trace.gaps(merged, 1, 8) == [(3, 5), (6, 8)]
    assert trace.program_name("jit_update(123456)") == "jit_update"
    assert trace.op_name("%fusion.1 = f32[2048,2048]{1,0:T(8,128)} fusion(f32[8]{0} %p)") \
        == "fusion.1"


def _raw_trace():
    """Two devices, 10 s. Device 0: two folds of 2 s, each with a 0.5 s
    all-reduce, of which 0.2 s run under a copy; a 1 s zeros program."""
    ops0 = [("fusion.1", 1.0, 2.5), ("all-reduce.1", 2.5, 3.0), ("copy.2", 2.8, 3.0),
            ("fusion.1", 5.0, 6.5), ("all-reduce.1", 6.5, 7.0), ("copy.2", 6.8, 7.0),
            ("broadcast.3", 8.0, 9.0)]
    modules0 = [("jit_update(11)", 1.0, 3.0), ("jit_update(11)", 5.0, 7.0),
                ("jit_zeros(12)", 8.0, 9.0)]
    return {
        "start_wall_s": 100.0, "stop_wall_s": 110.0,
        "devices": {0: {"modules": modules0, "ops": ops0},
                    1: {"modules": modules0[:2], "ops": ops0[:6]}},
    }


SPANS = [("fold_loop", 0.6, 7.5), ("sync", 3.0, 4.5), ("finalize", 7.5, 10.0)]


def test_reduce_trace_hand_checked():
    out = trace.reduce_trace(_raw_trace(), (0.0, 10.0), SPANS)
    dev0, dev1 = out["devices"][0], out["devices"][1]
    assert out["window_s"] == 10.0
    assert dev0["busy_s"] == pytest.approx(5.0) and dev1["busy_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(4.5)  # averaged over the chips
    assert dev0["programs"]["jit_update"] == {"count": 2, "seconds": pytest.approx(4.0)}
    assert dev0["programs"]["jit_zeros"]["count"] == 1
    assert dev0["collective_s"] == pytest.approx(1.0)
    assert dev0["collective_exposed_s"] == pytest.approx(0.6)
    assert dev0["collective_events"] == 2
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    # idle gaps of device 0, by the innermost span of the driver thread
    gaps = dict((name, secs) for name, secs in out["idle_gaps"])
    assert gaps == {
        "no_span": pytest.approx(1.0),   # [0, 1): its middle is before `fold_loop` opens
        "sync": pytest.approx(2.0),      # [3, 5): `sync` inside `fold_loop`
        "finalize": pytest.approx(2.0),  # [7, 8) and [9, 10)
    }
    assert out["idle_gaps"][0][1] == pytest.approx(2.0)  # longest first
    assert sum(gaps.values()) == pytest.approx(10.0 - dev0["busy_s"])


def test_reduce_trace_clips_to_the_window_and_survives_no_device():
    out = trace.reduce_trace(_raw_trace(), (2.0, 6.0))
    assert out["devices"][0]["busy_s"] == pytest.approx(2.0)  # [2, 3) and [5, 6)
    assert out["devices"][0]["programs"]["jit_update"]["seconds"] == pytest.approx(2.0)
    empty = trace.reduce_trace({"devices": {}})
    assert empty["devices"] == {} and empty["busy_s"] == 0.0


# -- costs, roofline, peaks ----------------------------------------------------


def test_fold_costs_from_shapes():
    flops, nbytes = cost.pca_fold(65536, 2048)
    assert flops == 2 * 65536 * 2048**2 + 65536 * 2048
    assert nbytes == 4 * 65536 * 2048 + 2 * 4 * 2048**2
    assert cost.fold_cost({"algo": "pca", "n_cols": 8}, 4) == cost.pca_fold(4, 8)
    with pytest.raises(KeyError):
        cost.fold_cost({"algo": "ivf"}, 4)


def test_roofline_says_which_bound():
    peaks = device.peaks_for("TPU v5 lite")
    flops, nbytes = cost.pca_fold(65536, 2048)
    line = cost.roofline(flops, nbytes, seconds=0.004, peaks=peaks)
    assert line["bound"] == "compute"
    assert line["least_s"] == pytest.approx(flops / 197e12)
    assert line["share"] == pytest.approx(flops / 197e12 / 0.004)
    # a fold of few rows moves the Gram more than it computes: memory-bound
    flops, nbytes = cost.pca_fold(64, 2048)
    assert cost.roofline(flops, nbytes, 0.001, peaks)["bound"] == "memory"


def test_peaks_table_refuses_an_unknown_device_kind():
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.UnknownDevice, match="TPU v9"):
        device.peaks_for("TPU v9")
    with pytest.raises(device.UnknownDevice):
        device.peaks_for("cpu")


def test_require_device_refuses_the_cpu_and_a_wrong_chip_count():
    with pytest.raises(device.NoDevice, match="needs a tpu"):
        device.require_device("tpu", 1)
    assert device.require_device(None, 1)["platform"] == "cpu"


# -- the window's memory, set-up, the data builders ------------------------------


@pytest.mark.parametrize("peak_before,peak_after,in_use,want", [
    # the peak grew inside the window: JAX's own figure at its end
    (1000, 1700, [400, 900, 300], 1700),
    # set-up's peak (rows made on the device) was higher: the sampler's most
    (5000, 5000, [400, 900, 300], 900),
])
def test_window_memory_is_the_windows_peak_not_the_processes(
        monkeypatch, peak_before, peak_after, in_use, want):
    readings = {"peak_bytes_in_use": [peak_before, peak_after],
                "bytes_in_use": list(in_use)}
    monkeypatch.setattr(device, "_stat", lambda key: [0, readings[key].pop(0)
                                                      if len(readings[key]) > 1
                                                      else readings[key][0]])
    memory = device.WindowMemory(period_s=1e9)  # only the two ends are sampled
    memory.start()
    assert memory.stop() == want == memory.peak_bytes


def test_setup_s_leaves_out_the_runtimes_own_seconds():
    import time

    said = []
    ctx = observe.Context(root=".", cell={}, config={}, params={}, seed=1, seconds=1.0,
                          trace=False, device={}, say=said.append,
                          process_start=time.time() - 30.0, runtime_s=12.5, out_dir=None)
    ctx.begin_window()
    ctx.end_window()
    assert ctx.obs.setup_s == pytest.approx(17.5, abs=0.5)
    assert "12.50 s for the accelerator's runtime" in said[0] and "= 30." in said[0]
    assert ctx.obs.memory_peak_bytes == 0  # the CPU reports nothing


def test_seeded_rows_repeat_with_the_seed_and_carry_the_planted_spectrum():
    spec = data.pca_spec(5, 32, 3)
    first = np.asarray(data.device_rows(spec, 5, 0, 4096))
    assert first.shape == (4096, 32) and first.dtype == np.float32
    assert np.array_equal(first, np.asarray(data.device_rows(spec, 5, 0, 4096)))
    assert not np.array_equal(first, np.asarray(data.device_rows(spec, 5, 1, 4096)))
    assert not np.array_equal(first, np.asarray(data.device_rows(spec, 6, 0, 4096)))
    # variances 32^2·0.93^2i + 1 along the planted directions, 1 elsewhere
    centred = first.astype(np.float64) - first.mean(axis=0)
    along = (centred @ spec["basis"].astype(np.float64)).var(axis=0)
    assert along == pytest.approx((32.0 * 0.93 ** np.arange(3)) ** 2 + 1, rel=0.1)
    assert np.linalg.eigvalsh(np.cov(centred.T))[:-3].max() < 1.3
    assert first.mean(axis=0) == pytest.approx(spec["mean"], abs=2.0)


# -- the reference against float64 NumPy --------------------------------------


def test_reference_pca_agrees_with_float64_numpy():
    rng = np.random.default_rng(0)
    basis, _ = np.linalg.qr(rng.standard_normal((24, 3)))
    batches = [((rng.standard_normal((400, 3)) * [9.0, 5.0, 3.0]) @ basis.T
                + 0.1 * rng.standard_normal((400, 24)) + 0.3).astype(np.float32)
               for _ in range(3)]
    weights = [2, 1, 3]
    ref = ref_pca.fit(batches, weights, k=3)
    x = np.concatenate([b for b, w in zip(batches, weights) for _ in range(w)]
                       ).astype(np.float64)
    xc = x - x.mean(axis=0)
    w, v = np.linalg.eigh(xc.T @ xc)
    sigma = np.sqrt(np.clip(w[::-1], 0, None))
    per, principal = agree.component_cosines(ref["pc"], v[:, ::-1][:, :3])
    assert per > 1 - 1e-9 and principal > 1 - 1e-9
    assert ref["explained_variance"] == pytest.approx((sigma / sigma.sum())[:3], rel=1e-5)
    assert ref["mean"] == pytest.approx(x.mean(axis=0), abs=1e-6)
    assert ref["rows"] == 2400


def test_agreement_checks_refuse_what_is_off():
    rng = np.random.default_rng(2)
    pc, _ = np.linalg.qr(rng.standard_normal((12, 2)))
    ref = {"pc": pc, "explained_variance": np.array([0.6, 0.4]), "mean": np.zeros(12)}
    tol = {"min_cos": 0.9999, "explained_variance_rel": 2.0**-9, "mean_abs": 2.0**-10}
    good = {"pc": -pc, "explained_variance": ref["explained_variance"] * 1.001,
            "mean": np.full(12, 1e-4)}  # sign-invariant
    assert agree.check_pca_fit(good, ref, tol, 12, 2) == []
    tilted = dict(good, pc=pc + 0.05 * rng.standard_normal((12, 2)))
    assert any("components" in b for b in agree.check_pca_fit(tilted, ref, tol, 12, 2))
    assert agree.check_pca_fit(dict(good, mean=np.ones(3)), ref, tol, 12, 2)
