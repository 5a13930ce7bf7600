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
    from perf.end_to_end import finalize_s, fold_rows_per_s
    from perf.layer_metrics import finalize_eig_ms

    fits = [{"finalize_s": 1.0, "end": 103.0}, {"finalize_s": 1.2, "end": 106.0},
            {"finalize_s": 1.1, "end": 109.0},
            {"finalize_s": 9.0, "end": 111.5}]  # its model arrived after the window
    passes = [{"rows": 500, "start": 100.0, "end": 102.0},
              {"rows": 500, "start": 103.0, "end": 105.0},
              {"rows": 500, "start": 109.5, "end": 110.5}]  # ended after the deadline
    obs = _observation(fits=fits, passes=passes,
                       before={"metrics": _snapshot(2.0, 4, 0.0)},
                       after={"metrics": _snapshot(5.3, 7, 0.0)})
    assert finalize_s.read(obs) == pytest.approx(1.1)
    assert fold_rows_per_s.read(obs) == pytest.approx(1000 / 4.0)
    assert finalize_eig_ms.read(obs) == pytest.approx(1100.0)
    empty = _observation(before={"metrics": {}}, after={"metrics": {}})
    assert finalize_s.read(empty) is None and fold_rows_per_s.read(empty) is None
    assert finalize_eig_ms.read(empty) is None  # nothing to read: left out of the line


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([90, 95, 100, 105, 110]) == pytest.approx(0.10)


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
