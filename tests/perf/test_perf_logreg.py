"""The logistic cell `logreg_d3000.newton_cached` (PR 32): its files and
entries, the plain reference against the program (the in-memory fit and the
daemon's job, cached and re-fed), a CPU rehearsal of a tiny cell end to end
and traced, planted faults through whole rehearsal runs, and the float8
and bfloat16 controls at a size a test can hold."""

import numpy as np
import pytest

import contract
import perf_rehearse_logreg as rehearse
from perf.harness import agree_logreg, cost, layout, logreg_data, observe
from perf.reference import control_logreg
from perf.reference import logreg as ref_logreg

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELL = "logreg_d3000.newton_cached"
NEW_PER_LAYER = {"newton_boundary_ms", "newton_solve_ms", "newton_fold_dispatch_ms"}
#: what every cell on a cached pass reports (PR 35; the daemon's two since
#: this cell may list them: `tests/perf/test_perf_kmeans.py` un-pinned)
CACHED_PER_LAYER = {"pass_cached_share", "rescan_dispatch_ms", "pass_fold_device_ms",
                    "pass_fold_roofline", "median_pass_rows_per_s", "late_pass_share"}
COMPARED = {"rows_not_folded", "pass0_grad_rel", "pass0_hess_rel", "coef_rel", "loss_rel",
            "rows_refed_in_window", "compiles_in_window"}


@pytest.fixture(scope="module")
def config():
    return layout.load_config(ROOT, BENCH, "logreg_d3000")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(tmp_path_factory.mktemp("logreg"))


def _batches(seed, d, rows, n_batches):
    planted = logreg_data.spec(seed, d)
    start = logreg_data.start_iterate(seed, planted)
    return [logreg_data.device_rows(planted, seed, i, rows) for i in range(n_batches)], start


def test_the_cell_is_the_deployment_cut_to_one_chips_rows(config):
    contract.check(ROOT)
    _, cell, cfg, traffic, p = layout.resolve(ROOT, CELL)
    assert cfg == config and traffic["generator"] == "newton_cached" and cell["chips"] == 1
    # binary, float32, the width, regParam, tol and standardization the
    # upstream suite's logistic run states; nothing cut but rows and passes
    assert (cfg["algo"], cfg["n_cols"], cfg["n_classes"], cfg["dtype"]) == (
        "logreg", 3000, 2, "float32")
    assert "NVIDIA/spark-rapids-ml" in cfg["source"] and "num_cols 3000" in cfg["source"]
    assert (cfg["max_iter"], cfg["tol"], cfg["reg"], cfg["fit_intercept"]) == (
        10, 1e-30, 1e-5, True)
    assert (cfg["standardization"], cfg["elastic_net"]) == (False, 0.0)
    assert cfg["arrow_batch_rows"] == 65536 and cfg["fold_program"] == "jit_update_group"
    assert list(cfg["reduced"]) == ["rows", "max_iter"]
    # what the source does not state is said to be set here, the width is not among it
    assert set(cfg["assumed"]) == {"data", "start", "arrow_batch_rows", "daemon_pass_cache_mb"}
    assert "from memory" not in str(cfg)
    assert p == {"batch_rows": 65536, "cached_batches": 8, "partitions": 8, "trace_s": 5.0}
    rows = p["batch_rows"] * p["cached_batches"]
    assert rows == 524_288 and rows * 8 == cfg["rows"]
    assert cell["rows_per_fit"] == rows * cfg["max_iter"] == 5_242_880
    # the cached pass — rows, labels, masks — holds a quarter of the chip and
    # fits the job's budget
    held = rows * cfg["n_cols"] * 4 + 2 * rows * 4
    assert held == 6_295_650_304
    assert 0.25 * 16e9 <= held <= cfg["daemon_pass_cache_mb"] << 20 < 16e9
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, CELL)}
                for kind in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == {"pass_rows_per_s", "setup_s"}
    assert reported["per_layer"] >= {"compiles_in_window"} | NEW_PER_LAYER | CACHED_PER_LAYER
    assert not reported["per_layer"] & {"fold_device_ms", "fold_roofline"}
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_PER_LAYER | CACHED_PER_LAYER:
            assert CELL in m["workloads"] and m["moves"] == "pass_rows_per_s"
    assert {m["name"]: m["layer"] for m in BENCH["per_layer"]
            if m["name"] in NEW_PER_LAYER} == {
        "newton_boundary_ms": "daemon", "newton_solve_ms": "model_programs",
        "newton_fold_dispatch_ms": "model_programs"}
    assert set(cfg["tolerances"]) == set(agree_logreg.RELATIVE)
    # each limit is written with its reason and the readings that set it
    assert all(len(cfg["tolerance_reasons"][name]) > 200 for name in cfg["tolerances"])


def test_the_folds_cost_is_compute_bound_at_one_read_of_the_rows(config):
    flops, nbytes = cost.fold_cost(config, 65536)
    n, d = 65536, 3000
    assert flops == 2.0 * n * d * d + 6.0 * n * d and nbytes == 4.0 * n * d + 8.0 * n + 8.0 * d * d
    line = cost.roofline(flops, nbytes, 9.4578e-3, {"bf16_flops_per_s": 197e12,
                                                    "hbm_bytes_per_s": 819e9})
    assert line["bound"] == "compute" and 0.63 < line["share"] < 0.64
    assert line["least_s"] == pytest.approx(5.994e-3, rel=0.01)


def test_the_seeded_rows_and_labels_are_the_law_the_configuration_states():
    seed, d = 2147483659, 3000
    planted = logreg_data.spec(seed, d)
    assert planted["loadings"].shape == (d, logreg_data.RANK)
    loadings, w = planted["loadings"].astype(np.float64), planted["w"].astype(np.float64)
    # the logits' variance under the law's covariance I + L Lᵀ: standard deviation 2
    assert w @ w + np.sum((loadings.T @ w) ** 2) == pytest.approx(4.0, rel=1e-5)
    x, y = (np.asarray(a) for a in logreg_data.device_rows(planted, seed, 3, 4096))
    again = [np.asarray(a) for a in logreg_data.device_rows(planted, seed, 3, 4096)]
    other = np.asarray(logreg_data.device_rows(planted, seed, 4, 4096)[0])
    np.testing.assert_array_equal(x, again[0])  # the same seed and index: the same batch
    np.testing.assert_array_equal(y, again[1])
    assert x.dtype == y.dtype == np.float32 and not np.array_equal(x, other)
    assert set(np.unique(y)) == {0.0, 1.0} and 0.3 < y.mean() < 0.7
    assert np.abs(x).max() < 12  # far inside what float8_e4m3 holds
    # columns of variance about 2, half of it shared through the low-rank part
    assert 1.6 < x.var(axis=0).mean() < 2.4
    corr = np.corrcoef(x[:, :64], rowvar=False)
    assert np.abs(corr - np.eye(64)).max() > 0.3
    # classes overlap: the planted logits put a fifth of the rows on the other side
    z = x.astype(np.float64) @ w + float(planted["b"])
    assert 1.8 < z.std() < 2.2 and 0.15 < np.mean((z > 0) != (y > 0.5)) < 0.35
    start = logreg_data.start_iterate(seed, planted)
    assert start["w"].shape == (d,) and start["b"].shape == (1,) and np.any(start["w"] != 0)
    z0 = x.astype(np.float64) @ start["w"].astype(np.float64)
    assert 0.4 < z0.std() < 0.6  # p(1-p) varies row by row from the first pass on
    np.testing.assert_array_equal(start["w"], logreg_data.start_iterate(seed, planted)["w"])


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_the_reference_against_the_programs_in_memory_fit(seed):
    """`fit_logistic_regression` from w = 0 to convergence and the reference
    from the same start: the same optimum of the same objective."""
    from spark_rapids_ml_tpu.models.logistic_regression import fit_logistic_regression

    d, rows, n_batches, reg = 64, 300, 4, 1e-3
    batches, _ = _batches(seed, d, rows, n_batches)
    x = np.concatenate([np.asarray(b[0]) for b in batches])
    y = np.concatenate([np.asarray(b[1]) for b in batches])
    sol = fit_logistic_regression(x, y, reg=reg, fit_intercept=True, max_iter=30, tol=1e-10)
    zero = {"w": np.zeros(d, np.float32), "b": np.zeros(1, np.float32)}
    ref = ref_logreg.fit(batches, zero, 30, 1e-5, reg)
    assert ref["n_iter"] < 30 and ref["rows"] == rows * n_batches
    assert agree_logreg.coef_rel(sol.coefficients, float(sol.intercept),
                                 ref["w"], ref["b"]) < 1e-5
    assert sol.loss == pytest.approx(ref["loss"], rel=1e-5)


@pytest.mark.parametrize("how", ["cached", "refed"])
@pytest.mark.parametrize("seed", [7, 3000000019])
def test_the_programs_job_against_the_plain_reference(mesh1, seed, how):
    """`_Job` fed once and folded from its cache — or fed every pass —
    against the reference over the same batches from the same start. Off the
    chip the program computes in float64, so the two differ by the
    reference's float32 only."""
    from spark_rapids_ml_tpu import config as program_config
    from spark_rapids_ml_tpu.serve.daemon import _Job

    d, rows, n_batches, passes, reg = 64, 300, 5, 6, 1e-4
    batches, start = _batches(seed, d, rows, n_batches)
    host = [(np.asarray(x), np.asarray(y)) for x, y in batches]
    with program_config.option("daemon_pass_cache_mb", 8 if how == "cached" else 0):
        job = _Job("logreg", d, mesh1, {"n_classes": 2})
    job.set_iterate(start, 0)
    first, counted, info = None, [], None
    for it in range(passes):
        if it and how == "cached":
            job.rescan(it)
        else:
            for x, y in host:
                job.fold(x, y, pass_id=it)
        state = [np.asarray(a) for a in job.peek_pass_state()[0]]
        first = state if first is None else first
        counted.append(float(state[-1]))
        info = job.step({"reg": reg, "fit_intercept": True})
    iterate = job.get_iterate()[0]
    names = ("gw", "gb", "hww", "hwb", "hbb", "loss", "n")
    model = {"w": iterate["w"], "b": float(iterate["b"][0]), "loss": info["loss"],
             "pass0": dict(zip(names, first)), "pass_rows": counted}
    ref = ref_logreg.fit(batches, start, passes, 0.0, reg)
    assert ref["n_iter"] == passes and ref["rows"] == rows * n_batches
    tight = {"pass0_grad_rel": 1e-6, "pass0_hess_rel": 1e-6, "coef_rel": 1e-5,
             "loss_rel": 1e-6}
    assert agree_logreg.check_fit(model, ref, tight, rows * n_batches) == []


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_the_tiny_cell_runs_end_to_end_and_traced(root, trace):
    result, lines = rehearse.run(root, rehearse.CELL, seconds=1.0, trace=trace)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] >= 10 and list(result)[-1] == "compared"
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) == rehearse.reports(root, rehearse.CELL, kind)
    assert f"= {rehearse.CACHED_ROWS} rows" in text and "compiles in window: 0" in text
    assert "from the wire (100% cached)" in text
    assert set(result["compared"]) == COMPARED
    assert result["compared"]["rows_not_folded"] == [0.0, 0.0]
    assert result["compared"]["rows_refed_in_window"] == [0.0, 0.0]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        off_the_chip = CACHED_PER_LAYER - {"pass_fold_device_ms", "pass_fold_roofline"}
        assert NEW_PER_LAYER | off_the_chip | {"compiles_in_window"} == set(got)
        assert got["compiles_in_window"] == 0
        assert 0 < got["newton_solve_ms"] < got["newton_boundary_ms"]
        assert got["newton_fold_dispatch_ms"] > 0
        # the daemon's two, listed for this cell too since PR 35
        assert got["pass_cached_share"] == 100.0 and got["rescan_dispatch_ms"] > 0
        assert got["median_pass_rows_per_s"] > 0 and 0 <= got["late_pass_share"] < 100
        for name in ("pass_fold_device_ms", "pass_fold_roofline"):
            assert f"metric {name}: nothing to read, left out" in text
    else:
        assert {"pass_rows_per_s", "setup_s"} == set(got) and got["pass_rows_per_s"] > 0
    assert "a pass: p10 " in text and "x the median, " in text


def test_a_program_whose_newton_job_keeps_no_pass_fails_at_once_and_makes_no_data(
        root, monkeypatch):
    """The parent commit: the generator asks the table for `cacheable` first."""
    from spark_rapids_ml_tpu.models.logistic_regression import LogisticRegressionJob

    monkeypatch.setattr(LogisticRegressionJob, "cacheable", False)
    made = []
    monkeypatch.setattr(logreg_data, "device_rows", lambda *a, **k: made.append(a))
    with pytest.raises(RuntimeError, match="is not `cacheable`"):
        rehearse.run(root, rehearse.CELL, seconds=0.2)
    assert made == []


def test_the_new_readers_find_nothing_in_a_program_without_the_spans_and_counters(config):
    """As on the parent commit: each returns None and does not raise."""
    obs = observe.Observation(config, {}, 1.0, {"kind": "TPU v5 lite"}, ROOT)
    obs.before = obs.after = {"metrics": {}}
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
        if calls[0] % 4:
            return real(job, pass_id, **kwargs)
        fed = [(np.asarray(xs), np.asarray(ys)) for xs, _, ys in job._cache.batches]
        for x, y in fed:
            job.fold(x, y, pass_id=pass_id)
        return {"pass_rows": job.pass_rows}

    monkeypatch.setattr(daemon._Job, "rescan", refeeding)


def _the_coefficients_altered_where_they_are_produced(monkeypatch):
    from spark_rapids_ml_tpu.models import logistic_regression

    real = logistic_regression._stream_newton_step_fn

    def altered(*args):
        step = real(*args)

        def stepping(gw, gb, hww, hwb, hbb, n, w, b):
            new_w, new_b, delta = step(gw, gb, hww, hwb, hbb, n, w, b)
            return new_w * 1.01, new_b, delta

        return stepping

    monkeypatch.setattr(logistic_regression, "_stream_newton_step_fn", altered)


@pytest.mark.parametrize("fault,caught_by,reads", [
    (_a_rescan_that_skips_a_batch, "rows_not_folded", 256),
    (_a_pass_that_is_fed_again, "rows_refed_in_window", None),
    (_the_coefficients_altered_where_they_are_produced, "coef_rel", None),
], ids=["a_rescan_that_skips_a_batch", "a_pass_that_is_fed_again",
        "the_coefficients_altered_where_they_are_produced"])
def test_a_fit_with_a_planted_fault_is_not_correct(root, monkeypatch, fault, caught_by, reads):
    fault(monkeypatch)
    result, lines = rehearse.run(root, rehearse.CELL, seconds=0.5)
    assert result["correct"] is False
    assert any("DISAGREES" in line for line in lines), "\n".join(lines)
    value, limit = result["compared"][caught_by]
    assert value > limit
    if reads is not None:
        assert value == reads
    if caught_by == "rows_refed_in_window":
        assert value % rehearse.CACHED_ROWS == 0 and result["compared"]["rows_not_folded"][0] == 0


def test_the_control_rounds_the_rows_alone_to_what_float8_e4m3_holds():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.0625, 1.1875, -2.75, 0.4375, 9.5], jnp.float32)
    assert control_logreg.lower(x).tolist() == [1.0, 1.0, 1.25, -2.75, 0.4375, 10.0]
    # and to what bfloat16 holds: 8 bits of mantissa with the hidden one
    fine = jnp.asarray([1.0, 1.00390625, 1.005859375, -2.76, 9.53125, 0.1], jnp.float32)
    assert control_logreg.lower(fine, "bfloat16").tolist() == fine.astype(
        jnp.bfloat16).astype(jnp.float32).tolist() != fine.tolist()
    assert control_logreg.lower(fine, "bfloat16").tolist()[:3] == [1.0, 1.0, 1.0078125]
    seen = []

    def spy(a):
        seen.append(a.shape)
        return a

    batches = [(np.ones((64, 8), np.float32), np.ones(64, np.float32)),
               (np.zeros((64, 8), np.float32), np.zeros(64, np.float32))]
    stats = ref_logreg.scan(batches, np.zeros(8, np.float32), 0.0, rounded=spy)
    assert seen == [(64, 8), (64, 8)] and stats["n"] == 128  # every batch's rows, no more


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_in_float8_is_not_correct(config, seed):
    """At d = 256 and 16,384 rows the control reads over every limit that
    decides on the chip; the reference against itself reads 0."""
    d, rows, n_batches, passes = 256, 2048, 8, 4
    batches, start = _batches(seed, d, rows, n_batches)
    fit_args = (start, passes, 0.0, config["reg"])
    ref = ref_logreg.fit(batches, *fit_args)
    whole = [float(rows * n_batches)]
    again = {**ref_logreg.fit(batches, *fit_args), "pass_rows": whole}
    assert agree_logreg.check_fit(again, ref, config["tolerances"], rows * n_batches) == []
    model = {**control_logreg.fit(ref_logreg, batches, *fit_args), "pass_rows": whole}
    problems = agree_logreg.check_fit(model, ref, config["tolerances"], rows * n_batches)
    for name in ("pass0_grad_rel", "pass0_hess_rel", "coef_rel"):
        assert any(name in p for p in problems), problems
    compared = agree_logreg.compared([{"model": model}], config["tolerances"],
                                     rows * n_batches)
    for name in ("pass0_grad_rel", "pass0_hess_rel"):
        value, limit = compared[name]
        assert value > 2 * limit, name
    assert compared["rows_not_folded"] == [0.0, 0.0]


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_from_bfloat16_rows_is_not_correct(config, seed):
    """The second control: below the float32 of the gradient's and the
    loss's sums. Its Hessian is the program's own by design and passes, so
    the first pass's gradient has to catch it, and does."""
    d, rows, n_batches, passes = 256, 2048, 8, 4
    batches, start = _batches(seed, d, rows, n_batches)
    fit_args = (start, passes, 0.0, config["reg"])
    ref = ref_logreg.fit(batches, *fit_args)
    model = {**control_logreg.fit(ref_logreg, batches, *fit_args, precision="bfloat16"),
             "pass_rows": [float(rows * n_batches)]}
    problems = agree_logreg.check_fit(model, ref, config["tolerances"], rows * n_batches)
    assert any("pass0_grad_rel" in p for p in problems), problems
    assert not any("pass0_hess_rel" in p for p in problems), problems
    value, limit = agree_logreg.compared([{"model": model}], config["tolerances"],
                                         rows * n_batches)["pass0_grad_rel"]
    assert value > 2 * limit
