"""Elastic-net logistic regression (Spark ML's `elasticNetParam`): the
daemon job's proximal-Newton boundary, the in-process fits, and the Spark
surface — against the plain reference of the benchmark
(`perf/reference/logreg_en.py`: float64 sums, the step solved exactly on
the host).

The objective is Spark's: 1/n Σ logloss + λ(α‖w‖₁ + (1 − α)/2‖w‖²), the
intercept unpenalised. With α = 0 nothing changes: the same programs as the
L2 fit, bit for bit."""

import numpy as np
import pytest

from perf.harness import agree_logreg, agree_logreg_en, logreg_en_data
from perf.reference import logreg_en as ref_en
from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import logistic_regression as lr
from spark_rapids_ml_tpu.models.logistic_regression import (
    LogisticRegression,
    LogisticRegressionModel,
    fit_logistic_regression,
    fit_logistic_stream,
)
from spark_rapids_ml_tpu.serve import DataPlaneDaemon
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils.xprof import LEDGER

STATE = ("gw", "gb", "hww", "hwb", "hbb", "loss", "n")


def _batches(seed, d, rows, n_batches):
    planted = logreg_en_data.spec(seed, d)
    start = logreg_en_data.start_iterate(seed, planted)
    batches = [tuple(np.asarray(a) for a in logreg_en_data.device_rows(planted, seed, i, rows))
               for i in range(n_batches)]
    return batches, start, planted


def _job(mesh, cache_mb, d):
    with config.option("daemon_pass_cache_mb", cache_mb):
        return _Job("logreg", d, mesh, {"n_classes": 2})


def _job_fit(mesh, batches, start, passes, params, cache_mb=8):
    """The daemon's job fed once and stepped `passes` times, every pass
    after the first a `rescan` where it keeps its pass (fed again where it
    does not) → (iterate, step infos, the first pass's state)."""
    d = batches[0][0].shape[1]
    job = _job(mesh, cache_mb, d)
    job.set_iterate(start, 0)
    infos, first = [], None
    for it in range(passes):
        if it and cache_mb:
            job.rescan(it)
        else:
            for x, y in batches:
                job.fold(x, y, pass_id=it)
        if first is None:
            first = dict(zip(STATE, (np.asarray(a) for a in job.peek_pass_state()[0])))
        infos.append(job.step(params))
    iterate = job.get_iterate()[0]
    return iterate, infos, first


def _objective(batches, w, b, reg, alpha):
    return ref_en.objective(ref_en.scan(batches, w, b), w, reg, alpha)


@pytest.mark.parametrize("d,rows,alpha,reg", [
    (64, 512, 1.0, 0.03),     # pure L1
    (256, 1024, 0.8, 0.02),   # elastic net: λ₂ > 0
], ids=["l1_d64", "elastic_net_d256"])
@pytest.mark.parametrize("seed", [5, 2147483659])
def test_the_jobs_proximal_newton_fit_is_the_reference_fit(mesh1, seed, d, rows, alpha, reg):
    """Coefficients, support and objective of the job's fit against the
    reference's from the same start over the same batches."""
    batches, start, planted = _batches(seed, d, rows, 4)
    passes = 12
    iterate, infos, first = _job_fit(mesh1, batches, start, passes,
                                     {"reg": reg, "elastic_net": alpha, "fit_intercept": True})
    ref = ref_en.fit(batches, start, passes, 0.0, reg, alpha)
    w, b = iterate["w"], float(iterate["b"][0])
    assert agree_logreg.coef_rel(w, b, ref["w"], ref["b"]) < 1e-6
    assert agree_logreg_en.support_mismatch(w, ref["w"], 1e-9) == 0
    assert 0 < np.count_nonzero(w) < d  # it selects: a sparse model, not a dense one
    assert infos[-1]["nonzero"] == np.count_nonzero(w)
    # the objective with its L1 term, what every step reports, is the
    # reference's (whose batch sums are float32)
    assert infos[-1]["loss"] == pytest.approx(ref["loss"], rel=1e-7)
    assert _objective(batches, w, b, reg, alpha) <= _objective(
        batches, ref["w"], ref["b"], reg, alpha) * (1 + 1e-7)
    # the first pass is the L2 fold's, at the start
    parts = agree_logreg.pass0_parts(first, ref["pass0"])
    assert max(parts.values()) < 1e-6


@pytest.mark.parametrize("seed", [5, 3000000019])
def test_the_objective_does_not_increase_across_passes(mesh1, seed):
    batches, start, _ = _batches(seed, 128, 512, 4)
    _, infos, _ = _job_fit(mesh1, batches, start, 8,
                           {"reg": 0.02, "elastic_net": 0.8, "fit_intercept": True})
    losses = [info["loss"] for info in infos]
    assert all(b <= a * (1 + 1e-12) for a, b in zip(losses, losses[1:])), losses
    assert losses[-1] < losses[0]
    # every step took products of the Hessian and left a sparse iterate
    assert all(info["prox_products"] > lr.PROX_POWER_ITER for info in infos[:3])
    assert all(0 < info["nonzero"] < 128 for info in infos)


def test_the_intercept_is_unpenalised(mesh1):
    """An L1 weight above every column's gradient zeroes w; the intercept
    then takes the intercept-only optimum, the log-odds of the labels."""
    batches, _, _ = _batches(11, 32, 512, 2)
    start = {"w": np.zeros(32, np.float32), "b": np.zeros(1, np.float32)}
    iterate, infos, _ = _job_fit(mesh1, batches, start, 6,
                                 {"reg": 10.0, "elastic_net": 1.0, "fit_intercept": True})
    y = np.concatenate([b[1] for b in batches]).astype(np.float64)
    assert not np.any(iterate["w"]) and infos[-1]["nonzero"] == 0
    assert float(iterate["b"][0]) == pytest.approx(np.log(y.mean() / (1 - y.mean())), abs=1e-9)
    # fit_intercept False leaves the intercept where it was
    iterate, _, _ = _job_fit(mesh1, batches, {"w": start["w"], "b": np.asarray([0.25])}, 8,
                             {"reg": 0.02, "elastic_net": 0.8, "fit_intercept": False})
    assert float(iterate["b"][0]) == 0.25
    ref = ref_en.fit(batches, {"w": start["w"], "b": np.asarray([0.25])}, 8, 0.0, 0.02, 0.8,
                     fit_intercept=False)
    assert ref["b"] == 0.25
    assert agree_logreg.coef_rel(iterate["w"], 0.25, ref["w"], ref["b"]) < 1e-6


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_a_cached_pass_equals_a_refed_one(mesh8, seed):
    """Direct feeds in one order and the cache: bit-equal iterates, step by
    step, with the L1 step at every boundary."""
    batches, start, _ = _batches(seed, 48, 1000, 3)
    params = {"reg": 0.02, "elastic_net": 0.8, "fit_intercept": True}
    fed, fed_infos, _ = _job_fit(mesh8, batches, start, 4, params, cache_mb=0)
    cached, cached_infos, _ = _job_fit(mesh8, batches, start, 4, params, cache_mb=8)
    for key in ("w", "b"):
        np.testing.assert_array_equal(fed[key], cached[key])
    assert [{k: v for k, v in i.items()} for i in fed_infos] == cached_infos


def _ledger_calls():
    return {name: entry["calls"] for name, entry in LEDGER.snapshot().items()}


def test_elastic_net_zero_is_the_l2_fit_bit_for_bit_through_its_programs(mesh1):
    batches, start, _ = _batches(13, 40, 600, 3)
    before = _ledger_calls()
    plain, plain_infos, _ = _job_fit(mesh1, batches, start, 4,
                                     {"reg": 0.01, "fit_intercept": True})
    mid = _ledger_calls()
    zero, zero_infos, _ = _job_fit(mesh1, batches, start, 4,
                                   {"reg": 0.01, "elastic_net": 0.0, "fit_intercept": True})
    after = _ledger_calls()
    for key in ("w", "b"):
        np.testing.assert_array_equal(plain[key], zero[key])
    assert plain_infos == zero_infos and set(zero_infos[0]) == {"delta", "loss", "iteration",
                                                                "pass_rows"}

    def grew(a, b):
        return {k for k, v in b.items() if v > a.get(k, 0)}

    assert grew(before, mid) == grew(mid, after)
    assert "logreg.newton_step" in grew(mid, after)
    assert "logreg.prox_newton_step" not in grew(before, after)
    # an elasticNetParam without a regParam has nothing to mix: the L2 path
    none, _, _ = _job_fit(mesh1, batches, start, 2, {"reg": 0.0, "elastic_net": 0.5})
    assert "logreg.prox_newton_step" not in grew(after, _ledger_calls())
    assert np.count_nonzero(none["w"]) == 40
    # the estimator: elasticNetParam 0 is the default fit, bit for bit
    x = np.concatenate([b[0] for b in batches]).astype(np.float64)
    y = np.concatenate([b[1] for b in batches])
    ds = {"features": x, "label": y}
    a = LogisticRegression(mesh=mesh1).setRegParam(0.01).fit(ds)
    b = LogisticRegression(mesh=mesh1).setRegParam(0.01).setElasticNetParam(0.0).fit(ds)
    np.testing.assert_array_equal(a.coefficients, b.coefficients)
    assert float(a.intercept) == float(b.intercept)


def test_the_counters_count_the_inner_solve_and_hold_the_support(mesh1):
    batches, start, _ = _batches(17, 64, 512, 2)
    metrics_mod.reset()
    _, infos, _ = _job_fit(mesh1, batches, start, 3,
                           {"reg": 0.03, "elastic_net": 0.8, "fit_intercept": True})
    snap = metrics_mod.snapshot()
    counted = sum(s["value"] for s in snap["srml_logreg_prox_iterations_total"]["samples"])
    assert counted == sum(i["prox_products"] for i in infos)
    held = snap["srml_logreg_nonzero_coefficients"]["samples"][0]["value"]
    assert held == infos[-1]["nonzero"]
    # the step is timed under the Newton spans: one span family for both jobs
    phases = {s["labels"]["phase"]: s["count"] for s in
              snap["srml_phase_duration_seconds"]["samples"]}
    assert phases["newton.solve"] == phases["newton.boundary"] == 3


def test_multinomial_with_an_l1_term_is_refused(mesh1, rng):
    x = rng.normal(size=(90, 4))
    y = np.arange(90) % 3
    with pytest.raises(ValueError, match="not supported for multinomial"):
        fit_logistic_regression(x, y, reg=0.1, elastic_net=0.5, mesh=mesh1)
    with pytest.raises(ValueError, match="not supported for multinomial"):
        LogisticRegression(mesh=mesh1).setRegParam(0.1).setElasticNetParam(0.5).fit(
            {"features": x, "label": y.astype(np.float64)})
    job = _Job("logreg", 4, mesh1, {"n_classes": 3})
    job.fold(x.astype(np.float32), y.astype(np.float64), pass_id=0)
    with pytest.raises(ValueError, match="not supported for multinomial"):
        job.step({"reg": 0.1, "elastic_net": 0.5})
    with pytest.raises(ValueError, match=r"in \[0, 1\]"):
        lr.check_elastic_net(1.5, 0.1, 2)
    # α = 0 stays the MM-Newton fit
    fit_logistic_regression(x, y, reg=0.1, elastic_net=0.0, mesh=mesh1, max_iter=2)


@pytest.mark.parametrize("alpha", [1.0, 0.5])
def test_the_in_process_fits_take_the_same_step(mesh8, alpha):
    """`LogisticRegression.fit` and `fit_logistic_stream`: the job's
    proximal-Newton passes from w = 0, against the reference from w = 0."""
    batches, _, _ = _batches(23, 32, 800, 2)
    x = np.concatenate([b[0] for b in batches]).astype(np.float64)
    y = np.concatenate([b[1] for b in batches])
    reg = 0.02
    zero = {"w": np.zeros(32, np.float32), "b": np.zeros(1, np.float32)}
    ref = ref_en.fit(batches, zero, 15, 0.0, reg, alpha)
    model = (LogisticRegression(mesh=mesh8).setRegParam(reg).setElasticNetParam(alpha)
             .setMaxIter(15).setTol(0.0).fit({"features": x, "label": y}))
    assert agree_logreg.coef_rel(model.coefficients, float(model.intercept),
                                 ref["w"], ref["b"]) < 1e-6
    assert agree_logreg_en.support_mismatch(model.coefficients, ref["w"], 1e-9) == 0
    assert model.summary.loss == pytest.approx(ref["loss"], rel=1e-7)
    sol = fit_logistic_stream(lambda: iter(batches), 32, reg=reg, elastic_net=alpha,
                              max_iter=15, tol=0.0, mesh=mesh8)
    np.testing.assert_allclose(sol.coefficients, model.coefficients, rtol=0, atol=1e-9)
    assert sol.loss == pytest.approx(ref["loss"], rel=1e-7)


def test_the_in_process_fit_places_its_rows_once(mesh8, monkeypatch):
    """Every pass of `fit_logistic_regression` with an L1 term folds the
    shards it placed before the first: rows and labels go to the devices
    once a fit, not once a pass."""
    batches, _, _ = _batches(31, 32, 800, 1)
    x, y = batches[0]
    placed = []
    real = lr.shard_rows

    def counting(a, *args, **kwargs):
        placed.append(np.shape(a))
        return real(a, *args, **kwargs)

    monkeypatch.setattr(lr, "shard_rows", counting)
    before = _ledger_calls().get("logreg.streaming_update", 0)
    sol = fit_logistic_regression(x.astype(np.float64), y, reg=0.02, elastic_net=0.8,
                                  max_iter=6, tol=0.0, mesh=mesh8)
    assert sol.n_iter == 6
    assert placed == [(800, 32), (800,)]
    assert _ledger_calls()["logreg.streaming_update"] - before == 6


def test_the_param_persists_with_the_estimator_and_the_model(mesh1, tmp_path):
    est = LogisticRegression(mesh=mesh1)
    assert est.getElasticNetParam() == 0.0
    assert est.setElasticNetParam(0.8) is est and est.getElasticNetParam() == 0.8
    with pytest.raises(Exception):
        LogisticRegression().setElasticNetParam(1.5)
    path = str(tmp_path / "est")
    est.save(path)
    assert LogisticRegression.load(path).getElasticNetParam() == 0.8
    batches, _, _ = _batches(29, 16, 256, 1)
    x, y = batches[0]
    model = est.setRegParam(0.05).setMaxIter(5).fit(
        {"features": x.astype(np.float64), "label": y})
    assert model.getElasticNetParam() == 0.8
    path = str(tmp_path / "model")
    model.save(path)
    loaded = LogisticRegressionModel.load(path)
    assert loaded.getElasticNetParam() == 0.8
    np.testing.assert_array_equal(loaded.coefficients, model.coefficients)


def test_spark_elastic_net_fits_through_the_daemons_cached_pass(mesh8, monkeypatch):
    """`SparkLogisticRegression().setElasticNetParam(0.8).setRegParam(…)`:
    rows and labels cross the wire once, every later pass is a `rescan`,
    `elastic_net` rides the step, and the model is the reference's."""
    from sparksim import SimDataFrame, simdf_from_numpy
    from spark_rapids_ml_tpu.spark import estimator as spark_est
    from spark_rapids_ml_tpu.spark.estimator import SparkLogisticRegression

    spark_est.register_dataframe_type(SimDataFrame)
    batches, _, _ = _batches(31, 24, 900, 1)
    x, y = batches[0]
    reg, alpha, passes = 0.03, 0.8, 10
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            df = simdf_from_numpy(x, n_partitions=3, label=y.astype(np.float64),
                                  concurrency=1)
            est = (SparkLogisticRegression().setRegParam(reg).setElasticNetParam(alpha)
                   .setMaxIter(passes).setTol(0.0))
            assert est.getElasticNetParam() == alpha
            model = est.fit(df)
    snap = metrics_mod.snapshot()

    def counter(name, **labels):
        return sum(s["value"] for s in (snap.get(name) or {}).get("samples", [])
                   if all(s["labels"].get(k) == v for k, v in labels.items()))

    # (tol 0: a fit that reaches its fixed point, a step of 0, stops there)
    assert 4 <= model.summary.numIter <= passes
    assert counter("srml_daemon_pass_rows_total", source="wire") == len(x)
    assert counter("srml_daemon_pass_rows_total", source="cache") == (
        model.summary.numIter - 1) * len(x)
    assert counter("srml_logreg_prox_iterations_total") > passes * lr.PROX_POWER_ITER
    zero = {"w": np.zeros(24, np.float32), "b": np.zeros(1, np.float32)}
    ref = ref_en.fit(batches, zero, passes, 0.0, reg, alpha)
    assert agree_logreg.coef_rel(model.coefficients, float(model.intercept),
                                 ref["w"], ref["b"]) < 1e-6
    assert agree_logreg_en.support_mismatch(model.coefficients, ref["w"], 1e-9) == 0
    assert 0 < np.count_nonzero(model.coefficients) < 24
    assert model.getElasticNetParam() == alpha


def test_spark_multinomial_elastic_net_is_refused_before_a_row_is_fed(mesh8, monkeypatch):
    from sparksim import SimDataFrame, simdf_from_numpy
    from spark_rapids_ml_tpu.spark import estimator as spark_est
    from spark_rapids_ml_tpu.spark.estimator import SparkLogisticRegression

    spark_est.register_dataframe_type(SimDataFrame)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(300, 4)).astype(np.float32)
    y = (np.arange(300) % 3).astype(np.float64)
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        metrics_mod.reset()
        df = simdf_from_numpy(x, n_partitions=2, label=y, concurrency=1)
        with pytest.raises(ValueError, match="not supported for multinomial"):
            SparkLogisticRegression().setRegParam(0.1).setElasticNetParam(0.5).fit(df)
    snap = metrics_mod.snapshot()
    fed = sum(s["value"] for s in (snap.get("srml_daemon_pass_rows_total") or {}).get(
        "samples", []))
    assert fed == 0
