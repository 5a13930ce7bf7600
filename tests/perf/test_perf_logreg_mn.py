"""The multinomial logistic cell `logreg_mn3_d3000.mm_newton_cached`: its
files and entries, the plain reference against a float64 loop and against
the program (the daemon's job, cached and re-fed), a CPU rehearsal of a tiny
cell end to end and traced, planted faults through whole rehearsal runs, and
the float8 and bfloat16 controls at a size a test can hold."""

import numpy as np
import pytest

import contract
import perf_rehearse_logreg_mn as rehearse
from perf.harness import cost, layout, observe

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELL = "logreg_mn3_d3000.mm_newton_cached"
NEW_PER_LAYER = {"softmax_boundary_ms", "softmax_solve_ms", "softmax_fold_dispatch_ms"}
CACHED_PER_LAYER = {"pass_cached_share", "rescan_dispatch_ms", "pass_fold_device_ms",
                    "pass_fold_roofline", "median_pass_rows_per_s", "late_pass_share"}
COMPARED = {"rows_not_folded", "pass0_grad_rel", "pass0_hess_rel", "coef_rel", "loss_rel",
            "rows_refed_in_window", "compiles_in_window"}
STATE = ("gw", "gb", "hw", "hwb", "hbb", "loss", "n")

agree = layout.load_module(ROOT, "harness", "agree_logreg_mn")
data = layout.load_module(ROOT, "harness", "logreg_mn_data")
reference = layout.load_module(ROOT, "reference", "logreg_mn")
control = layout.load_module(ROOT, "reference", "control_logreg_mn")


@pytest.fixture(scope="module")
def config():
    return layout.load_config(ROOT, BENCH, "logreg_mn3_d3000")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(tmp_path_factory.mktemp("logreg_mn"))


def _batches(seed, d, rows, n_batches, classes=3):
    planted = data.spec(seed, d, classes)
    start = data.start_iterate(seed, planted)
    return [data.device_rows(planted, seed, i, rows) for i in range(n_batches)], start


def test_the_cell_is_the_deployment_cut_to_one_chips_rows(config):
    contract.check(ROOT)
    _, cell, cfg, traffic, p = layout.resolve(ROOT, CELL)
    assert cfg == config and traffic["generator"] == "mm_newton_cached" and cell["chips"] == 1
    # three classes, float32, the width, regParam, tol and standardization the
    # upstream suite's multinomial run states; nothing cut but rows and passes
    assert (cfg["algo"], cfg["n_cols"], cfg["n_classes"], cfg["dtype"]) == (
        "logreg_mn", 3000, 3, "float32")
    assert "NVIDIA/spark-rapids-ml" in cfg["source"] and "--n_classes 3" in cfg["source"]
    sources = {c["name"]: c["source"] for c in BENCH["configs"]}
    assert sources["logreg_mn3_d3000"] != sources["logreg_d3000"]
    assert (cfg["max_iter"], cfg["tol"], cfg["reg"], cfg["fit_intercept"]) == (
        10, 1e-30, 1e-5, True)
    assert (cfg["standardization"], cfg["elastic_net"]) == (False, 0.0)
    assert cfg["arrow_batch_rows"] == 65536
    assert cfg["fold_program"] == "jit_softmax_update_group"
    assert list(cfg["reduced"]) == ["rows", "max_iter"]
    assert set(cfg["assumed"]) == {"n_classes", "data", "start", "arrow_batch_rows",
                                   "daemon_pass_cache_mb"}
    assert p == {"batch_rows": 65536, "cached_batches": 8, "partitions": 8, "trace_s": 5.0}
    rows = p["batch_rows"] * p["cached_batches"]
    assert rows == 524_288 and rows * 8 == cfg["rows"]
    assert cell["rows_per_fit"] == rows * cfg["max_iter"] == 5_242_880
    # the cached pass — rows, labels, masks — holds a quarter of the chip and
    # fits the job's budget beside the per-class state
    held = rows * cfg["n_cols"] * 4 + 2 * rows * 4
    state = cfg["n_classes"] * cfg["n_cols"] ** 2 * 4
    assert held == 6_295_650_304 and state == 108_000_000
    assert 0.25 * 16e9 <= held <= cfg["daemon_pass_cache_mb"] << 20 < held + state + 16e9 / 4
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, CELL)}
                for kind in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == {"pass_rows_per_s", "setup_s"}
    assert reported["per_layer"] == {"compiles_in_window"} | NEW_PER_LAYER | CACHED_PER_LAYER
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_PER_LAYER | CACHED_PER_LAYER:
            assert CELL in m["workloads"] and m["moves"] == "pass_rows_per_s"
    assert {m["name"]: (m["layer"], m["source"]) for m in BENCH["per_layer"]
            if m["name"] in NEW_PER_LAYER} == {
        "softmax_boundary_ms": ("daemon", "program_span"),
        "softmax_solve_ms": ("model_programs", "program_span"),
        "softmax_fold_dispatch_ms": ("model_programs", "program_counter")}
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"]
               if m["name"] in NEW_PER_LAYER)
    assert set(cfg["tolerances"]) == set(agree.RELATIVE)
    # each limit is written with its reason and the readings that set it
    assert all(len(cfg["tolerance_reasons"][name]) > 200 for name in cfg["tolerances"])


def test_the_folds_cost_is_the_algorithms_and_compute_bound(config):
    n, d, c = 524_288, 3000, 3
    flops, nbytes = cost.fold_cost(config, n)
    assert flops == 2.0 * c * n * d * d + 6.0 * c * n * d
    assert nbytes == 4.0 * n * d + 8.0 * n + 8.0 * c * d * d
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    line = cost.roofline(flops, nbytes, 0.2, peaks)
    assert line["bound"] == "compute"
    assert line["least_s"] == pytest.approx(0.1439, rel=0.01)


def test_the_seeded_rows_and_labels_are_the_law_the_configuration_states():
    seed, d, classes = 2147483659, 3000, 3
    planted = data.spec(seed, d, classes)
    assert planted["loadings"].shape == (d, data.RANK)
    assert planted["w"].shape == (d, classes) and planted["b"].shape == (classes,)
    loadings = planted["loadings"].astype(np.float64)
    w = planted["w"].astype(np.float64)
    # each class's logits: standard deviation 2 under the law's covariance I + L Lᵀ
    for c in range(classes):
        assert w[:, c] @ w[:, c] + np.sum((loadings.T @ w[:, c]) ** 2) == pytest.approx(
            4.0, rel=1e-5)
    assert abs(float(planted["b"].sum())) < 1e-6 and np.abs(planted["b"]).max() <= 0.5
    x, y = (np.asarray(a) for a in data.device_rows(planted, seed, 3, 4096))
    again = [np.asarray(a) for a in data.device_rows(planted, seed, 3, 4096)]
    np.testing.assert_array_equal(x, again[0])  # the same seed and index: the same batch
    np.testing.assert_array_equal(y, again[1])
    assert x.dtype == y.dtype == np.float32
    assert set(np.unique(y)) == {0.0, 1.0, 2.0}
    shares = np.bincount(y.astype(np.int64), minlength=classes) / len(y)
    assert np.all(np.abs(shares - 1 / 3) < 0.08)  # near a third each
    assert np.abs(x).max() < 12  # far inside what float8_e4m3 holds
    z = x.astype(np.float64) @ w + planted["b"]
    assert np.all((1.8 < z.std(axis=0)) & (z.std(axis=0) < 2.2))
    # classes overlap: a draw from the softmax, not the argmax of the logits
    assert 0.2 < np.mean(np.argmax(z, axis=1) != y) < 0.5
    start = data.start_iterate(seed, planted)
    assert start["w"].shape == (d, classes) and start["b"].shape == (classes,)
    z0 = x.astype(np.float64) @ start["w"].astype(np.float64)
    assert np.all((0.4 < z0.std(axis=0)) & (z0.std(axis=0) < 0.6))


@pytest.mark.parametrize("seed", [5, 3000000019])
def test_the_references_blocks_against_a_float64_numpy_loop(seed):
    """Every per-class block, border, gradient and loss of one scan, against
    the same sums written as a float64 loop over the rows' classes."""
    d, rows, classes = 48, 500, 3
    batches, start = _batches(seed, d, rows, 2, classes)
    got = reference.scan(batches, start["w"], start["b"])
    x = np.concatenate([np.asarray(b[0], np.float64) for b in batches])
    y = np.concatenate([np.asarray(b[1]) for b in batches]).astype(np.int64)
    z = x @ start["w"].astype(np.float64) + start["b"].astype(np.float64)
    p = np.exp(z - z.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    onehot = np.eye(classes)[y]
    assert got["n"] == 2 * rows
    np.testing.assert_allclose(got["gw"], x.T @ (p - onehot), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got["gb"], (p - onehot).sum(axis=0), rtol=1e-4, atol=1e-3)
    loss = np.sum(np.log(np.exp(z).sum(axis=1)) - z[np.arange(len(y)), y])
    assert got["loss"] == pytest.approx(loss, rel=1e-5)
    for c in range(classes):
        block = sum(p[i, c] * np.outer(x[i], x[i]) for i in range(len(y)))
        np.testing.assert_allclose(got["hw"][c], block, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(got["hwb"][c], x.T @ p[:, c], rtol=1e-4, atol=1e-3)
        assert got["hbb"][c] == pytest.approx(p[:, c].sum(), rel=1e-5)


@pytest.mark.parametrize("how", ["cached", "refed"])
@pytest.mark.parametrize("seed", [7, 3000000019])
def test_the_programs_job_against_the_plain_reference(mesh1, seed, how):
    """`_Job` fed once and folded from its cache — or fed every pass —
    against the reference over the same batches from the same start, the
    whole trajectory and the last pass taken from the program's iterate.
    Off the chip the program computes in float64, so the two differ by the
    reference's float32 only."""
    from spark_rapids_ml_tpu import config as program_config
    from spark_rapids_ml_tpu.serve.daemon import _Job

    d, rows, n_batches, passes, reg = 64, 300, 5, 6, 1e-4
    batches, start = _batches(seed, d, rows, n_batches)
    host = [(np.asarray(x), np.asarray(y)) for x, y in batches]
    with program_config.option("daemon_pass_cache_mb", 8 if how == "cached" else 0):
        job = _Job("logreg", d, mesh1, {"n_classes": 3})
    job.set_iterate(start, 0)
    first, counted, info, before = None, [], None, None
    for it in range(passes):
        if it == passes - 1:
            before = job.get_iterate()[0]
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
    model = {"w": iterate["w"], "b": iterate["b"], "loss": info["loss"],
             "pass0": dict(zip(STATE, first)), "pass_rows": counted}
    ref = reference.fit(batches, start, passes, 0.0, reg)
    assert ref["n_iter"] == passes and ref["rows"] == rows * n_batches
    last = reference.one_pass(batches, before, reg)
    tight = {"pass0_grad_rel": 1e-6, "pass0_hess_rel": 1e-6, "coef_rel": 1e-6,
             "loss_rel": 1e-6}
    assert agree.check_fit(model, ref["pass0"], last, tight, rows * n_batches) == []
    # the whole trajectory agrees too, at the program's float64 against float32
    assert agree.coef_rel(iterate["w"], iterate["b"], ref["w"], ref["b"]) < 1e-5
    assert info["loss"] == pytest.approx(ref["loss"], rel=1e-6)


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
    assert "the reference's last pass from 1 distinct iterate(s)" in text
    assert set(result["compared"]) == COMPARED
    assert result["compared"]["rows_not_folded"] == [0.0, 0.0]
    assert result["compared"]["rows_refed_in_window"] == [0.0, 0.0]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        off_the_chip = CACHED_PER_LAYER - {"pass_fold_device_ms", "pass_fold_roofline"}
        assert NEW_PER_LAYER | off_the_chip | {"compiles_in_window"} == set(got)
        assert got["compiles_in_window"] == 0
        assert 0 < got["softmax_solve_ms"] < got["softmax_boundary_ms"]
        assert got["softmax_fold_dispatch_ms"] > 0
        assert got["pass_cached_share"] == 100.0 and got["rescan_dispatch_ms"] > 0
        assert got["median_pass_rows_per_s"] > 0 and 0 <= got["late_pass_share"] < 100
        for name in ("pass_fold_device_ms", "pass_fold_roofline"):
            assert f"metric {name}: nothing to read, left out" in text
    else:
        assert {"pass_rows_per_s", "setup_s"} == set(got) and got["pass_rows_per_s"] > 0
    assert "a pass: p10 " in text and "x the median, " in text


def test_a_program_whose_multinomial_job_keeps_no_pass_fails_at_once_and_makes_no_data(
        root, monkeypatch):
    """The parent commit: the generator asks `cacheable_for` three classes
    first, and stops there."""
    from spark_rapids_ml_tpu.models.logistic_regression import LogisticRegressionJob

    monkeypatch.setattr(LogisticRegressionJob, "cacheable_for", classmethod(
        lambda cls, params: cls.feed_classes(params) <= 2))
    made = []
    monkeypatch.setattr(data, "device_rows", lambda *a, **k: made.append(a))
    with pytest.raises(RuntimeError, match="is not `cacheable_for`"):
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

    real = logistic_regression._stream_multinomial_step_fn

    def altered(*args):
        step = real(*args)

        def stepping(gw, gb, hw, hwb, hbb, n, w, b):
            new_w, new_b, delta = step(gw, gb, hw, hwb, hbb, n, w, b)
            return new_w * 1.01, new_b, delta

        return stepping

    monkeypatch.setattr(logistic_regression, "_stream_multinomial_step_fn", altered)


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


def test_the_control_rounds_the_rows_alone():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.0625, 1.1875, -2.75, 0.4375, 9.5], jnp.float32)
    assert control.lower(x).tolist() == [1.0, 1.0, 1.25, -2.75, 0.4375, 10.0]
    fine = jnp.asarray([1.0, 1.00390625, 1.005859375, -2.76, 9.53125, 0.1], jnp.float32)
    assert control.lower(fine, "bfloat16").tolist() == fine.astype(
        jnp.bfloat16).astype(jnp.float32).tolist() != fine.tolist()
    seen = []

    def spy(a):
        seen.append(a.shape)
        return a

    batches = [(np.ones((64, 8), np.float32), np.zeros(64, np.float32)),
               (np.zeros((64, 8), np.float32), np.full(64, 2.0, np.float32))]
    stats = reference.scan(batches, np.zeros((8, 3), np.float32), np.zeros(3, np.float32),
                           rounded=spy)
    assert seen == [(64, 8), (64, 8)] and stats["n"] == 128  # every batch's rows, no more
    np.testing.assert_allclose(stats["hbb"], [128 / 3] * 3, rtol=1e-6)


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
@pytest.mark.parametrize("precision,fails_by", [
    ("float8_e4m3fn", ("pass0_grad_rel", "pass0_hess_rel", "coef_rel")),
    ("bfloat16", ("pass0_grad_rel", "coef_rel")),
])
def test_the_reference_in_a_lower_precision_is_not_correct(config, seed, precision, fails_by):
    """At d = 64 the controls read over the limits that decide on the chip,
    compared as a run compares the program — the first pass at the start,
    the last pass from the control's own iterate; the true reference in
    their place reads correct. The bfloat16 control's curvature is the
    program's own by design: its gradient has to catch it."""
    d, rows, n_batches, passes = 64, 2048, 8, 4
    batches, start = _batches(seed, d, rows, n_batches)
    fit_args = (passes, 0.0, config["reg"])
    whole = rows * n_batches
    pass0_ref = reference.scan(batches, start["w"], start["b"])

    def checked(model):
        last = reference.one_pass(batches, model["before_last"], config["reg"])
        model = {**model, "pass_rows": [float(whole)]}
        return model, agree.check_fit(model, pass0_ref, last, config["tolerances"], whole)

    _, problems = checked(reference.fit(batches, start, *fit_args))
    assert problems == []
    model, problems = checked(control.fit(reference, batches, start, *fit_args,
                                          precision=precision))
    for name in fails_by:
        assert any(name in p for p in problems), problems
    compared = agree.compared([{"model": model}], config["tolerances"], whole)
    assert compared["rows_not_folded"] == [0.0, 0.0]
    value, limit = compared["pass0_grad_rel"]
    assert value > 2 * limit
