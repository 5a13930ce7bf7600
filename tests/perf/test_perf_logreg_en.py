"""The elastic-net logistic cell `logreg_en_d3000.prox_newton_cached`: its
files and entries, the plain reference, a CPU rehearsal of a tiny cell end
to end and traced, planted faults through whole rehearsal runs, the parent
that cannot run it, its two readers, and the float8 and bfloat16 controls
at a size a test can hold."""

import numpy as np
import pytest

import contract
import perf_rehearse_logreg_en as rehearse
from perf.harness import agree_logreg_en, cost, layout, logreg_data, logreg_en_data, observe
from perf.reference import control_logreg_en
from perf.reference import logreg_en as ref_en

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELL = "logreg_en_d3000.prox_newton_cached"
NEW_PER_LAYER = {"prox_inner_iters", "prox_step_roofline"}
#: what the cell reports of the accepted metrics: the cached pass's, and the
#: Newton boundary's (one span family for both logistic jobs)
ACCEPTED = {"pass_cached_share", "rescan_dispatch_ms", "pass_fold_device_ms",
            "pass_fold_roofline", "newton_boundary_ms", "newton_solve_ms",
            "newton_fold_dispatch_ms", "median_pass_rows_per_s", "late_pass_share",
            "compiles_in_window"}
COMPARED = {"rows_not_folded", "support_mismatch", "pass0_grad_rel", "pass0_hess_rel",
            "first_step_rel", "coef_rel", "loss_rel", "rows_refed_in_window",
            "compiles_in_window"}


@pytest.fixture(scope="module")
def config():
    return layout.load_config(ROOT, BENCH, "logreg_en_d3000")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(tmp_path_factory.mktemp("logreg_en"))


def _batches(seed, d, rows, n_batches):
    planted = logreg_en_data.spec(seed, d)
    start = logreg_en_data.start_iterate(seed, planted)
    return [logreg_en_data.device_rows(planted, seed, i, rows)
            for i in range(n_batches)], start


def test_the_cell_is_the_deployment_cut_to_one_chips_rows(config):
    contract.check(ROOT)
    _, cell, cfg, traffic, p = layout.resolve(ROOT, CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert {k: cell[k] for k in ("config", "traffic", "chips", "why")} == {
        k: entry[k] for k in ("config", "traffic", "chips", "why")}
    assert cfg == config and traffic["generator"] == "prox_newton_cached"
    assert cell["chips"] == 1
    # the upstream logistic table at its width, dtype, tol and standardization;
    # the L1 mix and a regParam that selects columns, both said to be assumed
    assert (cfg["algo"], cfg["n_cols"], cfg["n_classes"], cfg["dtype"]) == (
        "logreg_en", 3000, 2, "float32")
    assert "NVIDIA/spark-rapids-ml" in cfg["source"] and "num_cols 3000" in cfg["source"]
    assert (cfg["max_iter"], cfg["tol"], cfg["reg"], cfg["elastic_net"]) == (
        10, 1e-30, 3e-3, 0.8)
    assert (cfg["standardization"], cfg["fit_intercept"]) == (False, True)
    assert cfg["fold_program"] == "jit_update_group"
    assert cfg["prox_program"] == "jit_prox_newton_step"
    assert list(cfg["reduced"]) == ["rows", "max_iter"]
    assert set(cfg["assumed"]) == {"reg", "elastic_net", "data", "start",
                                   "arrow_batch_rows", "daemon_pass_cache_mb"}
    assert "from memory" not in str(cfg)
    assert p == {"batch_rows": 65536, "cached_batches": 8, "partitions": 8, "trace_s": 5.0}
    rows = p["batch_rows"] * p["cached_batches"]
    assert rows == 524_288 and rows * 8 == cfg["rows"]
    assert cell["rows_per_fit"] == rows * cfg["max_iter"]
    # the cached pass — rows, labels, masks — holds a quarter of the chip and
    # fits the job's budget
    held = rows * cfg["n_cols"] * 4 + 2 * rows * 4
    assert 0.25 * 16e9 <= held <= cfg["daemon_pass_cache_mb"] << 20 < 16e9
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, CELL)}
                for kind in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] >= {"pass_rows_per_s", "setup_s"}
    assert reported["per_layer"] >= ACCEPTED | NEW_PER_LAYER
    # the PCA cells' fold metrics are not this cell's: it reports the cached pass's
    assert not reported["per_layer"] & {"fold_device_ms", "fold_roofline"}
    new = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW_PER_LAYER}
    assert {n: (m["source"], m["layer"], m["moves"], m["unit"]) for n, m in new.items()} == {
        "prox_inner_iters": ("program_counter", "model_programs", "pass_rows_per_s",
                             "products"),
        "prox_step_roofline": ("device_trace", "kernels", "pass_rows_per_s", "%")}
    assert all(CELL in m["workloads"] for m in new.values())
    assert set(cfg["tolerances"]) == set(agree_logreg_en.RELATIVE) | {agree_logreg_en.FIRST}
    assert set(cfg["tolerance_reasons"]) == set(cfg["tolerances"]) | {"support_mismatch"}
    assert all(len(reason) > 200 for reason in cfg["tolerance_reasons"].values())
    assert 0 < cfg["support_margin"] < 1e-4


def test_the_costs_are_the_l2_fold_and_one_read_of_the_hessian_a_step(config):
    l2 = layout.load_config(ROOT, BENCH, "logreg_d3000")
    assert cost.fold_cost(config, 65536) == cost.fold_cost(l2, 65536)
    costs = layout.load_module(ROOT, "costs", "logreg_en")
    d = 3000
    flops, nbytes = costs.prox_step(config, 100)
    # the products' operations; the Hessian's bytes once, whatever the products
    assert flops == 100 * 2.0 * d * d and nbytes == 4.0 * d * d + 32.0 * d
    assert costs.prox_step(config, 2000)[1] == nbytes
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    line = cost.roofline(flops, nbytes, 1.37e-3, peaks)
    assert line["bound"] == "memory" and line["least_s"] == pytest.approx(44e-6, rel=0.01)
    assert 0.03 < line["share"] < 0.033
    # a step of 3,000 products is bound by its operations
    assert cost.roofline(*costs.prox_step(config, 3000), 1e-2, peaks)["bound"] == "compute"


def test_the_seeded_rows_plant_w_on_a_tenth_of_the_columns():
    seed, d = 2147483659, 3000
    planted = logreg_en_data.spec(seed, d)
    support = planted["support"]
    assert len(support) == 300 and np.all(np.diff(support) > 0)
    w = planted["w"].astype(np.float64)
    assert np.count_nonzero(w) == 300 and np.all(w[support] != 0)
    loadings = planted["loadings"].astype(np.float64)
    assert w @ w + np.sum((loadings.T @ w) ** 2) == pytest.approx(4.0, rel=1e-5)
    assert 0.05 < np.sqrt(np.mean(w[support] ** 2)) < 0.1  # about 0.08 a coefficient
    # the rows' law and the dense start are the L2 deployment's
    np.testing.assert_array_equal(planted["loadings"],
                                  logreg_data.spec(seed, d)["loadings"])
    start = logreg_en_data.start_iterate(seed, planted)
    assert np.count_nonzero(start["w"]) == d
    x, y = (np.asarray(a) for a in logreg_en_data.device_rows(planted, seed, 1, 2048))
    again = [np.asarray(a) for a in logreg_en_data.device_rows(planted, seed, 1, 2048)]
    np.testing.assert_array_equal(x, again[0])
    np.testing.assert_array_equal(y, again[1])
    assert set(np.unique(y)) == {0.0, 1.0} and np.abs(x).max() < 12


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_references_step_meets_its_optimality_conditions(seed):
    """The host solve is exact: its conditions hold to KKT_TOL, and every
    coordinate it zeroes has a gradient inside the L1 weight."""
    batches, start = _batches(seed, 96, 512, 2)
    reg, alpha = 0.02, 0.8
    stats = ref_en.scan(batches, start["w"], float(start["b"][0]))
    grad, hess = ref_en.model(stats, start["w"], reg, alpha)
    x = np.concatenate([start["w"].astype(np.float64), [float(start["b"][0])]])
    c = grad - hess @ x
    z = ref_en.solve_subproblem(hess, c, reg * alpha, x)
    assert ref_en.kkt_residual(hess, c, reg * alpha, z, True) <= ref_en.KKT_TOL * (
        np.abs(c).max() + reg * alpha)
    assert 0 < np.count_nonzero(z[:-1]) < 96
    # no point nearby does better on the subproblem
    q = lambda v: 0.5 * v @ hess @ v + c @ v + reg * alpha * np.abs(v[:-1]).sum()  # noqa: E731
    rng = np.random.default_rng(seed)
    assert all(q(z) <= q(z + 1e-4 * rng.standard_normal(97)) for _ in range(20))


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_the_tiny_cell_runs_end_to_end_and_traced(root, trace):
    result, lines = rehearse.run(root, rehearse.CELL, seconds=1.0, trace=trace)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] >= 14 and list(result)[-1] == "compared"
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) == rehearse.reports(root, rehearse.CELL, kind)
    assert f"= {rehearse.CACHED_ROWS} rows" in text and "compiles in window: 0" in text
    assert "from the wire (100% cached)" in text
    assert set(result["compared"]) == COMPARED
    for name in ("rows_not_folded", "support_mismatch", "rows_refed_in_window"):
        assert result["compared"][name] == [0.0, 0.0]
    assert "nonzero coefficients at the end of a fit: [" in text
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        off_the_chip = {"pass_fold_device_ms", "pass_fold_roofline", "prox_step_roofline"}
        assert (ACCEPTED | NEW_PER_LAYER) - off_the_chip == set(got)
        assert got["compiles_in_window"] == 0 and got["pass_cached_share"] == 100.0
        assert 0 < got["newton_solve_ms"] < got["newton_boundary_ms"]
        assert got["prox_inner_iters"] > 12  # the power iteration's and FISTA's
        for name in off_the_chip:
            assert f"metric {name}: nothing to read, left out" in text
    else:
        assert {"pass_rows_per_s", "setup_s"} == set(got) and got["pass_rows_per_s"] > 0


def test_a_program_without_the_elastic_net_fails_at_once_and_makes_no_data(
        root, monkeypatch):
    """The parent commit: its estimator has no `elasticNetParam`."""
    from spark_rapids_ml_tpu.models import logistic_regression

    class WithoutTheParam:
        def hasParam(self, name):
            return name != "elasticNetParam"

    monkeypatch.setattr(logistic_regression, "LogisticRegression", WithoutTheParam)
    made = []
    monkeypatch.setattr(logreg_data, "_rows_fn", lambda *a, **k: made.append(a))
    with pytest.raises(RuntimeError, match="has no `elasticNetParam`"):
        rehearse.run(root, rehearse.CELL, seconds=0.2)
    assert made == []


def test_the_new_readers_find_nothing_in_a_program_without_the_step(config):
    """As on the parent commit: each returns None and does not raise."""
    obs = observe.Observation(config, {}, 1.0, {"kind": "TPU v5 lite"}, ROOT)
    obs.before = obs.after = {"metrics": {}}
    for name in NEW_PER_LAYER:
        assert layout.load_module(ROOT, "layer_metrics", name).read(obs) is None


def test_the_step_roofline_counts_the_steps_wholly_inside_the_traced_interval(config):
    obs = observe.Observation(config, {}, 1.0, {"kind": "TPU v5 lite"}, ROOT)
    d = config["n_cols"]
    one_read = (4.0 * d * d + 32.0 * d) / 819e9
    obs.trace = {"devices": {0: {"programs": {"jit_prox_newton_step": {
        "count": 3, "seconds": 3 * one_read / 0.05}}}}}
    obs.notes["traced"] = [10.0, 20.0]
    # one step before the interval, two inside it, one across its end
    obs.notes["prox_steps"] = [[9.0, 9.5, 999], [11.0, 11.1, 200], [12.0, 12.1, 200],
                               [19.9, 20.1, 200]]
    share = layout.load_module(ROOT, "layer_metrics", "prox_step_roofline").read(obs)
    # two steps' bytes over three executions' time: it reads low, never high
    assert share == pytest.approx(5.0 * 2 / 3, rel=1e-3)
    assert obs.notes["prox_step_roofline_bound"] == "memory"


def _a_step_that_drops_the_l1_term(monkeypatch):
    from spark_rapids_ml_tpu.models import logistic_regression

    real = logistic_regression._stream_prox_newton_step_fn

    def dropped(reg, elastic_net, fit_intercept, ad):
        # the L2 weight kept, the L1 weight gone
        return real(reg * (1.0 - elastic_net), 0.0, fit_intercept, ad)

    monkeypatch.setattr(logistic_regression, "_stream_prox_newton_step_fn", dropped)


def _a_step_that_zeroes_the_support(monkeypatch):
    from spark_rapids_ml_tpu.models import logistic_regression

    real = logistic_regression._stream_prox_newton_step_fn

    def zeroing(*args):
        step = real(*args)

        def stepping(*inputs):
            new_w, new_b, delta, products, nonzero = step(*inputs)
            return new_w * 0.0, new_b, delta, products, nonzero * 0

        return stepping

    monkeypatch.setattr(logistic_regression, "_stream_prox_newton_step_fn", zeroing)


def _an_objective_without_its_l1_term(monkeypatch):
    from spark_rapids_ml_tpu.models import logistic_regression

    def smooth_only(lsum, n, reg, elastic_net, w):
        l2 = reg * (1.0 - elastic_net)
        return float(lsum / max(float(n), 1.0)) + 0.5 * l2 * float(np.sum(np.square(w)))

    monkeypatch.setattr(logistic_regression, "stream_elastic_net_objective", smooth_only)


def _an_inner_solve_stopped_early(monkeypatch):
    """The step's forcing term loosened a thousandfold (1e-4 → 1e-1): the
    fit still reaches the same fixed point, so only the first boundary
    shows it."""
    import functools

    from spark_rapids_ml_tpu.models import logistic_regression

    monkeypatch.setattr(logistic_regression, "PROX_FORCING",
                        1e3 * logistic_regression.PROX_FORCING)
    # programs of their own, built under the loosened term
    monkeypatch.setattr(logistic_regression, "_stream_prox_newton_step_fn", functools.lru_cache(
        maxsize=8)(logistic_regression._stream_prox_newton_step_fn.__wrapped__))


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


@pytest.mark.parametrize("fault,caught_by", [
    (_a_step_that_drops_the_l1_term, ("support_mismatch", "coef_rel")),
    (_a_step_that_zeroes_the_support, ("support_mismatch", "coef_rel")),
    (_an_objective_without_its_l1_term, ("loss_rel",)),
    (_an_inner_solve_stopped_early, ("first_step_rel",)),
    (_a_rescan_that_skips_a_batch, ("rows_not_folded",)),
], ids=["a_step_that_drops_the_l1_term", "a_step_that_zeroes_the_support",
        "an_objective_without_its_l1_term", "an_inner_solve_stopped_early",
        "a_rescan_that_skips_a_batch"])
def test_a_fit_with_a_planted_fault_is_not_correct(root, monkeypatch, fault, caught_by):
    fault(monkeypatch)
    result, lines = rehearse.run(root, rehearse.CELL, seconds=0.5)
    assert result["correct"] is False
    assert any("DISAGREES" in line for line in lines), "\n".join(lines)
    for name in caught_by:
        value, limit = result["compared"][name]
        assert value > limit, name


def test_the_control_rounds_the_rows_alone():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.0625, 1.1875, -2.75, 0.4375, 9.5], jnp.float32)
    assert control_logreg_en.lower(x).tolist() == [1.0, 1.0, 1.25, -2.75, 0.4375, 10.0]
    fine = jnp.asarray([1.0, 1.00390625, 1.005859375, -2.76, 9.53125, 0.1], jnp.float32)
    assert control_logreg_en.lower(fine, "bfloat16").tolist() == fine.astype(
        jnp.bfloat16).astype(jnp.float32).tolist()


@pytest.mark.parametrize("precision,caught_by", [
    ("float8_e4m3fn", ("pass0_grad_rel", "pass0_hess_rel", "coef_rel")),
    ("bfloat16", ("pass0_grad_rel",)),
])
@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_in_a_lower_precision_is_not_correct(config, seed, precision,
                                                           caught_by):
    """At d = 256 and 16,384 rows, a control's fit against the reference as
    a run compares a program's: its first pass at the start, its last
    boundary from its own last iterate. The reference against itself reads
    nothing."""
    d, rows, n_batches, passes = 256, 2048, 8, 5
    reg, alpha = 0.01, config["elastic_net"]
    batches, start = _batches(seed, d, rows, n_batches)
    whole = rows * n_batches
    pass0_ref = ref_en.scan(batches, start["w"], float(start["b"][0]))
    first_ref = ref_en.first_step(pass0_ref, start, reg, alpha)
    tol, margin = config["tolerances"], config["support_margin"]

    def judged(got):
        last = ref_en.last_step(batches, got["last_w"], got["last_b"], reg, alpha)
        model = {"w": got["w"], "b": got["b"], "loss": got["loss"], "pass0": got["pass0"],
                 "pass_rows": [float(whole)]}
        problems = agree_logreg_en.check_fit(model, pass0_ref, last, tol, margin, whole)
        compared = agree_logreg_en.compared([{"model": model}], tol, whole)
        compared[agree_logreg_en.FIRST], first_problems = agree_logreg_en.check_first_step(
            {"w": got["first_w"], "b": got["first_b"]}, first_ref, start, tol)
        return problems + first_problems, compared

    problems, _ = judged(ref_en.fit(batches, start, passes, 0.0, reg, alpha))
    assert problems == []
    problems, compared = judged(control_logreg_en.fit(
        ref_en, batches, start, passes, 0.0, reg, alpha, precision=precision))
    for name in caught_by:
        value, limit = compared[name]
        assert value > 2 * limit, (name, value, limit)
    assert compared["rows_not_folded"] == [0.0, 0.0]
