"""The KMeans cell `kmeans_d256_k100.lloyd_cached` (PR 28): its files and
entries, the program against the plain reference, a CPU rehearsal of a tiny
cell end to end and traced, planted faults through whole rehearsal runs, and
the float8 control at a size a test can hold."""

import numpy as np
import pytest

import contract
import perf_rehearse_kmeans as rehearse
from perf.harness import agree_kmeans, cost, kmeans_data, layout, observe
from perf.reference import control_kmeans
from perf.reference import kmeans as ref_kmeans

ROOT = layout.REPO_ROOT
BENCH = layout.load_benchmark(ROOT)
CELL = "kmeans_d256_k100.lloyd_cached"
NEW_PER_LAYER = {"pass_cached_share", "rescan_dispatch_ms", "lloyd_boundary_ms",
                 "lloyd_fold_dispatch_ms"}
#: PR 35: the cached cells' rate under a name and a bound of its own, and
#: what stands beside it
PASS_PER_LAYER = {"pass_fold_device_ms", "pass_fold_roofline", "median_pass_rows_per_s",
                  "late_pass_share"}


@pytest.fixture(scope="module")
def config():
    return layout.load_config(ROOT, BENCH, "kmeans_d256_k100")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(tmp_path_factory.mktemp("kmeans"))


def test_the_cell_is_the_sources_deployment_cut_to_one_chips_eighth(config):
    contract.check(ROOT)
    _, cell, cfg, traffic, p = layout.resolve(ROOT, CELL)
    assert cfg == config and traffic["generator"] == "lloyd_cached" and cell["chips"] == 1
    # the published widths, nothing cut but the rows
    assert (cfg["algo"], cfg["n_cols"], cfg["k"], cfg["rows"], cfg["dtype"]) == (
        "kmeans", 256, 100, 50_000_000, "float32")
    assert (cfg["max_iter"], cfg["tol"], cfg["arrow_batch_rows"]) == (20, 0.0, 65536)
    assert list(cfg["reduced"]) == ["rows"]
    assert p == {"batch_rows": 65536, "cached_batches": 96, "partitions": 8, "trace_s": 5.0}
    rows = p["batch_rows"] * p["cached_batches"]
    assert rows == 6_291_456 and 0 < rows / (cfg["rows"] / 8) - 1 < 0.007
    assert cell["rows_per_fit"] == rows * (cfg["max_iter"] + 1)
    # the cached pass holds a quarter of the chip and fits the job's budget
    held = rows * cfg["n_cols"] * 4 + rows * 4
    assert 0.25 * 2**34 <= held <= cfg["daemon_pass_cache_mb"] << 20 < 16e9
    reported = {kind: {m["name"] for m in layout.metric_entries(BENCH, kind, CELL)}
                for kind in ("end_to_end", "per_layer")}
    # by name, not by `==`: a later cached cell lists these by addition
    assert reported["end_to_end"] == {"pass_rows_per_s", "setup_s"}
    assert reported["per_layer"] >= {"compiles_in_window"} | NEW_PER_LAYER | PASS_PER_LAYER
    # a per-layer metric names the one end-to-end metric its cells report
    assert not reported["per_layer"] & {"fold_device_ms", "fold_roofline"}
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_PER_LAYER | PASS_PER_LAYER:
            assert CELL in m["workloads"] and m["moves"] == "pass_rows_per_s"
    assert {m["layer"] for m in BENCH["per_layer"] if m["name"] in NEW_PER_LAYER} == {
        "daemon", "model_programs"}
    assert {m["name"]: (m["layer"], m["source"], m["unit"], m["better"])
            for m in BENCH["per_layer"] if m["name"] in PASS_PER_LAYER} == {
        "pass_fold_device_ms": ("kernels", "device_trace", "ms", "lower"),
        "pass_fold_roofline": ("kernels", "device_trace", "%", "higher"),
        "median_pass_rows_per_s": ("daemon", "host_clock", "rows/s", "higher"),
        "late_pass_share": ("daemon", "host_clock", "%", "lower")}
    assert set(cfg["tolerances"]) == {"pass0_stats_rel", "centers_rel", "cost_rel"}


def test_the_folds_cost_is_memory_bound_at_the_cells_shape(config):
    flops, nbytes = cost.fold_cost(config, 65536)
    n, d, k = 65536, 256, 100
    assert flops == 4.0 * n * d * k + 3.0 * n * d and nbytes == 4.0 * n * d + 8.0 * k * d
    line = cost.roofline(flops, nbytes, 223.6e-6, {"bf16_flops_per_s": 197e12,
                                                   "hbm_bytes_per_s": 819e9})
    assert line["bound"] == "memory" and 0.36 < line["share"] < 0.37
    assert line["least_s"] == pytest.approx(82.2e-6, rel=0.01)


def test_the_seeded_rows_are_the_law_the_configuration_states():
    planted = kmeans_data.spec(2147483659, 256, 100)
    assert planted["centres"].shape == (100, 256) and planted["centres"].dtype == np.float32
    weights = np.exp(planted["log_weights"].astype(np.float64))
    assert weights.sum() == pytest.approx(1.0, abs=1e-6)
    assert weights[0] / weights[99] == pytest.approx(10.0, rel=1e-5)
    # nearest planted neighbours a few noise standard deviations apart
    c = planted["centres"].astype(np.float64)
    gaps = np.linalg.norm(c[:, None] - c[None], axis=-1) + np.eye(100) * 1e9
    assert 1.0 < np.median(gaps.min(axis=1)) < 5.0
    a = np.asarray(kmeans_data.device_rows(planted, 2147483659, 3, 2048))
    b = np.asarray(kmeans_data.device_rows(planted, 2147483659, 3, 2048))
    other = np.asarray(kmeans_data.device_rows(planted, 2147483659, 4, 2048))
    np.testing.assert_array_equal(a, b)  # the same seed and index: the same rows
    assert a.dtype == np.float32 and not np.array_equal(a, other)
    assert np.abs(a).max() < 30  # far inside what float8_e4m3 holds
    start = kmeans_data.start_centres(2147483659, a, 100)
    assert start.shape == (100, 256) and len({r.tobytes() for r in start}) == 100
    np.testing.assert_array_equal(start, kmeans_data.start_centres(2147483659, a, 100))


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_the_programs_job_against_the_plain_reference(mesh1, seed):
    """`_Job` fed once and scanned from its cache, against the reference
    over the same rows from the same start. Off the chip the program
    computes in float64, so the two differ by the reference's float32 only."""
    from spark_rapids_ml_tpu import config as program_config
    from spark_rapids_ml_tpu.serve.daemon import _Job

    d, k, rows, n_batches, passes = 48, 6, 700, 5, 6
    planted = kmeans_data.spec(seed, d, k)
    batches = [np.asarray(kmeans_data.device_rows(planted, seed, i, rows))
               for i in range(n_batches)]
    start = kmeans_data.start_centres(seed, batches[0], k)
    with program_config.option("daemon_pass_cache_mb", 8):
        job = _Job("kmeans", d, mesh1, {"k": k})
    job.set_iterate({"centers": start}, 0)
    for x in batches:
        job.fold(x, None, pass_id=0)
    first = [np.asarray(a) for a in job.peek_pass_state()[0]]
    for it in range(passes):
        if it:
            job.rescan(it)
        job.step({})
    job.rescan(passes)
    model = {"centers": job.get_iterate()[0]["centers"],
             "cost": float(job.peek_pass_state()[0][2]),
             "pass0": {"sums": first[0], "counts": first[1], "cost": float(first[2])},
             "pass_counts": [float(np.asarray(job.peek_pass_state()[0][1]).sum())]}
    ref = ref_kmeans.fit(batches, start, passes, 0.0)
    assert ref["n_iter"] == passes and ref["rows"] == rows * n_batches
    tight = {"pass0_stats_rel": 1e-6, "centers_rel": 1e-5, "cost_rel": 1e-6}
    assert agree_kmeans.check_fit(model, ref, tight, rows * n_batches) == []
    np.testing.assert_array_equal(first[1], ref["pass0"]["counts"])


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_the_tiny_cell_runs_end_to_end_and_traced(root, trace):
    result, lines = rehearse.run(root, rehearse.CELL, seconds=1.0, trace=trace)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] >= 13 and list(result)[-1] == "compared"
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) == rehearse.reports(root, rehearse.CELL, kind)
    assert f"= {rehearse.CACHED_ROWS} rows" in text and "compiles in window: 0" in text
    assert set(result["compared"]) == {
        "rows_not_folded", "pass0_stats_rel", "centers_rel", "cost_rel",
        "rows_refed_in_window", "compiles_in_window"}
    assert result["compared"]["rows_not_folded"] == [0.0, 0.0]
    assert result["compared"]["rows_refed_in_window"] == [0.0, 0.0]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if trace:
        assert NEW_PER_LAYER | {"compiles_in_window", "median_pass_rows_per_s",
                                "late_pass_share"} <= set(got)
        assert got["pass_cached_share"] == 100.0 and got["compiles_in_window"] == 0
        assert got["rescan_dispatch_ms"] > 0 and got["lloyd_boundary_ms"] > 0
        assert 0 < got["lloyd_fold_dispatch_ms"] < got["rescan_dispatch_ms"]
        assert got["median_pass_rows_per_s"] > 0 and 0 <= got["late_pass_share"] < 100
        for name in ("pass_fold_device_ms", "pass_fold_roofline"):
            assert f"metric {name}: nothing to read, left out" in text
    else:
        assert {"pass_rows_per_s", "setup_s"} == set(got) and got["pass_rows_per_s"] > 0
    # the run says its passes, and the share the reader reads, on a line of its own
    assert "a pass: p10 " in text and "x the median, " in text


def test_a_program_without_the_cache_fails_at_once_and_makes_no_data(root, monkeypatch):
    """The parent commit: the generator asks `_Job` for `rescan` first."""
    from spark_rapids_ml_tpu.serve import daemon

    monkeypatch.delattr(daemon._Job, "rescan")
    made = []
    monkeypatch.setattr(kmeans_data, "device_rows", lambda *a, **k: made.append(a))
    with pytest.raises(RuntimeError, match="keeps no pass cache"):
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
        rows = [np.asarray(xs) for xs, _ in job._cache.batches]
        for x in rows:
            job.fold(x, None, pass_id=pass_id)
        return {"pass_rows": job.pass_rows}

    monkeypatch.setattr(daemon._Job, "rescan", refeeding)


def _the_centres_altered_where_they_are_produced(monkeypatch):
    from spark_rapids_ml_tpu.models import kmeans

    real = kmeans.apply_lloyd_update

    def altered(sums, counts, centers):
        new, moved2 = real(sums, counts, centers)
        return new + 0.75 * (new - new.mean(axis=0)), moved2

    monkeypatch.setattr(kmeans, "apply_lloyd_update", altered)


@pytest.mark.parametrize("fault,caught_by,reads", [
    (_a_rescan_that_skips_a_batch, "rows_not_folded", 512),
    (_a_pass_that_is_fed_again, "rows_refed_in_window", None),
    (_the_centres_altered_where_they_are_produced, "centers_rel", None),
], ids=["a_rescan_that_skips_a_batch", "a_pass_that_is_fed_again",
        "the_centres_altered_where_they_are_produced"])
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


def test_the_control_rounds_rows_and_distance_centres_to_what_float8_e4m3_holds():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.0625, 1.1875, -2.75, 0.4375, 24.0], jnp.float32)
    assert control_kmeans.lower(x).tolist() == [1.0, 1.0, 1.25, -2.75, 0.4375, 24.0]
    seen = []

    def spy(a):
        seen.append(a.shape)
        return a

    batches = [np.ones((64, 8), np.float32), np.zeros((64, 8), np.float32)]
    ref_kmeans.scan(batches, np.eye(4, 8, dtype=np.float32), rounded=spy)
    assert seen == [(4, 8), (64, 8), (64, 8)]  # the centres, then every batch


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_in_float8_is_not_correct(config, seed):
    """At d = 256, k = 100 and 32,768 rows the control reads over the limit
    that decides on the chip; the reference against itself reads 0."""
    d, k, rows, n_batches, passes = 256, 100, 4096, 8, 3
    planted = kmeans_data.spec(seed, d, k)
    batches = [kmeans_data.device_rows(planted, seed, i, rows) for i in range(n_batches)]
    start = kmeans_data.start_centres(seed, np.asarray(batches[0]), k)
    ref = ref_kmeans.fit(batches, start, passes, 0.0)
    whole = [float(rows * n_batches)]
    again = {**ref_kmeans.fit(batches, start, passes, 0.0), "pass_counts": whole}
    assert agree_kmeans.check_fit(again, ref, config["tolerances"], rows * n_batches) == []
    model = {**control_kmeans.fit(ref_kmeans, batches, start, passes, 0.0),
             "pass_counts": whole}
    problems = agree_kmeans.check_fit(model, ref, config["tolerances"], rows * n_batches)
    assert any("pass0_stats_rel" in p for p in problems), problems
    compared = agree_kmeans.compared([{"model": model}], config["tolerances"],
                                     rows * n_batches)
    value, limit = compared["pass0_stats_rel"]
    assert value > 2 * limit and compared["rows_not_folded"] == [0.0, 0.0]
