"""The pass cache for the Newton job (docs/protocol.md "rescan"): a
logistic job — binary Newton, or multinomial MM-Newton with its group
program over the per-class state — keeps the batches its fold placed on
the device — rows, mask AND the label column — and the passes after the
first are folded from there.

The invariant is `tests/test_pass_cache.py`'s: **the cache changes the
transport of a pass, never its result.** Against a re-fed pass that folds
the same batches in the same order into one accumulator (direct feeds) a
cached pass is bit-equal; against a partitioned re-fed pass — whose stages
accumulate apart and are added at commit — the row count is bit-equal and
the sums differ by the order of the accumulator's additions only, within
2·(B−1)·u·Σ_b|s_b| (B batches, u the accumulator's unit roundoff, s_b a
batch's own statistic).
"""

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models.jobs import JOB_ALGORITHMS, job_algorithm
from spark_rapids_ml_tpu.serve import DataPlaneDaemon, protocol
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils import xprof

D = 24
STEP = {"reg": 1e-3, "fit_intercept": True}
# 5 batches, the last ragged: 1000 rows pad to 1024, 217 to 256
BATCHES = [(0, 1000), (1000, 2000), (2000, 3000), (3000, 4000), (4000, 4217)]


def _job(mesh, cache_mb, d=D, params=None):
    with config.option("daemon_pass_cache_mb", cache_mb):
        return _Job("logreg", d, mesh, params or {})


def _rows(seed, n, d=D):
    """Overlapping classes: logits of standard deviation ~1.5."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d) * 1.5 / np.sqrt(d)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-(x @ w + 0.2)))).astype(np.float64)
    return x.astype(np.float32), y


def _rows_mn(seed, n, d=D, classes=3):
    """Overlapping classes: each class's logits of standard deviation ~1.5,
    labels drawn from their softmax."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=(d, classes)) * 1.5 / np.sqrt(d)
    y = np.argmax(x @ w + rng.gumbel(size=(n, classes)), axis=1).astype(np.float64)
    return x.astype(np.float32), y


def _start_mn(seed, d=D, classes=3):
    rng = np.random.default_rng([seed, 1])
    return {"w": rng.normal(size=(d, classes)) * 0.1, "b": rng.normal(size=classes) * 0.05}


def _start(seed, d=D):
    rng = np.random.default_rng([seed, 1])
    return {"w": rng.normal(size=d) * 0.1, "b": np.asarray([0.05])}


def _stats(job):
    return [np.asarray(a) for a in jax.device_get(job.peek_pass_state()[0])]


def _counter(name, **labels):
    return sum(
        s["value"] for s in (metrics_mod.snapshot().get(name) or {}).get("samples", [])
        if all(s["labels"].get(k) == v for k, v in labels.items()))


def _phase_count(phase):
    return sum(
        s["count"] for s in (metrics_mod.snapshot().get(
            "srml_phase_duration_seconds") or {}).get("samples", [])
        if s["labels"].get("phase") == phase)


def test_the_table_says_which_algorithms_may_keep_their_pass(mesh8):
    # the forest joined them in PR 36 (tests/test_pass_cache_forest.py)
    assert {a for a, cls in JOB_ALGORITHMS.items() if cls.cacheable} == {
        "kmeans", "logreg", "rf"}
    binary = job_algorithm("logreg")(D, mesh8, {})
    assert binary.cacheable_for({}) and binary.boundary_span == "newton.boundary"
    # the multinomial job folds its cached pass with a group program of its
    # own: the class says so for any class count — the one answer the driver
    # and the daemon's job both ask — and the job that holds one has a budget
    logreg = job_algorithm("logreg")
    assert logreg.cacheable_for({"n_classes": 2}) and logreg.cacheable_for({"n_classes": 3})
    assert job_algorithm("kmeans").cacheable_for({"k": 3})
    assert not job_algorithm("pca").cacheable_for({})
    multi = _job(mesh8, 16, params={"n_classes": 3})
    assert multi._cache_budget == 16 << 20
    assert multi.algorithm.boundary_span == "softmax.boundary"
    x, _ = _rows(1, 300)
    multi.fold(x, np.arange(300) % 3, pass_id=0)
    assert multi.cache_ack() == {"cached": True, "cached_rows": 300}
    multi.step(STEP)
    assert multi.rescan(1) == {"pass_rows": 300, "cached_rows": 300, "cached_batches": 1}


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_a_cached_newton_pass_is_bit_equal_to_a_refed_pass_of_direct_feeds(mesh8, seed):
    x, y = _rows(seed, 4217)
    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    for job in (fed, cached):
        job.set_iterate(_start(seed), 0)
    for it in range(4):
        for lo, hi in BATCHES:
            fed.fold(x[lo:hi], y[lo:hi], pass_id=it)
        if it == 0:
            for lo, hi in BATCHES:
                cached.fold(x[lo:hi], y[lo:hi], pass_id=it)
            assert cached.cache_ack() == {"cached": True, "cached_rows": 4217}
            # the cached batch is what the fold placed: rows, mask, labels
            assert [len(b) for b in cached._cache.batches] == [3] * 5
            xs, ms, ys = cached._cache.batches[4]
            assert (xs.shape, ms.shape, ys.shape) == ((256, D), (256,), (256,))
            np.testing.assert_array_equal(np.asarray(ys)[:217], y[4000:4217])
            assert not np.asarray(ys)[217:].any() and np.asarray(ms).sum() == 217
        else:
            ack = cached.rescan(it)
            assert ack == {"pass_rows": 4217, "cached_rows": 4217, "cached_batches": 5}
        for a, b in zip(_stats(fed), _stats(cached)):
            np.testing.assert_array_equal(a, b)  # every leaf of the state: bit-equal
        assert _stats(cached)[-1] == 4217
        assert fed.step(STEP) == cached.step(STEP)
    assert fed.cache_ack() == {} and fed.pass_cache_bytes == 0
    for key in ("w", "b"):
        np.testing.assert_array_equal(
            fed.get_iterate()[0][key], cached.get_iterate()[0][key])


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_a_cached_multinomial_pass_is_bit_equal_to_a_refed_pass_of_direct_feeds(mesh8, seed):
    """The multinomial job's cached pass, folded by its group program, is the
    re-fed pass of direct feeds bit for bit, in every leaf of the per-class
    state, pass after pass of MM-Newton."""
    x, y = _rows_mn(seed, 4217)
    params = {"n_classes": 3}
    fed, cached = _job(mesh8, 0, params=params), _job(mesh8, 16, params=params)
    for job in (fed, cached):
        job.set_iterate(_start_mn(seed), 0)
    for it in range(4):
        for lo, hi in BATCHES:
            fed.fold(x[lo:hi], y[lo:hi], pass_id=it)
        if it == 0:
            for lo, hi in BATCHES:
                cached.fold(x[lo:hi], y[lo:hi], pass_id=it)
            assert cached.cache_ack() == {"cached": True, "cached_rows": 4217}
            xs, ms, ys = cached._cache.batches[4]
            assert (xs.shape, ms.shape, ys.shape) == ((256, D), (256,), (256,))
            np.testing.assert_array_equal(np.asarray(ys)[:217], y[4000:4217])
        else:
            ack = cached.rescan(it)
            assert ack == {"pass_rows": 4217, "cached_rows": 4217, "cached_batches": 5}
        got_fed, got_cached = _stats(fed), _stats(cached)
        assert [a.shape for a in got_cached] == [
            (D, 3), (3,), (3, D, D), (3, D), (3,), (), ()]
        for a, b in zip(got_fed, got_cached):
            np.testing.assert_array_equal(a, b)  # every leaf of the state: bit-equal
        assert got_cached[-1] == 4217
        assert fed.step(STEP) == cached.step(STEP)
    for key in ("w", "b"):
        np.testing.assert_array_equal(
            fed.get_iterate()[0][key], cached.get_iterate()[0][key])


@pytest.mark.parametrize("mesh", ["mesh1", "mesh8"])
def test_the_multinomial_group_program_is_its_batches_fed_singly(request, mesh):
    """`_stream_softmax_stats_group_fn` over a run of batches equals
    `_stream_softmax_stats_fn` called on each in order, bit for bit: one
    shard body, a barrier after each batch."""
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.parallel.sharding import shard_rows

    mesh = request.getfixturevalue(mesh)
    ad = config.get("accum_dtype")
    x, y = _rows_mn(41, 3 * 512)
    start = _start_mn(41)
    w = jax.numpy.asarray(start["w"], ad)
    b = jax.numpy.asarray(start["b"], ad)
    placed = []
    for i in range(3):
        xs, ms, _ = shard_rows(x[i * 512:(i + 1) * 512], mesh, dtype=np.float32)
        ys, _, _ = shard_rows(y[i * 512:(i + 1) * 512].astype(np.float32), mesh)
        placed.append((xs, ys, ms))
    single = lg._stream_softmax_stats_fn(mesh, 3, ad)
    state = lg.stream_softmax_zero_state(D, 3, ad)
    for xs, ys, ms in placed:
        state = single(state, w, b, xs, ys, ms)
    group = lg._stream_softmax_stats_group_fn(mesh, 3, ad)
    xs, ys, ms = zip(*placed)
    grouped = group(lg.stream_softmax_zero_state(D, 3, ad), w, b, xs, ys, ms)
    for a, g in zip(jax.device_get(state), jax.device_get(grouped)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g))
    assert float(np.asarray(grouped[-1])) == 3 * 512


@pytest.mark.parametrize("seed", [5, 3000000019])
def test_a_cached_newton_pass_against_a_partitioned_refed_pass_at_the_same_iterate(
        mesh8, seed):
    x, y = _rows(seed, 4217)
    parts = [BATCHES[0:2], BATCHES[2:4], BATCHES[4:5]]

    def feed(job, it):
        for pid, part in enumerate(parts):
            for lo, hi in part:
                job.fold(x[lo:hi], y[lo:hi], partition=pid, pass_id=it)
            job.commit(pid, pass_id=it)

    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    for job in (fed, cached):
        job.set_iterate(_start(seed), 0)
        feed(job, 0)
    cached.step(STEP)
    u = float(np.finfo(np.dtype(config.get("accum_dtype"))).eps) / 2
    for it in (1, 2, 3):
        fed.set_iterate(cached.get_iterate()[0], it)  # the same iterate on both
        feed(fed, it)
        assert cached.rescan(it)["pass_rows"] == 4217
        # a batch's own statistics, by the fold's own program, for the bound
        per_batch = []
        for xs, ms, ys in cached._cache.batches:
            per_batch.append([np.abs(np.asarray(a)) for a in jax.device_get(
                cached.algorithm.fold(cached.algorithm.zero_state(), xs, ms, (ys,)))])
        got_fed, got_cached = _stats(fed), _stats(cached)
        assert got_fed[-1] == got_cached[-1] == 4217  # the row count: bit-equal
        for i in range(len(got_fed) - 1):
            bound = 2 * (len(per_batch) - 1) * u * sum(b[i] for b in per_batch)
            assert np.all(np.abs(got_fed[i] - got_cached[i]) <= bound), i
        cached.step(STEP)


def test_a_rescan_folds_the_labels_by_the_group_in_one_ledgered_program(mesh8):
    x, y = _rows(19, 19 * 64)
    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    for job in (fed, cached):
        job.set_iterate(_start(19), 0)
        for i in range(19):
            job.fold(x[i * 64:(i + 1) * 64], y[i * 64:(i + 1) * 64], pass_id=0)
        job.step(STEP)
    for i in range(19):
        fed.fold(x[i * 64:(i + 1) * 64], y[i * 64:(i + 1) * 64], pass_id=1)
    name = "logreg.streaming_update_group"
    before = xprof.snapshot().get(name, {"calls": 0})["calls"]
    cached.rescan(1)
    assert xprof.snapshot()[name]["calls"] - before == 3  # 8 + 8 + 3 batches
    for a, b in zip(_stats(fed), _stats(cached)):
        np.testing.assert_array_equal(a, b)


def test_the_budget_counts_the_label_column_and_is_all_or_nothing(mesh8):
    d, rows = 64, 8192
    # a batch a device: rows, mask and labels; a 1 MiB budget holds three
    batch = (rows * d * 4 + rows * 4 + rows * 4) // 8
    assert 3 * batch <= 1 << 20 < 4 * batch
    # ... and would hold exactly as many without the labels: count them apart
    x, y = _rows(11, 6 * rows, d=d)
    plain, over = _job(mesh8, 0, d=d), _job(mesh8, 1, d=d)
    acks, held = [], []
    for it in range(3):
        for i in range(6):
            for job in (plain, over):
                job.fold(x[i * rows:(i + 1) * rows], y[i * rows:(i + 1) * rows], pass_id=it)
            acks.append(over.cache_ack()["cached"])
            held.append(over.pass_cache_bytes)
        if it:
            with pytest.raises(protocol.NoCachedPass, match="over its budget"):
                over.rescan(it)
        for a, b in zip(_stats(plain), _stats(over)):
            np.testing.assert_array_equal(a, b)
        assert plain.step(STEP) == over.step(STEP)
    assert acks == [True] * 3 + [False] * 15
    assert held[:3] == [batch, 2 * batch, 3 * batch] and held[3:] == [0] * 15
    assert over._cache is None
    # a stage's batches are counted the same way, labels included
    staged = _job(mesh8, 16, d=d)
    staged.fold(x[:rows], y[:rows], partition=0, pass_id=0)
    assert staged.pass_cache_bytes == batch and staged.cache_ack()["cached_rows"] == 0
    staged.commit(0, pass_id=0)
    assert staged.pass_cache_bytes == staged._cache.nbytes == batch


def test_a_restored_job_has_no_cached_pass_and_a_stale_pass_id_is_fenced(mesh8):
    x, y = _rows(13, 2000)
    job = _job(mesh8, 16)
    for pid, (lo, hi) in enumerate([(0, 1000), (1000, 2000)]):
        job.fold(x[lo:hi], y[lo:hi], partition=pid, pass_id=0)
        job.commit(pid, pass_id=0)
    with pytest.raises(protocol.NoCachedPass, match="still open"):
        job.rescan(0)
    job.step(STEP)
    # a zombie of the finished pass, and a rescan for a pass not yet open
    for stale in (0, 2):
        with pytest.raises(ValueError, match=f"stale pass_id {stale}"):
            job.rescan(stale)
    with pytest.raises(ValueError, match="stale pass_id 0"):
        job.fold(x[:10], y[:10], partition=5, pass_id=0)
    assert job.rescan(1)["cached_rows"] == 2000
    job.step(STEP)
    # what a durable snapshot restores holds no cached pass: the pass is re-fed
    restored = _job(mesh8, 16)
    restored.set_iterate(job.durable_arrays(), 2)
    with pytest.raises(protocol.NoCachedPass, match="keeps none"):
        restored.rescan(2)
    for pid, (lo, hi) in enumerate([(0, 1000), (1000, 2000)]):
        restored.fold(x[lo:hi], y[lo:hi], partition=pid, pass_id=2)
        restored.commit(pid, pass_id=2)
    assert job.rescan(2)["pass_rows"] == 2000
    want, got = _stats(job), _stats(restored)
    assert want[-1] == got[-1] == 2000
    for a, b in zip(want, got):
        np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-9)
    assert restored.step(STEP)["pass_rows"] == 2000
    assert restored.rescan(3)["cached_rows"] == 2000  # the re-fed pass refilled it


def test_the_kmeans_jobs_cached_batch_is_what_it_was(mesh8):
    """The wider cached tuple holds nothing more for an algorithm that
    places no column: `(xs, ms)`, its bytes, and `fold_group` without one."""
    with config.option("daemon_pass_cache_mb", 16):
        job = _Job("kmeans", D, mesh8, {"k": 4})
    x, _ = _rows(7, 1500)
    job.set_iterate({"centers": x[:4].copy()}, 0)
    job.fold(x[:1000], None, pass_id=0)
    job.fold(x[1000:], None, partition=0, pass_id=0)
    job.commit(0, pass_id=0)
    assert job.algorithm.place_columns(1024, None, n=1000) == ()
    assert [len(b) for b in job._cache.batches] == [2, 2]
    assert job.pass_cache_bytes == ((1024 + 512) * D * 4 + (1024 + 512) * 4) // 8
    want = _stats(job)
    job.set_iterate({"centers": x[:4].copy()}, 1)
    assert job.rescan(1) == {"pass_rows": 1500, "cached_rows": 1500, "cached_batches": 2}
    for a, b in zip(want, _stats(job)):
        np.testing.assert_array_equal(a, b)


def test_the_boundary_and_its_solve_are_spans_of_the_phase_histogram(mesh8):
    x, y = _rows(23, 600)
    job = _job(mesh8, 0)
    before = {p: _phase_count(p) for p in ("newton.boundary", "newton.solve")}
    job.fold(x, y, pass_id=0)
    info = job.step(STEP)
    assert isinstance(info["delta"], float) and isinstance(info["loss"], float)
    assert list(info) == ["iteration", "delta", "loss", "pass_rows"]
    for phase, count in before.items():
        assert _phase_count(phase) == count + 1, phase
    assert "logreg.newton_step" in xprof.snapshot()


PHASES = ("newton.boundary", "newton.solve", "softmax.boundary", "softmax.solve")
PATHS = ("srml_logreg_fold_path_total", "srml_logreg_softmax_fold_path_total")


@pytest.mark.parametrize("classes,spans,counter,programs", [
    (2, ("newton.boundary", "newton.solve"), "srml_logreg_fold_path_total",
     ("logreg.streaming_update", "logreg.streaming_update_group", "logreg.newton_step")),
    (3, ("softmax.boundary", "softmax.solve"), "srml_logreg_softmax_fold_path_total",
     ("logreg.softmax_streaming_update", "logreg.softmax_streaming_update_group",
      "logreg.softmax_newton_step")),
], ids=["binary", "multinomial"])
def test_each_job_counts_its_own_spans_programs_and_fold_paths(
        mesh8, classes, spans, counter, programs):
    """A boundary is one sample of each of its job's two spans and of no
    other; a fold program's dispatch is one count of its job's path counter
    (`xla` off the chip) and of no other; the binary job's span, ledger and
    counter names are what they were before the multinomial job had its own."""
    x, _ = _rows(37, 600)
    y = (np.arange(600) % classes).astype(np.float64)
    job = _job(mesh8, 16, params={"n_classes": classes})
    assert (job.algorithm.boundary_span, job.algorithm.solve_span) == spans
    phases = {p: _phase_count(p) for p in PHASES}
    paths = {c: _counter(c) for c in PATHS}
    xla = _counter(counter, path="xla")
    calls = {name: xprof.snapshot().get(name, {"calls": 0})["calls"] for name in programs}
    job.fold(x[:300], y[:300], pass_id=0)
    job.fold(x[300:], y[300:], pass_id=0)
    job.step(STEP)
    job.rescan(1)  # both cached batches: one group dispatch
    job.step(STEP)
    assert {p: _phase_count(p) - n for p, n in phases.items()} == {
        p: 2 if p in spans else 0 for p in PHASES}
    assert {c: _counter(c) - n for c, n in paths.items()} == {
        c: 3 if c == counter else 0 for c in PATHS}
    assert _counter(counter, path="xla") - xla == 3  # off the chip: the XLA body
    assert [xprof.snapshot()[name]["calls"] - calls[name] for name in programs] == [2, 1, 2]


# ---------------- through a real daemon, client and spark/estimator.py -------


def _fit(x, y, max_iter=4):
    from sparksim import simdf_from_numpy
    from spark_rapids_ml_tpu.spark.estimator import SparkLogisticRegression

    # concurrency=1: commits in partition order, so that the partitioned
    # re-fed fit and the cached one fold in one order
    df = simdf_from_numpy(x, n_partitions=3, label=y, concurrency=1)
    return (SparkLogisticRegression().setRegParam(1e-3).setMaxIter(max_iter)
            .setTol(0.0).fit(df))


def test_spark_logistic_rows_and_labels_cross_the_wire_once_and_the_model_is_the_key_off_model(
        mesh8, monkeypatch):
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    x, y = _rows(29, 900, d=6)
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
        metrics_mod.reset()
        off = _fit(x, y)
        assert _counter("srml_daemon_pass_rows_total") == 0
        assert _counter("srml_daemon_requests_total", op="rescan") == 0

        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            on = _fit(x, y)
    # rows crossed the wire in pass 0 only; three Newton passes came from the cache
    assert _counter("srml_daemon_pass_rows_total", source="wire") == len(x)
    assert _counter("srml_daemon_pass_rows_total", source="cache") == 3 * len(x)
    assert _counter("srml_daemon_passes_total", source="wire") == 1
    assert _counter("srml_daemon_passes_total", source="cache") == 3
    assert on.summary.numIter == off.summary.numIter == 4
    assert on.summary.n_rows == off.summary.n_rows == len(x)
    # the sums differ by the order of the accumulator's additions only
    np.testing.assert_allclose(on.coefficients, off.coefficients, rtol=0, atol=1e-10)
    assert float(on.intercept) == pytest.approx(float(off.intercept), abs=1e-10)
    assert on.summary.loss == pytest.approx(off.summary.loss, rel=1e-12)


def test_spark_multinomial_logistic_asks_no_cache_and_feeds_every_pass(
        mesh8, monkeypatch):
    """The multinomial fit keeps its pass since its job has a group program:
    `cacheable_for` three classes is true, the driver takes the cached-pass
    branch, its rows and labels cross the wire once, the two later MM-Newton
    passes come from the daemon's cache, and the model is the key-off model."""
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    rng = np.random.default_rng(31)
    x = rng.normal(size=(600, 5)).astype(np.float32)
    y = np.argmax(x[:, :3] + 0.5 * rng.normal(size=(600, 3)), axis=1).astype(np.float64)
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
        metrics_mod.reset()
        off = _fit(x, y, max_iter=3)
        assert _counter("srml_daemon_requests_total", op="rescan") == 0
        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            on = _fit(x, y, max_iter=3)
    assert _counter("srml_daemon_pass_rows_total", source="wire") == len(x)
    assert _counter("srml_daemon_pass_rows_total", source="cache") == 2 * len(x)
    assert _counter("srml_daemon_passes_total", source="wire") == 1
    assert _counter("srml_daemon_passes_total", source="cache") == 2
    assert _counter("srml_daemon_requests_total", op="rescan") >= 2
    assert on.summary.numIter == off.summary.numIter == 3
    assert on.coefficients.shape == off.coefficients.shape == (3, 5)
    # the sums differ by the order of the accumulator's additions only
    np.testing.assert_allclose(on.coefficients, off.coefficients, rtol=0, atol=1e-10)
    np.testing.assert_allclose(np.asarray(on.intercept), np.asarray(off.intercept),
                               rtol=0, atol=1e-10)
