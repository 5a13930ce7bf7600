"""The pass cache (ISSUE 28; docs/protocol.md "rescan"): a kmeans job keeps
the batches its fold placed on the device, and the passes after the first
are scanned from there.

The invariant under test: **the cache changes the transport of a pass,
never its result.** Against a re-fed pass that folds the same batches in
the same order into one accumulator (direct feeds) a cached pass is
bit-equal; against a partitioned re-fed pass — whose stages accumulate
apart and are added at commit — `counts` are bit-equal and `sums`, `cost`
differ by the order of the accumulator's additions only, within
2·(B−1)·u·Σ_b|s_b| (B batches, u the accumulator's unit roundoff, s_b a
batch's own statistic).
"""

import socket
import time

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon, protocol
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import faults
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils.faults import FaultPlan

D, K = 32, 6


def _job(mesh, cache_mb, k=K, d=D):
    with config.option("daemon_pass_cache_mb", cache_mb):
        return _Job("kmeans", d, mesh, {"k": k})


def _rows(seed, n, d=D, k=K):
    """`k` overlapping blobs: neighbouring rows flip between centres."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(k, d)) * 1.5
    x = centres[rng.integers(0, k, size=n)] + rng.normal(size=(n, d))
    return x.astype(np.float32)


def _stats(job):
    return [np.asarray(a) for a in jax.device_get(job.peek_pass_state()[0])]


def _counter(name, **labels):
    return sum(
        s["value"] for s in (metrics_mod.snapshot().get(name) or {}).get("samples", [])
        if all(s["labels"].get(k) == v for k, v in labels.items()))


# 5 batches, the last ragged: 1000 rows pad to 1024, 217 to 256
BATCHES = [(0, 1000), (1000, 2000), (2000, 3000), (3000, 4000), (4000, 4217)]


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_a_cached_pass_is_bit_equal_to_a_refed_pass_of_direct_feeds(mesh8, seed):
    x = _rows(seed, 4217)
    start = x[:K].copy()
    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    for job in (fed, cached):
        job.set_iterate({"centers": start}, 0)
    for it in range(4):
        for lo, hi in BATCHES:
            fed.fold(x[lo:hi], None, pass_id=it)
        if it == 0:
            for lo, hi in BATCHES:
                cached.fold(x[lo:hi], None, pass_id=it)
            assert cached.cache_ack() == {"cached": True, "cached_rows": 4217}
        else:
            ack = cached.rescan(it)
            assert ack == {"pass_rows": 4217, "cached_rows": 4217, "cached_batches": 5}
        for a, b in zip(_stats(fed), _stats(cached)):
            np.testing.assert_array_equal(a, b)  # sums, counts, cost: bit-equal
        assert fed.step({}) == cached.step({})
    # a job without a budget answers none of the additive fields
    assert fed.cache_ack() == {} and fed.pass_cache_bytes == 0
    np.testing.assert_array_equal(
        fed.get_iterate()[0]["centers"], cached.get_iterate()[0]["centers"])


@pytest.mark.parametrize("seed", [5, 3000000019])
def test_a_cached_pass_against_a_partitioned_refed_pass_at_the_same_iterate(mesh8, seed):
    """Stages accumulate apart and are added at commit; the cached pass
    folds the same batches into one accumulator: `counts` bit-equal, `sums`
    and `cost` within the stated bound of the accumulator's additions."""
    x = _rows(seed, 4217)
    parts = [BATCHES[0:2], BATCHES[2:4], BATCHES[4:5]]

    def feed(job, it):
        for pid, part in enumerate(parts):
            for lo, hi in part:
                job.fold(x[lo:hi], None, partition=pid, pass_id=it)
            job.commit(pid, pass_id=it)

    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    centres = x[100:100 + K].copy()
    for job in (fed, cached):
        job.set_iterate({"centers": centres}, 0)
        feed(job, 0)
    cached.step({})
    u = float(np.finfo(np.dtype(config.get("accum_dtype"))).eps) / 2
    for it in (1, 2, 3):
        centres = np.asarray(cached.get_iterate()[0]["centers"])
        fed.set_iterate({"centers": centres}, it)  # the same iterate on both
        feed(fed, it)
        assert cached.rescan(it)["pass_rows"] == 4217
        # a batch's own statistics, by the fold's own program, for the bound
        per_batch = []
        with config.option("daemon_pass_cache_mb", 0):
            for xs, ms in cached._cache.batches:
                per_batch.append([np.abs(np.asarray(a)) for a in jax.device_get(
                    cached.algorithm.fold(cached.algorithm.zero_state(), xs, ms))])
        bound = [2 * (len(per_batch) - 1) * u * sum(b[i] for b in per_batch)
                 for i in range(3)]
        (s0, c0, k0), (s1, c1, k1) = _stats(fed), _stats(cached)
        np.testing.assert_array_equal(c0, c1)
        assert c1.sum() == 4217
        assert np.all(np.abs(s0 - s1) <= bound[0])
        assert abs(k0 - k1) <= bound[2]
        cached.step({})


def test_a_rescan_dispatches_runs_of_one_shape_up_to_eight_batches_a_program(mesh8):
    """One program a batch leaves the device waiting for the host's dispatch
    (PERF.md §5): `rescan` folds a group a dispatch, in order, and the
    ledger counts the dispatches."""
    from spark_rapids_ml_tpu.serve.daemon import _RESCAN_GROUP, _rescan_groups
    from spark_rapids_ml_tpu.utils import xprof

    assert _RESCAN_GROUP == 8
    shapes = [(64, D)] * 11 + [(32, D)] * 2 + [(64, D)]
    batches = [(np.zeros(shape, np.float32), i) for i, shape in enumerate(shapes)]
    groups = list(_rescan_groups(batches))
    assert [len(g) for g in groups] == [8, 3, 2, 1]
    assert [i for g in groups for _, i in g] == list(range(14))  # in order, none lost
    assert all(len({x.shape for x, _ in g}) == 1 for g in groups)

    x = _rows(19, 19 * 64)
    fed, cached = _job(mesh8, 0), _job(mesh8, 16)
    for job in (fed, cached):
        job.set_iterate({"centers": x[:K].copy()}, 0)
        for i in range(19):
            job.fold(x[i * 64:(i + 1) * 64], None, pass_id=0)
        job.step({})
    for i in range(19):
        fed.fold(x[i * 64:(i + 1) * 64], None, pass_id=1)
    calls = lambda: xprof.snapshot()["kmeans.streaming_update_group"]["calls"]  # noqa: E731
    before = calls() if "kmeans.streaming_update_group" in xprof.snapshot() else 0
    cached.rescan(1)
    assert calls() - before == 3  # 8 + 8 + 3 batches
    for a, b in zip(_stats(fed), _stats(cached)):
        np.testing.assert_array_equal(a, b)


def test_only_the_committed_attempts_batches_are_cached_and_the_losers_are_freed(mesh8):
    x = _rows(7, 3000)
    job = _job(mesh8, 16)
    job.set_iterate({"centers": x[:K].copy()}, 0)
    # partition 0, two attempts side by side (speculation): attempt 1 wins
    job.fold(x[0:500], None, partition=0, attempt=0, pass_id=0)
    job.fold(x[0:500], None, partition=0, attempt=1, pass_id=0)
    job.fold(x[500:1000], None, partition=0, attempt=1, pass_id=0)
    batch = (512 * D * 4 + 512 * 4) // 8  # one padded batch and its mask, per device
    assert job.pass_cache_bytes == 3 * batch and job.cache_ack()["cached_rows"] == 0
    staged_before = job.staged_bytes
    job.commit(0, attempt=1, pass_id=0)
    assert not job.staged  # the loser's stage went, with its batch
    assert job.pass_cache_bytes == 2 * batch == job._cache.nbytes
    assert job.staged_bytes == 0 < staged_before  # counted apart from the cache
    assert [b[0].shape for b in job._cache.batches] == [(512, D), (512, D)]
    # the loser's late traffic is a committed partition's duplicate: not kept
    job.fold(x[500:1000], None, partition=0, attempt=0, pass_id=0)
    job.fold(x[1000:3000], None, partition=1, pass_id=0)
    job.commit(1, pass_id=0)
    assert job.cache_ack() == {"cached": True, "cached_rows": 3000}
    assert job._cache.committed == {0: 1000, 1: 2000}
    # the pass that fills the cache is still open: no rescan into it
    with pytest.raises(protocol.NoCachedPass, match="still open"):
        job.rescan(0)
    want = _stats(job)
    job.set_iterate({"centers": x[:K].copy()}, 1)  # the same centres, pass 1
    ack = job.rescan(1)
    assert ack == {"pass_rows": 3000, "cached_rows": 3000, "cached_batches": 3}
    got = _stats(job)
    np.testing.assert_array_equal(want[1], got[1])
    np.testing.assert_allclose(want[0], got[0], rtol=1e-6)
    # a second rescan into a pass that holds rows is refused; a replay of the
    # first (same id, its ack lost) gets that ack again
    with pytest.raises(ValueError, match="already holds"):
        job.rescan(1)
    job.step({})
    first = job.rescan(2, rescan_id="r-1")
    assert job.rescan(2, rescan_id="r-1") == first and job.pass_rows == 3000
    # finalize ends the fit: the cached pass is freed
    job.finalize({})
    assert job._cache is None and job.pass_cache_bytes == 0


def test_over_budget_the_cache_is_dropped_and_the_refed_fit_equals_the_parents(mesh8):
    d, rows = 64, 8192  # 2 MiB a batch, 256 KiB a device: a 1 MiB budget holds three
    x = _rows(11, 6 * rows, d=d)
    start = x[:K].copy()
    plain, over = _job(mesh8, 0, d=d), _job(mesh8, 1, d=d)
    for job in (plain, over):
        job.set_iterate({"centers": start}, 0)
    acks = []
    for it in range(3):
        for i in range(6):
            for job in (plain, over):
                job.fold(x[i * rows:(i + 1) * rows], None, pass_id=it)
            acks.append(over.cache_ack()["cached"])
        if it:
            with pytest.raises(protocol.NoCachedPass, match="over its budget"):
                over.rescan(it)
        for a, b in zip(_stats(plain), _stats(over)):
            np.testing.assert_array_equal(a, b)
        assert plain.step({}) == over.step({})
    # all or nothing: kept while it fitted, dropped whole by the fourth batch,
    # and not tried again in the later passes of this fit
    assert acks == [True] * 3 + [False] * 15
    assert over.pass_cache_bytes == 0 and over._cache is None


def test_a_rewind_of_the_filling_pass_frees_its_part_and_a_restored_job_has_none(mesh8):
    x = _rows(13, 2000)
    job = _job(mesh8, 16)
    job.set_iterate({"centers": x[:K].copy()}, 0)
    job.fold(x[:1000], None, partition=0, pass_id=0)
    job.commit(0, pass_id=0)
    # recovery rewinds pass 0 (a daemon of the fit rebooted): a part of a pass
    job.set_iterate({"centers": x[:K].copy()}, 0)
    assert job._cache is None and job.pass_cache_bytes == 0
    for pid, (lo, hi) in enumerate([(0, 1000), (1000, 2000)]):
        job.fold(x[lo:hi], None, partition=pid, pass_id=0)
        job.commit(pid, pass_id=0)
    job.step({})
    assert job.rescan(1)["cached_rows"] == 2000
    # a rewind to a later boundary keeps the cached pass
    job.set_iterate(job.get_iterate()[0], 1)
    assert job.rescan(1)["cached_rows"] == 2000
    # a re-fed pass replaces the cached one
    job.set_iterate(job.get_iterate()[0], 2)
    job.fold(x[:1000], None, partition=0, pass_id=2)
    assert job._cache.filled_at == 2 and job.pass_cache_bytes == (1024 * D * 4 + 1024 * 4) // 8
    # what a durable snapshot restores holds no cached pass
    restored = _job(mesh8, 16)
    restored.set_iterate(job.durable_arrays(), 3)
    with pytest.raises(protocol.NoCachedPass, match="keeps none"):
        restored.rescan(3)
    # a job of another algo is given no budget
    with config.option("daemon_pass_cache_mb", 16):
        pca = _Job("pca", D, mesh8, {})
    assert pca.cache_ack() == {} and pca._cache_budget == 0


# ---------------- through a real daemon, client and spark/estimator.py -------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _no_plan_leaks():
    yield
    faults.deactivate()


def test_the_wire_op_its_acks_and_health(mesh8):
    x = _rows(17, 1500)
    with config.option("daemon_pass_cache_mb", 16), \
            DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        with DataPlaneClient(*daemon.address) as c:
            params = {"k": K}
            c.set_iterate("j", {"centers": x[:K]}, 0, algo="kmeans", params=params)
            for pid, (lo, hi) in enumerate([(0, 800), (800, 1500)]):
                c.feed("j", x[lo:hi], algo="kmeans", params=params, partition=pid, pass_id=0)
                rows, meta = c.commit("j", pid, pass_id=0, with_meta=True)
            assert rows == 1500 and meta["cached"] is True and meta["cached_rows"] == 1500
            held = c.health()["pass_cache_bytes"]
            assert held == 2 * (1024 * D * 4 + 1024 * 4) // 8
            c.step("j")
            ack = c.rescan("j", pass_id=1)
            assert (ack["pass_rows"], ack["cached_rows"], ack["cached_batches"]) == (1500, 1500, 2)
            assert ack["boot_id"] == daemon.boot_id
            assert c.step("j")["pass_rows"] == 1500
            # an error ack the client tells from a transport fault
            c.feed("p", x[:100], algo="pca")
            with pytest.raises(protocol.NoCachedPass, match="no cached pass"):
                c.rescan("p")
            assert c.stats["reconnects"] == 0
            snap = c.metrics()
            assert snap["srml_daemon_pass_cache_bytes"]["samples"][0]["value"] == held
            c.drop("j")
            assert c.health()["pass_cache_bytes"] == 0
    # with the key off: no field, no gauge
    metrics_mod.reset()
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        with DataPlaneClient(*daemon.address) as c:
            c.set_iterate("j", {"centers": x[:K]}, 0, algo="kmeans", params={"k": K})
            c.feed("j", x[:800], algo="kmeans", params={"k": K}, partition=0, pass_id=0)
            assert c.commit("j", 0, pass_id=0, with_meta=True)[1].keys() == {"id", "boot_id"}
            assert "pass_cache_bytes" not in c.health()
            assert not any("pass" in name for name in c.metrics())
            c.step("j")
            with pytest.raises(protocol.NoCachedPass):
                c.rescan("j", pass_id=1)


@pytest.fixture
def blobs():
    rng = np.random.default_rng(23)
    k, d = 3, 5
    centres = rng.normal(size=(k, d)) * 2
    x = np.concatenate([centres[i] + rng.normal(size=(200, d)) for i in range(k)])
    return x[rng.permutation(len(x))].astype(np.float32)


def _fit(x, max_iter=3):
    from sparksim import simdf_from_numpy
    from spark_rapids_ml_tpu.spark.estimator import SparkKMeans

    # concurrency=1: commits in partition order, so that the partitioned
    # re-fed fit and the cached one fold in one order
    df = simdf_from_numpy(x, n_partitions=3, concurrency=1)
    return SparkKMeans().setK(3).setMaxIter(max_iter).setTol(0.0).setSeed(5).fit(df)


def _requests_by_op(daemon):
    """The daemon's per-op request counts once a fit's requests are all
    counted. A connection thread counts a request AFTER it has answered
    it, so the driver can hold the last `drop`'s answer before the count
    moves; the fit closes its clients, and a thread that has ended has
    counted everything it served."""
    deadline = time.monotonic() + 10.0
    while daemon._active_conns and time.monotonic() < deadline:
        time.sleep(0.002)
    assert daemon._active_conns == 0
    return {s["labels"]["op"]: s["value"] for s in metrics_mod.snapshot()[
        "srml_daemon_requests_total"]["samples"]}


def test_spark_kmeans_rows_cross_the_wire_once_and_the_model_is_the_key_off_model(
        mesh8, monkeypatch, blobs):
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
        metrics_mod.reset()
        off = _fit(blobs)
        ops_off = _requests_by_op(daemon)
        assert "rescan" not in ops_off and _counter("srml_daemon_pass_rows_total") == 0
        assert ops_off["commit"] == 3 * 4  # 3 passes and the cost scan, all fed

        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            on = _fit(blobs)
        ops_on = _requests_by_op(daemon)
    # rows crossed the wire in pass 0 only; three scans came from the cache
    assert _counter("srml_daemon_pass_rows_total", source="wire") == len(blobs)
    assert _counter("srml_daemon_pass_rows_total", source="cache") == 3 * len(blobs)
    assert _counter("srml_daemon_passes_total", source="wire") == 1
    assert _counter("srml_daemon_passes_total", source="cache") == 3
    assert ops_on["commit"] == 3 and ops_on["rescan"] == 3
    assert ops_on["step"] == ops_off["step"] == 3
    # the key on costs the fit no op besides: fewer feeds and commits, a rescan a pass
    assert set(ops_on) == set(ops_off) | {"rescan"}
    for op in ("feed", "commit", "seed", "step", "finalize", "drop"):  # pings vary
        assert ops_on[op] <= ops_off[op], op
    # the model: counts are whole numbers, so the assignment is the same, and
    # the centres differ by the order of the accumulator's additions only
    assert on.summary.numIter == off.summary.numIter == 3
    assert on.summary.n_rows == off.summary.n_rows == len(blobs)
    np.testing.assert_allclose(on.centers, off.centers, rtol=0, atol=1e-12)
    assert on.summary.trainingCost == pytest.approx(off.summary.trainingCost, rel=1e-13)


def test_spark_kmeans_a_daemon_restarted_mid_fit_has_no_cached_pass_and_the_pass_is_refed(
        tmp_path, mesh8, monkeypatch, blobs):
    """Durable state on: the daemon dies at a pass boundary (step applied,
    snapshot written, ack unsent) and is restarted. The resurrected job
    holds no cached pass: `rescan` says so, the pass is re-fed inside
    `with_recovery`, which refills the cache, and the later passes are
    scanned from it again."""
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    port, holder = _free_port(), {}

    def start():
        holder["d"] = DataPlaneDaemon(host="127.0.0.1", port=port, mesh=mesh8,
                                      state_dir=str(tmp_path / "state")).start()

    def restart():
        holder["d"].stop()
        start()

    start()
    monkeypatch.setenv("SRML_DAEMON_ADDRESS", f"127.0.0.1:{port}")
    monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
    monkeypatch.setenv("SRML_FIT_RECOVERY_ATTEMPTS", "2")
    try:
        with config.option("daemon_pass_cache_mb", 16):
            clean = _fit(blobs, max_iter=5)
            metrics_mod.reset()
            plan = (FaultPlan(seed=3)
                    .rule("daemon.pass_boundary", "crash", after=2, times=1)
                    .on_crash(restart))
            with faults.active(plan):
                healed = _fit(blobs, max_iter=5)
        assert plan.fired.get("daemon.pass_boundary") == 1
    finally:
        holder["d"].stop()
    # pass 0 and the pass after the restart were fed, the rest scanned
    assert _counter("srml_daemon_passes_total", source="wire") >= 2
    assert _counter("srml_daemon_pass_rows_total", source="wire") >= 2 * len(blobs)
    assert _counter("srml_daemon_passes_total", source="cache") >= 3
    assert _counter("srml_daemon_job_restores_total") >= 1
    assert healed.summary.numIter == clean.summary.numIter == 5
    np.testing.assert_allclose(healed.centers, clean.centers, rtol=0, atol=1e-12)


# ---------------- two daemons: every daemon of the fit scans its own cache ----


def _two_daemon_fit(a, b, x, k, conf=()):
    from sparksim import SimSparkSession, simdf_from_numpy
    from spark_rapids_ml_tpu.spark.estimator import SparkKMeans

    addr = ["%s:%d" % d.address for d in (a, b)]
    session = SimSparkSession({"spark.srml.daemon.address": addr[0],
                               "spark.srml.daemon.addresses": ",".join(addr), **dict(conf)})
    # the upper half of the partitions is routed to the second daemon
    env_plan = {pid: {"SRML_DAEMON_ADDRESS": addr[1]} for pid in (2, 3)}
    df = simdf_from_numpy(x, n_partitions=4, session=session, env_plan=env_plan,
                          concurrency=1)
    return SparkKMeans().setK(k).setMaxIter(4).setTol(0.0).setSeed(3).fit(df)


@pytest.fixture
def integer_blobs():
    """Integer-valued rows: every statistic is exact, so the order of the
    additions cannot move the model and equality is bitwise."""
    rng = np.random.default_rng(29)
    k, d = 4, 6
    centres = rng.integers(-12, 13, size=(k, d)) * 2
    x = np.concatenate([centres[i] + rng.integers(-3, 4, size=(150, d)) for i in range(k)])
    return x[rng.permutation(len(x))].astype(np.float64), k


@pytest.mark.parametrize("collectives", [True, False], ids=["collective", "hub"])
def test_two_daemons_scan_their_own_caches_and_the_model_is_the_fed_one(
        mesh8, monkeypatch, integer_blobs, collectives):
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
    monkeypatch.delenv("SRML_DAEMON_ADDRESS", raising=False)
    x, k = integer_blobs
    with config.option("mesh_collectives", collectives), \
            DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        fed = _two_daemon_fit(a, b, x, k)
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            # the driver's side of the key by Spark conf, as fit_recovery_attempts
            cached = _two_daemon_fit(a, b, x, k, {"spark.srml.daemon.pass_cache_mb": "16"})
    assert _counter("srml_daemon_pass_rows_total", source="wire") == len(x)
    scans = fed.summary.numIter  # the passes after the first, and the cost scan
    assert scans >= 2 and cached.summary.numIter == scans
    assert _counter("srml_daemon_pass_rows_total", source="cache") == scans * len(x)
    assert _counter("srml_daemon_passes_total", source="cache") == scans * 2  # each daemon its own
    np.testing.assert_array_equal(cached.centers, fed.centers)
    assert cached.summary.trainingCost == fed.summary.trainingCost
    assert cached.summary.n_rows == fed.summary.n_rows == len(x)


def test_a_daemon_without_its_cached_pass_sends_the_whole_pass_back_to_a_refed_one(
        mesh8, monkeypatch, integer_blobs):
    """The second daemon asked has lost its cached pass after the first has
    folded its own: every daemon is re-opened at the pass's boundary, the
    pass is fed (which refills both caches), and the later passes are
    scanned again. No recovery budget is needed for it."""
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    monkeypatch.delenv("SRML_DAEMON_ADDRESS", raising=False)
    monkeypatch.delenv("SRML_FIT_RECOVERY_ATTEMPTS", raising=False)
    x, k = integer_blobs
    real, calls = _Job.rescan, []

    def losing(job, *args, **kwargs):
        calls.append(job)
        if len(calls) == 2:
            job._drop_cache()
            job._cache_ok = True  # lost, not over budget: the refill may be kept
        return real(job, *args, **kwargs)

    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        fed = _two_daemon_fit(a, b, x, k)
        monkeypatch.setattr(_Job, "rescan", losing)
        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            cached = _two_daemon_fit(a, b, x, k)
    assert calls[0] is not calls[1]
    # passes 0 and 1 fed, the later scans from each daemon's cache (and the
    # one rescan of pass 1 that was rewound)
    scans = fed.summary.numIter
    assert scans >= 2 and cached.summary.numIter == scans
    assert _counter("srml_daemon_pass_rows_total", source="wire") == 2 * len(x)
    assert _counter("srml_daemon_passes_total", source="cache") == 1 + (scans - 1) * 2
    np.testing.assert_array_equal(cached.centers, fed.centers)
    assert cached.summary.trainingCost == fed.summary.trainingCost
    assert cached.summary.n_rows == len(x)
