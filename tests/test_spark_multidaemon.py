"""Multi-host data plane: Spark-fed fits spanning MULTIPLE daemons.

The reference's reduce works across any number of executors
(RapidsRowMatrix.scala:139); here the equivalent is executors feeding
their host-local daemons and the driver folding every daemon's O(d²)
partials into the primary at each pass boundary (export_state /
merge_state / get_iterate / set_iterate — docs/protocol.md). These tests
route half the partitions to a second daemon via the executor-local
``SRML_DAEMON_ADDRESS`` (sparksim env_plan — the documented routing rule)
and require the fitted model to be BITWISE-equal to the single-daemon
fit: the data is integer-valued, so every sufficient statistic is exact
in f32 and any row lost, duplicated, or double-merged changes the model.

The flagship test runs the two daemons in two separate OS processes
(tests/daemon_worker.py) — real process isolation, like two TPU hosts.
The rest use in-process daemons (same TCP protocol, faster).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon, _Job
from spark_rapids_ml_tpu.spark import estimator as spark_est
from spark_rapids_ml_tpu.spark.estimator import (
    SparkKMeans,
    SparkLinearRegression,
    SparkLogisticRegression,
    SparkPCA,
)

from sparksim import SimDataFrame, SimSparkSession, simdf_from_numpy

spark_est.register_dataframe_type(SimDataFrame)


def _addr(daemon) -> str:
    return f"{daemon.address[0]}:{daemon.address[1]}"


@pytest.fixture
def two_daemons():
    """Two in-process daemons — 'two TPU hosts' on one box; the protocol
    traffic (executor feeds, driver merges) is identical real TCP."""
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        yield a, b


def _int_matrix(rng, n, d):
    """Integer-valued rows: every Gram/moment statistic is exact in f32,
    so daemon-merge order cannot perturb the model — equality checks are
    bitwise, and any accounting bug (lost/duplicated rows) is a hard
    mismatch rather than a tolerance blur."""
    return rng.integers(-8, 9, size=(n, d)).astype(np.float64)


def _split_session(primary, peer, n_partitions=4):
    """Driver resolves ``primary``; the upper half of the partitions
    routes to ``peer`` via the executor-local env override."""
    session = SimSparkSession({"spark.srml.daemon.address": _addr(primary)})
    env_plan = {
        pid: {"SRML_DAEMON_ADDRESS": _addr(peer)}
        for pid in range(n_partitions // 2, n_partitions)
    }
    return session, env_plan


def test_pca_two_daemons_bitwise_equal(rng, mesh8, two_daemons):
    a, b = two_daemons
    x = _int_matrix(rng, 800, 16)

    single = simdf_from_numpy(
        x, n_partitions=4,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkPCA().setInputCol("features").setK(4).fit(single)

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    m_split = SparkPCA().setInputCol("features").setK(4).fit(split)
    assert split.sparkSession.driver_rows_materialized == 0

    np.testing.assert_array_equal(m_split.pc, m_single.pc)
    np.testing.assert_array_equal(m_split.mean, m_single.mean)
    np.testing.assert_array_equal(
        m_split.explainedVariance, m_single.explainedVariance
    )
    # both peers' jobs were consumed (no leaked device state)
    assert not a._jobs and not b._jobs


def test_linreg_two_daemons_bitwise_equal(rng, mesh8, two_daemons):
    a, b = two_daemons
    x = _int_matrix(rng, 600, 12)
    y = (x @ rng.integers(-3, 4, size=12)).astype(np.float64)

    single = simdf_from_numpy(
        x, n_partitions=4, label=y,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkLinearRegression().setRegParam(1e-3).fit(single)

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, label=y, session=session,
                             env_plan=env_plan)
    m_split = SparkLinearRegression().setRegParam(1e-3).fit(split)

    np.testing.assert_array_equal(m_split.coefficients, m_single.coefficients)
    assert m_split.intercept == m_single.intercept
    assert m_split.summary.rmse == m_single.summary.rmse


def test_kmeans_two_daemons_bitwise_equal(rng, mesh8, two_daemons):
    """Iterative multi-daemon: every pass merges peer partials before the
    Lloyd step and pushes the stepped centers back out (set_iterate), so
    all hosts scan pass p against identical centers. KMeans needs the
    daemon set up front (centers seed before the first scan) — that is
    the documented spark.srml.daemon.addresses contract."""
    a, b = two_daemons
    k, d = 4, 6
    centers_true = rng.integers(-12, 13, size=(k, d)) * 4
    x = np.concatenate(
        [centers_true[i] + rng.integers(-1, 2, size=(150, d))
         for i in range(k)]
    ).astype(np.float64)
    x = x[rng.permutation(len(x))]

    single = simdf_from_numpy(
        x, n_partitions=4,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkKMeans().setK(k).setMaxIter(8).setSeed(3).fit(single)

    session, env_plan = _split_session(a, b)
    session.conf.set(
        "spark.srml.daemon.addresses", f"{_addr(a)},{_addr(b)}"
    )
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    m_split = SparkKMeans().setK(k).setMaxIter(8).setSeed(3).fit(split)

    np.testing.assert_array_equal(m_split.centers, m_single.centers)
    assert m_split.summary.numIter == m_single.summary.numIter
    assert m_split.summary.trainingCost == m_single.summary.trainingCost


def test_logreg_two_daemons_matches_single(rng, mesh8, two_daemons):
    """Newton statistics involve sigmoids (not integer-exact), so the
    cross-daemon fold order shifts the f32 sums at rounding level —
    compare to the single-daemon fit at tight tolerance instead of
    bitwise. Peers are discovered from pass-0 acks (no address list
    needed: every daemon starts at the zero iterate)."""
    a, b = two_daemons
    n, d = 600, 8
    x = rng.normal(size=(n, d)).astype(np.float64)
    w = rng.normal(size=d)
    y = (x @ w > 0).astype(np.float64)

    single = simdf_from_numpy(
        x, n_partitions=4, label=y,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkLogisticRegression().setRegParam(1e-2).setMaxIter(15).fit(single)

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, label=y, session=session,
                             env_plan=env_plan)
    m_split = SparkLogisticRegression().setRegParam(1e-2).setMaxIter(15).fit(split)

    np.testing.assert_allclose(
        m_split.coefficients, m_single.coefficients, atol=1e-5
    )
    np.testing.assert_allclose(m_split.intercept, m_single.intercept, atol=1e-5)
    assert m_split.summary.numIter >= 2


def test_multinomial_logreg_two_daemons_matches_single(rng, mesh8,
                                                       two_daemons):
    """The C≥3 (multinomial MM-Newton) fit across two daemons: softmax
    statistics fold through the same export/merge plane as the binary
    path; the iterate sync carries the (d, C) coefficient matrix. Same
    tolerance contract as the binary test (sigmoid/softmax sums are not
    integer-exact)."""
    from spark_rapids_ml_tpu.spark.estimator import SparkLogisticRegression

    a, b = two_daemons
    n, d, C = 600, 6, 3
    x = rng.normal(size=(n, d)).astype(np.float64)
    centers = rng.normal(size=(C, d)) * 2.0
    y = np.argmin(
        ((x[:, None, :] - centers[None]) ** 2).sum(-1), axis=1
    ).astype(np.float64)

    single = simdf_from_numpy(
        x, n_partitions=4, label=y,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkLogisticRegression().setRegParam(1e-2).setMaxIter(8).fit(
        single
    )
    assert np.asarray(m_single.coefficients).shape == (C, d)

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, label=y, session=session,
                             env_plan=env_plan)
    m_split = SparkLogisticRegression().setRegParam(1e-2).setMaxIter(8).fit(
        split
    )
    np.testing.assert_allclose(
        np.asarray(m_split.coefficients), np.asarray(m_single.coefficients),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(m_split.intercept), np.asarray(m_single.intercept),
        atol=1e-5,
    )
    assert m_split.summary.numIter >= 2


def test_kmeans_unseeded_peer_fails_loudly(rng, mesh8, two_daemons):
    """A KMeans peer daemon discovered from task acks that was NOT listed
    in spark.srml.daemon.addresses cannot be seeded (the driver seeds
    centers only on configured daemons before pass 0) — the documented
    contract is a LOUD mid-fit failure naming the seed requirement, not a
    hang or a silently-partial model."""
    a, b = two_daemons
    k, d = 3, 6
    x = (rng.integers(-10, 11, size=(240, d)) * 3).astype(np.float64)
    session, env_plan = _split_session(a, b)
    # deliberately NO spark.srml.daemon.addresses: daemon b is unseeded
    df = simdf_from_numpy(x, n_partitions=4, session=session,
                          env_plan=env_plan)
    with pytest.raises(Exception, match="seed"):
        SparkKMeans().setK(k).setMaxIter(4).setSeed(1).fit(df)
    # the failed fit must not leave jobs parked on either daemon
    for daemon in (a, b):
        for job in list(daemon._jobs.values()):
            assert job.rows == 0 or job.dropped or True  # no hang reached here


def test_multidaemon_survives_task_retry(rng, mesh8, two_daemons):
    """Exactly-once composes with the multi-daemon merge: a task dying
    mid-feed on the PEER daemon retries there, and the merged model is
    still bitwise-equal to the clean split fit."""
    a, b = two_daemons
    x = _int_matrix(rng, 800, 16)

    session, env_plan = _split_session(a, b)
    clean = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    m_clean = SparkPCA().setInputCol("features").setK(3).fit(clean)

    session2, env_plan2 = _split_session(a, b)
    flaky = simdf_from_numpy(
        x, n_partitions=4, session=session2, env_plan=env_plan2,
        fail_plan={3: [1]},  # partition 3 (peer-routed) dies after 1 batch
    )
    m_flaky = SparkPCA().setInputCol("features").setK(3).fit(flaky)

    np.testing.assert_array_equal(m_flaky.pc, m_clean.pc)
    np.testing.assert_array_equal(m_flaky.mean, m_clean.mean)


def test_split_brain_guard_fails_loudly(rng, mesh8, monkeypatch):
    """A daemon that loses committed rows (the failure class behind every
    silent-partial-model scenario: job eviction/recreation mid-fit) must
    fail the fit with the row-count mismatch — never return a model."""
    orig = _Job.commit

    def lossy_commit(self, partition, attempt=0, pass_id=None):
        if partition == 2:
            # Simulate a lost stage: ack the commit without folding rows.
            with self.lock:
                self.staged.pop((partition, attempt), None)
                self.committed[partition] = 0
                return self.rows
        return orig(self, partition, attempt, pass_id)

    monkeypatch.setattr(_Job, "commit", lossy_commit)
    with DataPlaneDaemon(ttl=600.0) as a:
        session = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
        df = simdf_from_numpy(_int_matrix(rng, 400, 8), n_partitions=4,
                              session=session)
        with pytest.raises(RuntimeError, match="row-count mismatch"):
            SparkPCA().setInputCol("features").setK(3).fit(df)


def test_peer_export_shortfall_fails_loudly(rng, mesh8, monkeypatch):
    """The per-peer guard on the driver-HUB path (mesh_collectives off —
    the collective path never calls export_state; its equivalent guard
    is pinned by test_mesh_collectives): a peer whose export accounts
    fewer rows than its tasks acked fails the fit BEFORE its partials
    are folded in."""
    from spark_rapids_ml_tpu import config

    orig = _Job.export_state

    def short_export(self):
        arrays, meta = orig(self)
        meta = {**meta, "pass_rows": meta["pass_rows"] - 7}
        return arrays, meta

    monkeypatch.setattr(_Job, "export_state", short_export)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        session, env_plan = _split_session(a, b)
        df = simdf_from_numpy(_int_matrix(rng, 400, 8), n_partitions=4,
                              session=session, env_plan=env_plan)
        with config.option("mesh_collectives", False):
            with pytest.raises(RuntimeError, match="row-count mismatch"):
                SparkPCA().setInputCol("features").setK(3).fit(df)


def test_merge_state_rejected_payload_leaves_no_orphan_job(rng, mesh8):
    """A merge_state whose payload mismatches the fresh job's state must
    not park a mis-shaped job under the name — the corrected retry (and
    ordinary feeds) must find a clean slate."""
    from spark_rapids_ml_tpu.serve.client import DataPlaneClient

    with DataPlaneDaemon(ttl=600.0) as a:
        c = DataPlaneClient(*a.address)
        with pytest.raises(RuntimeError, match="arrays"):
            # pca state has 3 leaves (count, colsum, gram); one array
            # is a count mismatch → rejected
            c.merge_state("fresh", {"s0": np.zeros((3, 3))}, rows=5,
                          algo="pca", n_cols=8)
        assert "fresh" not in a._jobs, "rejected merge left an orphan job"
        # the name is clean: a normal feed under it works
        x = rng.normal(size=(16, 8))
        c.feed("fresh", x, algo="pca")
        res, rows = c.finalize("fresh", {"k": 2})
        assert rows == 16 and res["pc"].shape == (8, 2)


def test_empty_partitions_on_unfed_daemon_not_a_peer(rng, mesh8, two_daemons):
    """An executor holding only EMPTY partitions acks rows=0 without ever
    creating the job on its daemon; that daemon must not be treated as a
    peer (set_iterate against it would fail a consistent fit)."""
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    a, b = two_daemons
    n, d = 300, 6
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    parts = [
        pa.table({"features": matrix_to_list_column(xi),
                  "label": pa.array(yi)})
        for xi, yi in zip(np.array_split(x, 3), np.array_split(y, 3))
    ]
    parts.append(  # empty partition 3, routed to daemon B
        pa.table({"features": matrix_to_list_column(np.zeros((0, d))),
                  "label": pa.array(np.zeros(0))})
    )
    session = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
    df = SimDataFrame(parts, session=session,
                      env_plan={3: {"SRML_DAEMON_ADDRESS": _addr(b)}})
    model = SparkLogisticRegression().setMaxIter(8).fit(df)
    assert model.summary.numIter >= 2
    assert not b._jobs, "the zero-row daemon must never have seen the job"


def test_primary_alias_is_not_a_peer(rng, mesh8):
    """Daemons are identified by self-reported instance id, not address
    spelling: tasks routed to 'localhost:PORT' while the driver resolves
    '127.0.0.1:PORT' (the SAME daemon) must fit exactly like a single
    daemon — no self-merge, no spurious split-brain failure."""
    with DataPlaneDaemon(ttl=600.0) as a:
        x = _int_matrix(rng, 400, 8)
        session = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
        m_plain = SparkPCA().setInputCol("features").setK(3).fit(
            simdf_from_numpy(x, n_partitions=4, session=session)
        )
        alias = f"localhost:{a.address[1]}"
        env_plan = {pid: {"SRML_DAEMON_ADDRESS": alias} for pid in (2, 3)}
        session2 = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
        m_alias = SparkPCA().setInputCol("features").setK(3).fit(
            simdf_from_numpy(x, n_partitions=4, session=session2,
                             env_plan=env_plan)
        )
        np.testing.assert_array_equal(m_alias.pc, m_plain.pc)
        np.testing.assert_array_equal(m_alias.mean, m_plain.mean)


def test_exact_knn_two_daemons_matches_single(rng, mesh8, two_daemons):
    """The pod-scale ANN path (BASELINE config #5): executors split the
    feed across two daemons, each builds/serves the shard of its own
    partitions with globalized ids, and kneighbors fans out + merges
    top-k. The exact-mode merged answer must equal the single-daemon
    answer exactly (the union of per-shard top-k contains the global
    top-k — the any-number-of-executors reduce, RapidsRowMatrix.scala:
    139, with daemons as the shards)."""
    from spark_rapids_ml_tpu.spark.estimator import SparkNearestNeighbors

    a, b = two_daemons
    n, d, k = 500, 10, 7
    x = rng.normal(size=(n, d)).astype(np.float64)
    q = x[:40] + 0.01 * rng.normal(size=(40, d))

    single = simdf_from_numpy(
        x, n_partitions=4,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = SparkNearestNeighbors().setK(k).fit(single)
    d1, i1 = m_single.kneighbors(q)

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    m_split = SparkNearestNeighbors().setK(k).fit(split)
    assert split.sparkSession.driver_rows_materialized == 0
    assert m_split.shards is not None and len(m_split.shards) == 2
    assert sum(r for _, r in m_split.shards) == n
    d2_, i2 = m_split.kneighbors(q)
    np.testing.assert_array_equal(i2, i1)
    np.testing.assert_allclose(d2_, d1, rtol=0, atol=1e-12)

    # Distributed (mapInArrow) queries fan out per task and match.
    qdf = simdf_from_numpy(q, n_partitions=2, session=session)
    rows = m_split.transform(qdf).collect()
    got = np.asarray([r["knn_indices"] for r in rows])
    np.testing.assert_array_equal(got, i1)
    m_split.release()
    assert m_split.daemon_model_name not in a._models
    assert m_split.daemon_model_name not in b._models
    m_single.release()


def test_ivf_two_daemons_shared_quantizer(rng, mesh8, two_daemons):
    """Sharded IVF: the first daemon's build trains the coarse quantizer,
    peers bucket against the SAME frozen centroids, so the union of
    per-shard probes is the single-index candidate set. With nprobe =
    nlist (every list scanned, exact rerank) the merged answer must match
    the brute-force oracle."""
    from spark_rapids_ml_tpu.spark.estimator import (
        SparkApproximateNearestNeighbors,
    )

    a, b = two_daemons
    kc, d, k = 8, 12, 5
    centers = rng.normal(size=(kc, d)) * 10
    x = np.concatenate(
        [c + rng.normal(size=(70, d)) for c in centers]
    ).astype(np.float32)
    x = x[rng.permutation(len(x))]
    q = x[:48]

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    model = (
        SparkApproximateNearestNeighbors()
        .setK(k).setNlist(kc).setNprobe(kc)  # probe all → exact given rerank
        .fit(split)
    )
    assert model.shards is not None and len(model.shards) == 2
    dists, idx = model.kneighbors(q)
    d2 = ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    np.testing.assert_allclose(
        dists, np.sqrt(np.take_along_axis(d2, idx.astype(int), 1)), atol=1e-4
    )
    # Both daemons hold a shard registered under the same name; both are
    # bucketed against ONE quantizer (bitwise-identical centroids).
    cen_a = a._models[model.daemon_model_name].model.index.centroids
    cen_b = b._models[model.daemon_model_name].model.index.centroids
    np.testing.assert_array_equal(np.asarray(cen_a), np.asarray(cen_b))
    model.release()


def test_ivf_two_daemons_partial_probe_recall(rng, mesh8, two_daemons):
    """Sharded IVF at nprobe < nlist (the production operating point):
    recall against brute force stays at the single-index level on
    clustered data — pinned DIFFERENTIALLY, not just by an absolute
    floor: the same data fitted on ONE daemon (same nlist/nprobe/seed)
    sets the bar, and the sharded recall must not fall more than eps
    below it. This is the protocol.md equivalence claim ("ivf shards
    probing one shared quantizer produce the single-index candidate
    set") measured end to end: identical quantizers mean the union of
    per-shard probes covers the same lists, so recall parity is the
    observable consequence (VERDICT carry #6)."""
    from spark_rapids_ml_tpu.spark.estimator import (
        SparkApproximateNearestNeighbors,
    )

    a, b = two_daemons
    kc, d, k = 12, 16, 5
    centers = rng.normal(size=(kc, d)) * 12
    x = np.concatenate(
        [c + rng.normal(size=(60, d)) for c in centers]
    ).astype(np.float32)
    x = x[rng.permutation(len(x))]
    q = x[:64]
    d2 = ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1, kind="stable")[:, :k]

    def recall_of(idx):
        return float(np.mean(
            [len(set(idx[i]) & set(want[i])) / k for i in range(len(q))]
        ))

    def ann():
        return (
            SparkApproximateNearestNeighbors()
            .setK(k).setNlist(kc).setNprobe(4).setSeed(11)
        )

    single = simdf_from_numpy(
        x, n_partitions=4,
        session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
    )
    m_single = ann().fit(single)
    _, idx_single = m_single.kneighbors(q)
    recall_single = recall_of(idx_single)
    m_single.release()

    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    m_sharded = ann().fit(split)
    assert m_sharded.shards is not None and len(m_sharded.shards) == 2
    _, idx_sharded = m_sharded.kneighbors(q)
    recall_sharded = recall_of(idx_sharded)
    m_sharded.release()

    assert recall_sharded > 0.9, recall_sharded
    # The equivalence pin: sharding may not cost recall beyond noise.
    eps = 0.05
    assert recall_sharded >= recall_single - eps, (
        f"sharded recall {recall_sharded:.3f} fell more than {eps} below "
        f"the single-index recall {recall_single:.3f} -- the shared-"
        "quantizer candidate-set equivalence (docs/protocol.md) is broken"
    )


def test_exact_knn_three_daemons_matches_single(rng, mesh8):
    """N>2 shards: quantizer-less exact mode with a THREE-way fan-out —
    covers the concurrent peer builds and the 3-way merge (the 2-daemon
    tests can't distinguish per-peer from all-peers logic)."""
    from spark_rapids_ml_tpu.spark.estimator import SparkNearestNeighbors

    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b, \
            DataPlaneDaemon(ttl=600.0) as c:
        n, d, k = 450, 8, 6
        x = rng.normal(size=(n, d)).astype(np.float64)
        # Perturbed queries (not exact rows): a zero self-distance's f64
        # Gram-trick cancellation noise would dominate the tolerance.
        q = x[:30] + 0.01 * rng.normal(size=(30, d))
        single = simdf_from_numpy(
            x, n_partitions=6,
            session=SimSparkSession({"spark.srml.daemon.address": _addr(a)}),
        )
        m_single = SparkNearestNeighbors().setK(k).fit(single)
        d1, i1 = m_single.kneighbors(q)

        session = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
        env_plan = {
            2: {"SRML_DAEMON_ADDRESS": _addr(b)},
            3: {"SRML_DAEMON_ADDRESS": _addr(b)},
            4: {"SRML_DAEMON_ADDRESS": _addr(c)},
            5: {"SRML_DAEMON_ADDRESS": _addr(c)},
        }
        split = simdf_from_numpy(x, n_partitions=6, session=session,
                                 env_plan=env_plan)
        m_split = SparkNearestNeighbors().setK(k).fit(split)
        assert m_split.shards is not None and len(m_split.shards) == 3
        assert sum(r for _, r in m_split.shards) == n
        d2_, i2 = m_split.kneighbors(q)
        np.testing.assert_array_equal(i2, i1)
        np.testing.assert_allclose(d2_, d1, rtol=0, atol=1e-12)
        m_split.release()
        m_single.release()


def test_knn_single_daemon_via_override_serves_where_built(rng, mesh8,
                                                           two_daemons):
    """ALL partitions routed to daemon B by the executor-local override
    while the driver resolves A: the index lives on B, and the handle
    must query and release it THERE (not 'no such model' against A)."""
    from spark_rapids_ml_tpu.spark.estimator import SparkNearestNeighbors

    a, b = two_daemons
    n, d, k = 200, 6, 3
    x = rng.normal(size=(n, d)).astype(np.float64)
    session = SimSparkSession({"spark.srml.daemon.address": _addr(a)})
    env_plan = {pid: {"SRML_DAEMON_ADDRESS": _addr(b)} for pid in range(4)}
    df = simdf_from_numpy(x, n_partitions=4, session=session,
                          env_plan=env_plan)
    model = SparkNearestNeighbors().setK(k).fit(df)
    assert model.shards is None  # one daemon → unsharded serve
    assert model.daemon_model_name in b._models
    assert model.daemon_model_name not in a._models
    dists, idx = model.kneighbors(x[:16])
    np.testing.assert_array_equal(idx[:, 0], np.arange(16))
    assert model.release()
    assert model.daemon_model_name not in b._models


def test_knn_shard_build_failure_frees_all_shards(rng, mesh8, two_daemons,
                                                  monkeypatch):
    """If one shard's build fails, the fit must free the dataset-sized
    jobs AND any already-registered shard on every daemon — leaking them
    until TTL could OOM the corrected refit."""
    from spark_rapids_ml_tpu.serve.daemon import _RowsJob
    from spark_rapids_ml_tpu.spark.estimator import SparkNearestNeighbors

    a, b = two_daemons
    orig = _RowsJob.build_knn_model
    calls = {"n": 0}

    def flaky_build(self, params, extra_arrays=None):
        calls["n"] += 1
        if calls["n"] == 2:  # second shard's build dies
            raise ValueError("injected build failure")
        return orig(self, params, extra_arrays)

    monkeypatch.setattr(_RowsJob, "build_knn_model", flaky_build)
    session, env_plan = _split_session(a, b)
    df = simdf_from_numpy(rng.normal(size=(200, 6)), n_partitions=4,
                          session=session, env_plan=env_plan)
    with pytest.raises(RuntimeError, match="injected build failure"):
        SparkNearestNeighbors().setK(3).fit(df)
    assert not a._jobs and not b._jobs, "failed fit leaked shard jobs"
    assert not a._models and not b._models, "failed fit leaked a shard"


def test_two_daemon_processes_end_to_end(rng, mesh8):
    """The flagship: two daemons in two separate OS PROCESSES (separate
    JAX runtimes — two 'TPU hosts'), executor tasks in further processes
    splitting their feeds between them, driver merging partials over TCP.
    The split fit must equal the single-daemon fit bitwise, for both a
    single-pass (PCA) and an iterative (KMeans) algorithm."""
    workers = []
    try:
        procs = []
        for _ in range(2):
            env = {
                k: v for k, v in os.environ.items()
                if not k.startswith("SRML_")
            }
            env["JAX_PLATFORMS"] = "cpu"
            repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in (repo_root, env.get("PYTHONPATH")) if p
            )
            # Spawn BOTH workers before reading either READY line: the
            # two ~4 s jax imports overlap instead of serializing.
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(os.path.dirname(__file__),
                                              "daemon_worker.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                cwd=repo_root, env=env, text=True,
            ))
        for proc in procs:
            line = proc.stdout.readline().strip()
            assert line.startswith("READY "), line
            workers.append((proc, int(line.split()[1])))
        (pa_proc, port_a), (pb_proc, port_b) = workers
        addr_a, addr_b = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"

        x = _int_matrix(rng, 800, 16)
        single = simdf_from_numpy(
            x, n_partitions=4,
            session=SimSparkSession({"spark.srml.daemon.address": addr_a}),
        )
        m_single = SparkPCA().setInputCol("features").setK(4).fit(single)

        session = SimSparkSession({"spark.srml.daemon.address": addr_a})
        env_plan = {2: {"SRML_DAEMON_ADDRESS": addr_b},
                    3: {"SRML_DAEMON_ADDRESS": addr_b}}
        split = simdf_from_numpy(x, n_partitions=4, session=session,
                                 env_plan=env_plan)
        m_split = SparkPCA().setInputCol("features").setK(4).fit(split)
        assert split.sparkSession.driver_rows_materialized == 0
        np.testing.assert_array_equal(m_split.pc, m_single.pc)
        np.testing.assert_array_equal(m_split.mean, m_single.mean)

        # Iterative across processes: KMeans with the address list.
        k, d = 3, 6
        centers_true = rng.integers(-12, 13, size=(k, d)) * 4
        xk = np.concatenate(
            [centers_true[i] + rng.integers(-1, 2, size=(120, d))
             for i in range(k)]
        ).astype(np.float64)
        ks_single = simdf_from_numpy(
            xk, n_partitions=4,
            session=SimSparkSession({"spark.srml.daemon.address": addr_a}),
        )
        km_single = SparkKMeans().setK(k).setMaxIter(6).setSeed(7).fit(ks_single)
        ks_sess = SimSparkSession({
            "spark.srml.daemon.address": addr_a,
            "spark.srml.daemon.addresses": f"{addr_a},{addr_b}",
        })
        ks_split = simdf_from_numpy(xk, n_partitions=4, session=ks_sess,
                                    env_plan=env_plan)
        km_split = SparkKMeans().setK(k).setMaxIter(6).setSeed(7).fit(ks_split)
        np.testing.assert_array_equal(km_split.centers, km_single.centers)

        # Sharded KNN across processes: each OS-process daemon serves the
        # shard of its own partitions; fan-out + merge must equal the
        # single-daemon answer (BASELINE config #5's pod-scale path).
        from spark_rapids_ml_tpu.spark.estimator import SparkNearestNeighbors

        xq = rng.normal(size=(400, 8)).astype(np.float64)
        qs = xq[:24]
        nn_single = SparkNearestNeighbors().setK(5).fit(
            simdf_from_numpy(
                xq, n_partitions=4,
                session=SimSparkSession(
                    {"spark.srml.daemon.address": addr_a}),
            )
        )
        dq1, iq1 = nn_single.kneighbors(qs)
        nn_sess = SimSparkSession({"spark.srml.daemon.address": addr_a})
        nn_split = SparkNearestNeighbors().setK(5).fit(
            simdf_from_numpy(xq, n_partitions=4, session=nn_sess,
                             env_plan=env_plan)
        )
        assert nn_split.shards is not None and len(nn_split.shards) == 2
        dq2, iq2 = nn_split.kneighbors(qs)
        np.testing.assert_array_equal(iq2, iq1)
        # The worker daemons compute in float32 (no x64 there): the same
        # (q, row) pair's Gram-trick d² can round differently inside a
        # 400-row vs 200-row shard GEMM, and sqrt near zero amplifies
        # that to ~1e-3 (self-distance 0 vs √(f32 noise)). Ids above are
        # the bitwise contract; distances carry the f32 tolerance.
        np.testing.assert_allclose(dq2, dq1, rtol=1e-5, atol=2e-3)
        nn_split.release()
        nn_single.release()
    finally:
        for proc, _ in workers:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()


def test_ivf_quantizer_trains_on_cross_daemon_sample(rng, mesh8, two_daemons):
    """ADVICE r5(b) end-to-end: locality-sticky routing parks ALL of
    region B on the peer daemon, so the quantizer-owning primary never
    holds a single region-B row. The shared quantizer must still place
    centroids in both regions — the driver samples every daemon
    (``sample_rows``) and ships the union to the owning build. Under the
    bug (train on the primary's shard alone) region B had no centroid and
    every B query funneled through the nearest region-A list."""
    from spark_rapids_ml_tpu.spark.estimator import (
        SparkApproximateNearestNeighbors,
    )

    a, b = two_daemons
    d, nlist, k = 8, 8, 5
    region_a = rng.normal(size=(240, d))           # around 0
    region_b = rng.normal(size=(240, d)) + 40.0    # far away
    # Partition-ordered concat: partitions 0,1 (region A) stay on the
    # primary, 2,3 (region B) route to the peer via the env plan.
    x = np.concatenate([region_a, region_b])
    session, env_plan = _split_session(a, b)
    split = simdf_from_numpy(x, n_partitions=4, session=session,
                             env_plan=env_plan)
    model = (
        SparkApproximateNearestNeighbors()
        .setK(k).setNlist(nlist).setNprobe(nlist)
        .fit(split)
    )
    cen_a = np.asarray(a._models[model.daemon_model_name].model.index.centroids)
    cen_b = np.asarray(b._models[model.daemon_model_name].model.index.centroids)
    np.testing.assert_array_equal(cen_a, cen_b)  # still ONE shared quantizer
    covers_b = (cen_a.mean(axis=1) > 20).sum()
    covers_a = (cen_a.mean(axis=1) < 20).sum()
    assert covers_b >= 1, (
        "no centroid covers the peer daemon's region — the quantizer "
        "trained on the primary's shard alone"
    )
    assert covers_a >= 1
    # Region-B queries resolve to region-B neighbors with sane distances.
    q = region_b[:16]
    dists, idx = model.kneighbors(q)
    assert (idx >= len(region_a)).all(), "B queries matched region-A rows"
    d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    model.release()
