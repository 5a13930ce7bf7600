"""Pod-scale fit: on-mesh collective reductions replacing the driver hub.

Covers the collective layer end to end (docs/mesh.md):

* ``parallel/mapreduce.py`` primitives (map_fn/reduce_sum/all_concat/
  reduce_topk) against numpy oracles on the 8-device mesh;
* mesh membership: epoch bumps on join/leave/REBOOT, the ``mesh_info``
  wire op, the ``health`` mesh block;
* the ``reduce_mesh`` wire op: epoch fencing, the (boot_id, pass_rows)
  pre-reduce handshake, partition-accounting guards, replay dedupe;
* the flagship parity contract: a 2-daemon Spark-sim fit reduced on the
  mesh is BITWISE-identical to the same fit forced through the driver
  export/merge hub (``mesh_collectives`` off) — the fallback and the
  fast path may never drift;
* daemon reboot mid-fit under collectives: epoch bump → the PR 4 ledger
  replays the pass → bitwise-equal model;
* capacity: d over the per-device Gram budget raises on a 1-device mesh
  and fits via the model-parallel Gram/eigh (sharding instead of
  rejection), including the real d=8192 acceptance shape;
* satellites: warmup-on-register, the persistent compile cache +
  ``srml_xla_persistent_cache_hits_total``, and perfcheck's MULTICHIP
  gating (dryrun = skip-not-pass; efficiency floor).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.ops import gram as gram_ops
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel import membership
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
from spark_rapids_ml_tpu.serve.client import DataPlaneClient
from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon, _Job
from spark_rapids_ml_tpu.spark import estimator as spark_est
from spark_rapids_ml_tpu.spark.estimator import SparkKMeans, SparkPCA
from spark_rapids_ml_tpu.utils import metrics as metrics_mod

from sparksim import SimDataFrame, SimSparkSession, simdf_from_numpy

spark_est.register_dataframe_type(SimDataFrame)


def _addr(daemon) -> str:
    return f"{daemon.address[0]}:{daemon.address[1]}"


def _counter_total(snap, name, **labels):
    total = 0
    for s in snap.get(name, {}).get("samples", []):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
    return total


def _int_matrix(rng, n, d):
    """Integer rows: every statistic is exact in f32/f64, so bitwise
    equality is a real invariant, not a tolerance blur."""
    return rng.integers(-8, 9, size=(n, d)).astype(np.float64)


def _split_session(primary, peer, n_partitions=4):
    session = SimSparkSession({"spark.srml.daemon.address": _addr(primary)})
    env_plan = {
        pid: {"SRML_DAEMON_ADDRESS": _addr(peer)}
        for pid in range(n_partitions // 2, n_partitions)
    }
    return session, env_plan


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ------------------------- mapreduce primitives ------------------------------


def test_reduce_sum_matches_numpy(rng, mesh8):
    x = rng.standard_normal((64, 16))
    xs = jax.device_put(x, NamedSharding(mesh8, P(DATA_AXIS, None)))
    f = mr.map_fn(
        lambda b: mr.reduce_sum(jnp.sum(b, axis=0), DATA_AXIS),
        mesh8,
        in_specs=P(DATA_AXIS, None),
        out_specs=P(),
    )
    np.testing.assert_allclose(
        np.asarray(jax.jit(f)(xs)), x.sum(axis=0), rtol=1e-12
    )


def test_all_concat_matches_numpy(rng, mesh8):
    x = rng.standard_normal((16, 8))
    xs = jax.device_put(x, NamedSharding(mesh8, P(DATA_AXIS, None)))
    f = mr.map_fn(
        lambda b: mr.all_concat(b, DATA_AXIS, axis=0),
        mesh8,
        in_specs=P(DATA_AXIS, None),
        out_specs=P(None, None),
        check_vma=False,
    )
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(xs)), x)


def test_reduce_topk_is_exact(rng, mesh8):
    """Per-shard local top-k merged by reduce_topk == global top-k."""
    q, n, k = 5, 64, 4
    d2 = rng.standard_normal((q, n)) ** 2
    ids = np.broadcast_to(np.arange(n, dtype=np.int64), (q, n)).copy()
    d2s = jax.device_put(d2.T, NamedSharding(mesh8, P(DATA_AXIS, None)))
    ids_s = jax.device_put(ids.T, NamedSharding(mesh8, P(DATA_AXIS, None)))

    def shard(db, di):
        neg, pos = jax.lax.top_k(-db.T, k)  # local top-k per shard
        return mr.reduce_topk(-neg, jnp.take_along_axis(di.T, pos, axis=1), k)

    f = mr.map_fn(
        shard, mesh8,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    dist, idx = jax.jit(f)(d2s, ids_s)
    order = np.argsort(d2, axis=1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.sort(order))
    np.testing.assert_allclose(
        np.asarray(dist), np.take_along_axis(d2, order, axis=1), rtol=1e-12
    )


def test_collective_traces_are_booked(rng, mesh8):
    """The lint gate routes every collective through mapreduce; this pins
    that the routing is observable — tracing a program books the
    counter."""
    before = _counter_total(
        metrics_mod.snapshot(), "srml_parallel_collective_traces_total"
    )
    x = rng.standard_normal((32, 8)).astype(np.float32)
    # A fresh shape signature forces a retrace of the fused stats.
    f = gram_ops.sharded_stats(mesh8)
    xs, mask, _ = __import__(
        "spark_rapids_ml_tpu.parallel.sharding", fromlist=["shard_rows"]
    ).shard_rows(x, mesh8)
    jax.block_until_ready(f(xs, mask))
    after = _counter_total(
        metrics_mod.snapshot(), "srml_parallel_collective_traces_total"
    )
    assert after > before


# ------------------------- membership + wire ops -----------------------------


def test_membership_epoch_bumps_on_join_leave_and_reboot():
    reg = membership.MeshMembership()

    class H:  # a registrable handle
        pass

    h1, h2 = H(), H()
    e0 = reg.epoch
    e1 = reg.register("a", "boot1", h1)
    assert e1 > e0
    e2 = reg.register("b", "boot2", h2)
    assert e2 > e1
    # REBOOT: same id, new boot — must bump (the stale-partial fence).
    e3 = reg.register("a", "boot9", h1)
    assert e3 > e2
    snap = reg.snapshot()
    boots = {m["id"]: m["boot_id"] for m in snap["members"]}
    assert boots == {"a": "boot9", "b": "boot2"}
    e4 = reg.unregister("b")
    assert e4 > e3
    assert reg.unregister("nope") == e4  # unknown id: no silent bump
    assert reg.get("a", boot_id="boot1") is None  # old incarnation gone
    assert reg.get("a", boot_id="boot9") is h1


def test_membership_unregister_is_incarnation_scoped():
    """A superseded daemon object's late stop() must not deregister the
    live successor holding the same durable instance id."""
    reg = membership.MeshMembership()

    class H:
        pass

    a1, a2 = H(), H()
    reg.register("X", "boot1", a1)
    reg.register("X", "boot2", a2)  # successor on the same durable id
    e = reg.epoch
    assert reg.unregister("X", boot_id="boot1") == e  # stale: no-op
    assert reg.get("X", boot_id="boot2") is a2
    assert reg.unregister("X", boot_id="boot2") > e  # the live one leaves
    assert reg.get("X") is None


def test_membership_dead_handles_read_as_absent():
    reg = membership.MeshMembership()

    class H:
        pass

    h = H()
    reg.register("ghost", "b", h)
    del h
    assert reg.get("ghost") is None
    assert reg.snapshot()["members"] == []


def test_mesh_info_op_and_health_mesh_block(mesh8):
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        with DataPlaneClient(*a.address) as c:
            info = c.mesh_info()
            ids = {m["id"]: m["boot_id"] for m in info["members"]}
            assert ids.get(a.instance_id) == a.boot_id
            assert ids.get(b.instance_id) == b.boot_id
            assert info["epoch"] == membership.registry().epoch
            health = c.health()
            assert health["mesh"]["epoch"] == info["epoch"]
            assert health["mesh"]["members"] >= 2
        epoch_before = membership.registry().epoch
    # both daemons stopped -> two unregistrations
    assert membership.registry().epoch >= epoch_before + 1


def _feed_pca_job(client, job, x, partition=0):
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    table = pa.table({"features": matrix_to_list_column(x)})
    client.feed(job, table, algo="pca", input_col="features",
                partition=partition, attempt=0)
    client.commit(job, partition=partition, attempt=0)


def test_reduce_mesh_op_folds_and_fences(rng, mesh8):
    """Protocol-level reduce_mesh: a correct request folds the peer's
    device state into the primary (rows account); a stale epoch, a wrong
    boot_id, and a row-count lie each refuse loudly BEFORE folding."""
    x1 = _int_matrix(rng, 64, 8).astype(np.float32)
    x2 = _int_matrix(rng, 32, 8).astype(np.float32)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        with DataPlaneClient(*a.address) as ca, DataPlaneClient(*b.address) as cb:
            _feed_pca_job(ca, "job", x1, partition=0)
            _feed_pca_job(cb, "job", x2, partition=1)
            epoch = ca.mesh_info()["epoch"]
            peers = {
                b.instance_id: {
                    "boot_id": b.boot_id, "rows": 32, "partitions": [1],
                }
            }
            # stale epoch → refused
            with pytest.raises(RuntimeError, match="membership changed"):
                ca.reduce_mesh("job", epoch=epoch - 1, peers=peers)
            # wrong boot → refused (rebooted-peer fence)
            bad = {b.instance_id: {**peers[b.instance_id], "boot_id": "dead"}}
            with pytest.raises(RuntimeError, match="not a co-resident"):
                ca.reduce_mesh("job", epoch=epoch, peers=bad)
            # row-count lie → refused (pre-reduce handshake)
            lie = {b.instance_id: {**peers[b.instance_id], "rows": 31}}
            with pytest.raises(RuntimeError, match="row-count mismatch"):
                ca.reduce_mesh("job", epoch=epoch, peers=lie)
            # orphan partition → refused
            orphan = {
                b.instance_id: {**peers[b.instance_id], "partitions": [2]}
            }
            with pytest.raises(RuntimeError, match="partition accounting"):
                ca.reduce_mesh("job", epoch=epoch, peers=orphan)
            # the real thing
            resp = ca.reduce_mesh(
                "job", epoch=epoch, peers=peers, drop_peers=True
            )
            assert resp["rows"] == 96 and resp["reduced"] == 1
            arrays = ca.finalize_pca("job", k=2)
            assert arrays["pc"].shape == (8, 2)
            # peer job dropped daemon-side (drop_peers)
            with pytest.raises(RuntimeError, match="no such job"):
                cb.status("job")


def test_reduce_mesh_replay_after_drop_peers_returns_cached_ack(rng, mesh8):
    """Replay safety (the client's lost-ack retry): a reduce that
    APPLIED — and dropped the peer jobs — must answer its replay from
    the dedupe memory, not re-validate against the now-gone peers (and
    not re-fold). Dedupe runs before the epoch fence too: a replay
    after unrelated membership churn still gets its cached ack."""
    x1 = _int_matrix(rng, 64, 8).astype(np.float32)
    x2 = _int_matrix(rng, 32, 8).astype(np.float32)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        with DataPlaneClient(*a.address) as ca, DataPlaneClient(*b.address) as cb:
            _feed_pca_job(ca, "job", x1, partition=0)
            _feed_pca_job(cb, "job", x2, partition=1)
            epoch = ca.mesh_info()["epoch"]
            req = {
                "op": "reduce_mesh", "job": "job", "epoch": epoch,
                "peers": {b.instance_id: {
                    "boot_id": b.boot_id, "rows": 32, "partitions": [1],
                }},
                "algo": "pca", "params": {}, "drop_peers": True,
                "reduce_id": "replay-fixed-1",
            }
            r1, _ = ca._op(dict(req))
            assert r1["rows"] == 96
            r2, _ = ca._op(dict(req))  # identical replay: cached, no refold
            assert r2["rows"] == 96 and r2["reduced"] == 1
            stale = {**req, "epoch": epoch - 1}
            r3, _ = ca._op(dict(stale))  # dedupe beats the epoch fence
            assert r3["rows"] == 96
            arrays = ca.finalize_pca("job", k=2)
            assert arrays["pc"].shape == (8, 2)


def test_unrelated_membership_churn_is_absorbed(rng, mesh8, monkeypatch):
    """The epoch fence is process-global: an UNRELATED daemon joining/
    leaving between the driver's mesh_info and its reduce must cost one
    retry, not the pass — the fit still reduces on the mesh and the
    model is unchanged."""
    x = _int_matrix(rng, 400, 8)
    real = DataPlaneClient.reduce_mesh
    state = {"churn": 0}

    def churny(self, jobname, **kw):
        if state["churn"] == 0:
            state["churn"] = 1
            DataPlaneDaemon(ttl=600.0).start().stop()  # epoch += 2
        return real(self, jobname, **kw)

    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        session, env_plan = _split_session(a, b)

        def fit():
            df = simdf_from_numpy(
                x, n_partitions=4, session=session, env_plan=env_plan
            )
            return SparkPCA().setInputCol("features").setK(3).fit(df)

        monkeypatch.setattr(DataPlaneClient, "reduce_mesh", churny)
        before = _counter_total(
            metrics_mod.snapshot(), "srml_daemon_mesh_reduces_total"
        )
        m_churned = fit()
        assert state["churn"] == 1, "the churn never fired"
        assert _counter_total(
            metrics_mod.snapshot(), "srml_daemon_mesh_reduces_total"
        ) > before, "the fit fell off the collective path"
        monkeypatch.setattr(DataPlaneClient, "reduce_mesh", real)
        m_clean = fit()
    np.testing.assert_array_equal(m_churned.pc, m_clean.pc)


def test_reduce_mesh_against_oracle(rng, mesh8):
    """The folded state equals the single-daemon accumulate of the
    union — the collective reduce is the identity the hub provides."""
    x1 = _int_matrix(rng, 48, 6).astype(np.float32)
    x2 = _int_matrix(rng, 80, 6).astype(np.float32)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        with DataPlaneClient(*a.address) as ca, DataPlaneClient(*b.address) as cb:
            _feed_pca_job(ca, "j", x1, partition=0)
            _feed_pca_job(cb, "j", x2, partition=1)
            epoch = ca.mesh_info()["epoch"]
            ca.reduce_mesh(
                "j", epoch=epoch,
                peers={b.instance_id: {
                    "boot_id": b.boot_id, "rows": 80, "partitions": [1],
                }},
                drop_peers=True,
            )
            merged = ca.finalize_pca("j", k=3)
        with DataPlaneDaemon(ttl=600.0) as solo:
            with DataPlaneClient(*solo.address) as cs:
                _feed_pca_job(cs, "j", x1, partition=0)
                _feed_pca_job(cs, "j", x2, partition=1)
                alone = cs.finalize_pca("j", k=3)
    np.testing.assert_array_equal(merged["pc"], alone["pc"])
    np.testing.assert_array_equal(
        merged["explained_variance"], alone["explained_variance"]
    )


# --------------------- flagship: estimator-level parity ----------------------


def test_two_daemon_pca_collective_vs_hub_bitwise(rng, mesh8):
    """THE parity contract: the on-mesh reduction and the driver-hub
    fallback produce bit-for-bit the same model on the same 2-daemon
    dataset — and the collective run really did reduce on the mesh
    (counter evidence), while the hub run really did not."""
    x = _int_matrix(rng, 400, 16)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        session, env_plan = _split_session(a, b)

        def fit():
            df = simdf_from_numpy(
                x, n_partitions=4, session=session, env_plan=env_plan
            )
            return SparkPCA().setInputCol("features").setK(4).fit(df)

        before = _counter_total(
            metrics_mod.snapshot(), "srml_daemon_mesh_reduces_total"
        )
        m_mesh = fit()
        mid = metrics_mod.snapshot()
        assert _counter_total(mid, "srml_daemon_mesh_reduces_total") > before, (
            "the collective path never engaged — this parity test proved "
            "nothing"
        )
        with config.option("mesh_collectives", False):
            m_hub = fit()
        after = metrics_mod.snapshot()
        assert _counter_total(
            after, "srml_daemon_mesh_reduces_total"
        ) == _counter_total(mid, "srml_daemon_mesh_reduces_total"), (
            "the hub run reduced on the mesh anyway"
        )
        assert _counter_total(
            after, "srml_fit_mesh_reduce_paths_total", path="hub"
        ) > 0
    np.testing.assert_array_equal(m_mesh.pc, m_hub.pc)
    np.testing.assert_array_equal(
        np.asarray(m_mesh.explainedVariance),
        np.asarray(m_hub.explainedVariance),
    )


def test_two_daemon_kmeans_collective_vs_hub_bitwise(rng, mesh8):
    """Iterative twin: every Lloyd pass reduces on the mesh (one
    reduce_mesh per pass), and the multi-pass model still matches the
    hub path bitwise."""
    x = _int_matrix(rng, 360, 8)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        session, env_plan = _split_session(a, b)
        # kmeans: every daemon must be seeded by the driver (the
        # documented addresses contract for iterative fits).
        session.conf.set(
            "spark.srml.daemon.addresses", f"{_addr(a)},{_addr(b)}"
        )

        def fit():
            df = simdf_from_numpy(
                x, n_partitions=4, session=session, env_plan=env_plan,
                concurrency=1,
            )
            return (
                SparkKMeans().setK(3).setFeaturesCol("features")
                .setMaxIter(4).setSeed(7).fit(df)
            )

        before = _counter_total(
            metrics_mod.snapshot(), "srml_daemon_mesh_reduces_total",
            algo="kmeans",
        )
        m_mesh = fit()
        assert _counter_total(
            metrics_mod.snapshot(), "srml_daemon_mesh_reduces_total",
            algo="kmeans",
        ) >= before + 2  # at least iterate passes + the final cost pass
        with config.option("mesh_collectives", False):
            m_hub = fit()
    np.testing.assert_array_equal(m_mesh.centers, m_hub.centers)
    assert m_mesh.summary.trainingCost == m_hub.summary.trainingCost
    assert m_mesh.summary.numIter == m_hub.summary.numIter


def test_estimator_reduce_guard_fails_loudly(rng, mesh8, monkeypatch):
    """The collective twin of the hub's export-shortfall guard: a peer
    whose live accounting disagrees with the task acks fails the fit
    with the row-count mismatch — never a silently wrong model."""
    orig = _Job.peek_pass_state

    def lying_peek(self):
        state, pass_rows, committed, iteration = orig(self)
        return state, pass_rows - 7, committed, iteration

    monkeypatch.setattr(_Job, "peek_pass_state", lying_peek)
    with DataPlaneDaemon(ttl=600.0) as a, DataPlaneDaemon(ttl=600.0) as b:
        session, env_plan = _split_session(a, b)
        df = simdf_from_numpy(_int_matrix(rng, 400, 8), n_partitions=4,
                              session=session, env_plan=env_plan)
        with pytest.raises(RuntimeError, match="row-count mismatch"):
            SparkPCA().setInputCol("features").setK(3).fit(df)


# ------------------- reboot mid-fit: epoch bump → replay ---------------------


def test_peer_reboot_mid_fit_replays_to_bitwise_model(
    rng, mesh8, monkeypatch
):
    """A VOLATILE peer daemon reboots at a pass boundary mid-kmeans:
    its membership re-registration bumps the epoch and mints a new
    boot_id, the driver's next boundary op fails, and — recovery
    enabled — the PR 4 ledger replays the pass; the final model is
    bitwise-equal to the uninterrupted fit and the collective path
    carried the replayed passes too."""
    k, d = 3, 5
    centers_true = rng.normal(size=(k, d)) * 8
    x = np.concatenate(
        [centers_true[i] + rng.normal(size=(90, d)) * 0.3 for i in range(k)]
    ).astype(np.float64)
    x = x[rng.permutation(len(x))]

    peer_port = _free_port()
    holder = {}

    def start_peer():
        holder["d"] = DataPlaneDaemon(
            host="127.0.0.1", port=peer_port, mesh=mesh8
        ).start()

    start_peer()
    with DataPlaneDaemon(ttl=600.0, mesh=mesh8) as primary:
        session = SimSparkSession({
            "spark.srml.daemon.address": _addr(primary),
            "spark.srml.daemon.addresses":
                f"{_addr(primary)},127.0.0.1:{peer_port}",
        })
        env_plan = {
            pid: {"SRML_DAEMON_ADDRESS": f"127.0.0.1:{peer_port}"}
            for pid in (2, 3)
        }

        def fit():
            df = simdf_from_numpy(
                x, n_partitions=4, session=session, env_plan=env_plan,
                concurrency=1,
            )
            return (
                SparkKMeans().setK(3).setFeaturesCol("features")
                .setMaxIter(4).setSeed(5).fit(df)
            )

        try:
            m_clean = fit()

            monkeypatch.setenv("SRML_FIT_RECOVERY_ATTEMPTS", "2")
            fired = {"n": 0}
            real_step = DataPlaneClient.step

            def step_then_reboot_peer(self, job, params=None):
                info = real_step(self, job, params=params)
                if fired["n"] == 0:
                    fired["n"] = 1
                    epoch_before = membership.registry().epoch
                    holder["d"].stop()  # pass-local partials die here
                    start_peer()
                    assert membership.registry().epoch >= epoch_before + 2
                return info

            monkeypatch.setattr(DataPlaneClient, "step", step_then_reboot_peer)
            rec_before = _counter_total(
                metrics_mod.snapshot(), "srml_fit_recoveries_total"
            )
            m_rec = fit()
            assert fired["n"] == 1, "the reboot never fired"
            assert _counter_total(
                metrics_mod.snapshot(), "srml_fit_recoveries_total"
            ) > rec_before, "the fit never recovered — it proved nothing"
        finally:
            holder["d"].stop()
    np.testing.assert_array_equal(m_clean.centers, m_rec.centers)
    assert m_clean.summary.trainingCost == m_rec.summary.trainingCost
    assert m_clean.summary.numIter == m_rec.summary.numIter


# ---------------- capacity: model-parallel Gram/eigh -------------------------


def test_gram_capacity_budget_small(monkeypatch, rng, mesh4x2, mesh1):
    """Budget semantics at a fast shape (budget shrunk): 1-device over
    budget raises; a model axis whose slab fits returns must-shard; a
    slab still over budget raises with the mesh hint."""
    monkeypatch.setattr(gram_ops, "GRAM_DEVICE_BUDGET_BYTES", 64 * 128 * 8)
    with pytest.raises(gram_ops.GramCapacityError, match="model"):
        gram_ops.require_gram_capacity(128, mesh1, accum_dtype="float64")
    assert gram_ops.require_gram_capacity(
        128, mesh4x2, accum_dtype="float64"
    ) is True
    assert gram_ops.require_gram_capacity(
        32, mesh1, accum_dtype="float64"
    ) is False
    with pytest.raises(gram_ops.GramCapacityError, match="mesh_model_axis"):
        gram_ops.require_gram_capacity(1024, mesh4x2, accum_dtype="float64")


def test_fit_pca_model_parallel_small_budget(monkeypatch, rng, mesh4x2, mesh1):
    """Same fit, shrunk budget: the 1-device path rejects, the 2-way
    model mesh fits, and the sharded result matches the unconstrained
    exact fit to solver tolerance."""
    from spark_rapids_ml_tpu.models.pca import fit_pca

    d = 128
    scale = np.exp(-np.arange(d) / 8.0)
    x = (rng.standard_normal((1024, d)) * scale).astype(np.float64)
    ref = fit_pca(x, k=3, mesh=mesh1)  # unconstrained oracle
    monkeypatch.setattr(gram_ops, "GRAM_DEVICE_BUDGET_BYTES", 64 * 128 * 8)
    with pytest.raises(gram_ops.GramCapacityError):
        fit_pca(x, k=3, mesh=mesh1)
    sol = fit_pca(x, k=3, mesh=mesh4x2, solver="randomized")
    dots = np.abs(np.sum(sol.pc * ref.pc, axis=0))
    assert np.all(dots > 1 - 1e-6), dots


def test_daemon_job_over_budget_refuses_at_creation(monkeypatch, rng, mesh8):
    """The Spark fit path's gate: a daemon job whose replicated (d, d)
    accumulator busts the budget refuses at the FIRST FEED with the
    capacity error — never an opaque device OOM mid-pass."""
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    monkeypatch.setattr(gram_ops, "GRAM_DEVICE_BUDGET_BYTES", 64 * 64 * 8)
    x = rng.standard_normal((16, 128)).astype(np.float32)
    with DataPlaneDaemon(ttl=600.0) as d:
        with DataPlaneClient(*d.address) as c:
            t = pa.table({"features": matrix_to_list_column(x)})
            with pytest.raises(RuntimeError, match="budget"):
                c.feed("big", t, algo="pca", input_col="features",
                       partition=0, attempt=0)
            # under-budget widths are untouched
            t2 = pa.table({"features": matrix_to_list_column(x[:, :32])})
            c.feed("ok", t2, algo="pca", input_col="features",
                   partition=0, attempt=0)
            c.commit("ok", partition=0, attempt=0)


def test_fit_pca_stream_over_budget_raises(monkeypatch, mesh8):
    from spark_rapids_ml_tpu.models.pca import fit_pca_stream

    monkeypatch.setattr(gram_ops, "GRAM_DEVICE_BUDGET_BYTES", 64 * 64 * 8)
    with pytest.raises(gram_ops.GramCapacityError, match="budget"):
        fit_pca_stream(iter([np.zeros((8, 128))]), k=2, n_cols=128)


def test_fit_pca_d8192_model_parallel_succeeds_where_single_device_raises(
    rng, devices
):
    """The acceptance shape (docs/mesh.md): at d=8192 the float64 (d, d)
    accumulator is 512 MiB — over the 256 MiB default per-device budget
    — so the single-device fit refuses, and the 8-way model-parallel
    Gram/eigh carries it (64 MiB slab/device), returning a finite,
    oracle-aligned top component."""
    from spark_rapids_ml_tpu.models.pca import fit_pca

    d, n, k = 8192, 256, 4
    scale = np.exp(-np.arange(d) / 64.0) + 1e-3
    x = (rng.standard_normal((n, d)) * scale).astype(np.float64)
    m1 = make_mesh(data=1, model=1, devices=devices[:1])
    with pytest.raises(gram_ops.GramCapacityError):
        fit_pca(x, k=k, mesh=m1, solver="randomized")
    m8 = make_mesh(data=1, model=8, devices=devices)
    sol = fit_pca(x, k=k, mesh=m8, solver="randomized")
    assert sol.pc.shape == (d, k) and np.all(np.isfinite(sol.pc))
    xc = x - x.mean(axis=0)
    w, v = np.linalg.eigh(xc.T @ xc)
    ref = v[:, ::-1][:, :1]
    assert abs(float(np.sum(sol.pc[:, :1] * ref))) > 0.99


# --------------------------- satellites --------------------------------------


def test_warmup_on_register_precompiles_ladder(rng):
    """With serve_warmup_on_register on (and batching on), registration
    itself warms the reachable ladder: an explicit warmup afterwards
    compiles NOTHING new. Control: without the flag, the explicit
    warmup is the first to compile."""
    from spark_rapids_ml_tpu.models.pca import PCA

    d = 16
    x = rng.standard_normal((256, d)).astype(np.float32)
    model = PCA().setK(3).fit({"features": x})
    arrays = model._model_data()

    with config.option("serve_batching", True):
        with config.option("serve_warmup_on_register", True):
            with DataPlaneDaemon() as daemon:
                with DataPlaneClient(*daemon.address) as c:
                    assert c.ensure_model("warm", "pca", arrays) is True
                    info = c.warmup("warm", n_cols=d)
                    assert info["enabled"] is True
                    assert info["compiled"] == 0, (
                        "registration should have pre-compiled the ladder"
                    )
        with config.option("serve_warmup_on_register", False):
            with DataPlaneDaemon() as daemon:
                with DataPlaneClient(*daemon.address) as c:
                    assert c.ensure_model("cold", "pca", arrays) is True
                    info = c.warmup("cold", n_cols=d)
                    assert info["compiled"] > 0


def test_warmup_on_register_covers_daemon_built_knn(rng):
    """The kneighbors half of the contract: a daemon-built KNN index
    shard (finalize_knn registration — KNN never rides ensure_model)
    pre-compiles its ladder at registration too; the explicit warmup
    afterwards finds nothing left to compile."""
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    x = rng.standard_normal((256, 16)).astype(np.float32)
    with config.option("serve_batching", True):
        with config.option("serve_warmup_on_register", True):
            with DataPlaneDaemon() as d:
                with DataPlaneClient(*d.address) as c:
                    t = pa.table({"features": matrix_to_list_column(x)})
                    c.feed("kj", t, algo="knn", input_col="features",
                           partition=0, attempt=0)
                    c.commit("kj", partition=0, attempt=0)
                    c.finalize_knn("kj", register_as="kidx", mode="exact")
                    info = c.warmup("kidx", n_cols=16)
                    assert info["enabled"] is True
                    assert info["compiled"] == 0, (
                        "the knn registration should have pre-compiled "
                        "the kneighbors ladder"
                    )


def test_warmup_on_register_noop_without_batching(rng):
    """Batching off: the flag must not break registration (nor start a
    scheduler)."""
    from spark_rapids_ml_tpu.models.pca import PCA

    x = rng.standard_normal((64, 8)).astype(np.float32)
    arrays = PCA().setK(2).fit({"features": x})._model_data()
    with config.option("serve_warmup_on_register", True):
        with DataPlaneDaemon() as daemon:
            with DataPlaneClient(*daemon.address) as c:
                assert c.ensure_model("m", "pca", arrays) is True
                out = c.transform("m", x[:4])
                assert out["output"].shape == (4, 2)


@pytest.mark.slow
def test_compile_cache_placed_from_outside_counts_hits(tmp_path):
    """The one rule (utils/compile_cache.py): with JAX_COMPILATION_CACHE_DIR
    set, nothing is set in code — JAX reads the variable itself — and a
    second process compiling the same program reads the disk cache, counted
    by srml_xla_persistent_cache_hits_total."""
    cache = str(tmp_path / "xla-cache")
    prog = (
        "import os\n"
        "import jax, jax.numpy as jnp\n"
        "import spark_rapids_ml_tpu as s\n"
        "from spark_rapids_ml_tpu.utils import xprof, metrics\n"
        "from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache\n"
        "assert ensure_compile_cache() == os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "f = xprof.ledgered_jit('test.cache_probe', lambda x: jnp.sin(x) @ x)\n"
        "import numpy as np\n"
        "print(float(np.asarray(f(jnp.ones((64, 64)))).sum()))\n"
        "snap = metrics.snapshot()\n"
        "hits = sum(s['value'] for s in snap.get("
        "'srml_xla_persistent_cache_hits_total', {}).get('samples', []))\n"
        "print('HITS', hits)\n"
    )
    env = dict(os.environ)
    env.update({
        "JAX_COMPILATION_CACHE_DIR": cache,
        "JAX_PLATFORMS": "cpu",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
    })
    first = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert first.returncode == 0, first.stderr[-2000:]
    assert os.path.isdir(cache) and os.listdir(cache), (
        "first process wrote nothing to the cache dir"
    )
    second = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert second.returncode == 0, second.stderr[-2000:]
    hits = int(float(second.stdout.strip().splitlines()[-1].split()[-1]))
    assert hits >= 1, second.stdout


# ------------------------- perfcheck: multichip ------------------------------


def _mc_record(eff=0.9, metric="multichip_fit_rows_per_sec_d512_k16", **kw):
    rec = {
        "metric": metric, "value": 100000.0, "unit": "rows/s",
        "n_devices": 8, "simulated": True, "dryrun": False,
        "scaling_efficiency": eff,
        "xla": {"warmup": {}, "steady": {"f": {"compiles": 0}}},
    }
    rec.update(kw)
    return rec


def test_perfcheck_multichip_dryrun_is_skip_not_pass():
    from spark_rapids_ml_tpu.tools import perfcheck

    ok, lines = perfcheck.check_multichip(
        {"n_devices": 8, "rc": 0, "ok": True, "tail": "dryrun OK"}, []
    )
    assert ok is True
    assert any("SKIP" in ln and "NOT a pass" in ln for ln in lines)


def test_perfcheck_multichip_efficiency_floor():
    from spark_rapids_ml_tpu.tools import perfcheck

    ok, lines = perfcheck.check_multichip(_mc_record(eff=0.79), [])
    assert ok is False
    assert any("REGRESSION" in ln for ln in lines)
    ok, _ = perfcheck.check_multichip(_mc_record(eff=0.81), [])
    assert ok is True


def test_perfcheck_multichip_trajectory_ratchets_the_floor():
    from spark_rapids_ml_tpu.tools import perfcheck

    history = [_mc_record(eff=1.2), _mc_record(eff=1.3), _mc_record(eff=1.25)]
    # 0.85 clears the absolute floor but is >15% below median 1.25.
    ok, lines = perfcheck.check_multichip(_mc_record(eff=0.85), history)
    assert ok is False
    ok, _ = perfcheck.check_multichip(_mc_record(eff=1.15), history)
    assert ok is True
    # dryrun history records are excluded, not fatal
    ok, lines = perfcheck.check_multichip(
        _mc_record(eff=0.9), [{"tail": "dryrun", "n_devices": 8}]
    )
    assert ok is True
    assert any("dryrun history" in ln for ln in lines)


def test_perfcheck_multichip_allow_compile_hatch_works():
    """The escape hatch the failure message advertises must actually
    unblock the gate — with the mesh-prefixed name it prints."""
    from spark_rapids_ml_tpu.tools import perfcheck

    rec = _mc_record(
        xla={"warmup": {}, "steady": {"8dev:gram.f": {"compiles": 2}}}
    )
    ok, lines = perfcheck.check_multichip(rec, [])
    assert ok is False and any("8dev:gram.f" in ln for ln in lines)
    ok, _ = perfcheck.check_multichip(rec, [], allow_compiles=("8dev:gram.f",))
    assert ok is True


def test_perfcheck_multichip_real_vs_simulated_do_not_mix():
    from spark_rapids_ml_tpu.tools import perfcheck

    history = [_mc_record(eff=1.4)]  # simulated trajectory
    ok, _ = perfcheck.check_multichip(
        _mc_record(eff=0.85, simulated=False), history
    )
    assert ok is True  # real-pod 0.85 gates on the absolute floor only


def test_perfcheck_non_dict_input_exits_gracefully(tmp_path, capsys):
    """A JSON array (a history file) or bare scalar piped in must get
    the graceful 'no JSON record' exit, not a traceback."""
    from spark_rapids_ml_tpu.tools import perfcheck

    for content in ("[]", "42", '[{"metric": "x"}]'):
        p = tmp_path / "notarecord.json"
        p.write_text(content)
        assert perfcheck.main([str(p)]) == 2
        assert "no JSON record" in capsys.readouterr().err


@pytest.mark.perf
@pytest.mark.slow
def test_multichip_bench_smoke(tmp_path):
    """bench.py --multichip end to end at toy shapes: the record carries
    a real (non-dryrun) scaling number, per-phase timing including the
    raw all-reduce microphase, and a steady ledger the storm gate can
    read."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SRML_BENCH_MULTICHIP_DEVICES": "4",
        "SRML_BENCH_MULTICHIP_D": "64",
        "SRML_BENCH_MULTICHIP_BATCH_ROWS": "2048",
        "SRML_BENCH_MULTICHIP_BATCHES": "4",
        "SRML_BENCH_MULTICHIP_KMEANS_K": "4",
        "SRML_BENCH_MULTICHIP_KMEANS_PASSES": "2",
    })
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "bench.py", "--multichip"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["dryrun"] is False
    assert rec["n_devices"] == 4
    assert rec["scaling_efficiency"] > 0
    for side in ("one_device", "n_device"):
        phases = rec[side]["phases"]
        for phase in ("pca_fold", "pca_finalize", "kmeans_fold",
                      "allreduce_dxd"):
            assert phases[phase] >= 0
    assert isinstance(rec["xla"]["steady"], dict) and rec["xla"]["steady"]
