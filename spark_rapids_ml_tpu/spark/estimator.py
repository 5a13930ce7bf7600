"""PySpark DataFrame adapters for the core estimators.

The reference's user contract: change one import, keep the Spark ML code
(`new com.nvidia.spark.ml.feature.PCA().setInputCol(...).fit(df)`,
reference PCA.scala:27-37, README.md:27-37 — with the features column as
ArrayType rather than Vector). These wrappers reproduce that contract for
PySpark: ``SparkPCA().setInputCol("features").setK(3).fit(spark_df)``.

**fit is distributed**, reproducing the reference's defining property —
per-partition work on executors with only O(d²) partials crossing the
wire (RapidsRowMatrix.scala:118-139). Each partition task streams its
Arrow batches to the TPU-host data-plane daemon (``serve/``) and commits;
the driver finalizes and receives only the model. The dataset is NEVER
collected to the driver. Iterative algorithms (KMeans/LogReg) run one
Spark job per pass with a daemon ``step`` at each boundary — the Lloyd /
Newton scan loop with Spark as the scan engine. Task retries and
speculative duplicates are safe: feeds stage per (partition, attempt) and
only ``commit`` folds them in (see serve/daemon.py).

``transform`` runs the model on Arrow batches via ``mapInArrow`` (one
batch per executor task — the analogue of the reference's columnar UDF,
RapidsPCA.scala:128-161), falling back to a collect-based path for old
PySpark.

pyspark is optional: import of this module never requires it; calling
``fit``/``transform`` with a Spark DataFrame does. KNN/ANN fits stream
rows to the daemon(s) like everything else; with multiple daemons the
index is built and served as PER-DAEMON SHARDS with fan-out/merge
queries (``_fit_knn``) — nothing ever collects to the driver.
"""

from __future__ import annotations

import uuid
from typing import Any, Optional

import numpy as np

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.spark import daemon_session
from spark_rapids_ml_tpu.utils import faults
from spark_rapids_ml_tpu.utils import journal
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils.logging import get_logger
from spark_rapids_ml_tpu.utils.profiling import trace_span

logger = get_logger("spark.estimator")

#: Crash-recovery telemetry (docs/observability.md). Recoveries are the
#: pass replays the fit performed after a daemon incarnation change or a
#: poisoned pass; drop errors are cleanup drops that failed — each one is
#: a daemon job leaked until the TTL reaper finds it.
_M_FIT_RECOVERIES = metrics_mod.counter(
    "srml_fit_recoveries_total",
    "Fit passes replayed after a daemon incarnation change or poisoned "
    "pass, by algo",
)
_M_DROP_ERRORS = metrics_mod.counter(
    "srml_client_drop_errors_total",
    "Cleanup drop() calls that failed (the daemon job leaks until its "
    "TTL), by stage",
)
_M_MESH_PATHS = metrics_mod.counter(
    "srml_fit_mesh_reduce_paths_total",
    "Multi-daemon pass reductions by path (collective = on-mesh "
    "reduce_mesh; hub = driver-mediated export/merge fallback)",
)
_M_DAEMON_LOSSES = metrics_mod.counter(
    "srml_fit_daemon_losses_total",
    "Peer daemons declared permanently dead and quarantined by an "
    "elastic fit (fit_daemon_loss_tolerance > 0; docs/protocol.md "
    "'Permanent daemon loss'), by algo",
)
_M_FIT_REROUTES = metrics_mod.counter(
    "srml_fit_reroutes_total",
    "Feed passes rerun on the shrunken topology after a daemon loss — "
    "the dead daemon's partitions reroute to survivors, by algo",
)
_M_FIT_JOINS = metrics_mod.counter(
    "srml_fit_joins_total",
    "Daemons admitted into a RUNNING fit at a pass boundary "
    "(fit_daemon_join_policy=boundary; docs/protocol.md 'Mid-fit "
    "daemon join'), by algo",
)
_M_FIT_REBALANCED = metrics_mod.counter(
    "srml_fit_rebalanced_rows_total",
    "Rows the task layer rebalanced onto mid-fit joiners on their "
    "first acked pass after admission, by algo",
)


def _drop_quietly(client, job: str, stage: str) -> None:
    """Cleanup drop that cannot mask the fit's outcome — but is COUNTED
    and logged: a silently swallowed failure here leaks a daemon job
    (d×d device buffers, or a dataset-sized knn stage) invisibly until
    the TTL reaper hides the evidence."""
    try:
        client.drop(job)
    except Exception as e:
        _M_DROP_ERRORS.inc(stage=stage)
        logger.debug(
            "cleanup drop of job %r failed (%s); the daemon holds it "
            "until its TTL: %s", job, stage, e,
        )


def _pyspark():
    try:
        import pyspark  # noqa: F401
        from pyspark.sql import DataFrame

        return DataFrame
    except ImportError:
        return None


# Extra DataFrame types treated as Spark-shaped (duck-typed stand-ins that
# implement the same surface — the test harness's SimDataFrame registers
# here so the REAL wrapper code paths run without a pyspark install).
_EXTRA_DF_TYPES: tuple = ()


def register_dataframe_type(cls) -> None:
    global _EXTRA_DF_TYPES
    _EXTRA_DF_TYPES = tuple(set(_EXTRA_DF_TYPES) | {cls})


def _is_spark_df(dataset: Any) -> bool:
    if _EXTRA_DF_TYPES and isinstance(dataset, _EXTRA_DF_TYPES):
        return True
    df_cls = _pyspark()
    return df_cls is not None and isinstance(dataset, df_cls)


def _check_not_orphan_spark_df(dataset: Any) -> None:
    """Raise the promised clear error for Spark-shaped datasets when
    pyspark is missing (instead of an opaque core-estimator failure)."""
    if _pyspark() is None and (
        hasattr(dataset, "sparkSession")
        or type(dataset).__module__.split(".")[0] == "pyspark"
    ):
        raise ImportError(
            "pyspark is not installed; Spark* estimators need it for "
            "DataFrame inputs. Use the core estimators "
            "(spark_rapids_ml_tpu.PCA etc.) with arrow/pandas/numpy data."
        )


def _df_to_arrow(df, columns):
    """Spark DataFrame -> pyarrow.Table restricted to ``columns``."""
    import pyarrow as pa

    selected = df.select(*columns)
    # Spark 4 / recent 3.x: native Arrow collect.
    if hasattr(selected, "toArrow"):
        return selected.toArrow()
    pdf = selected.toPandas()
    return pa.Table.from_pandas(pdf, preserve_index=False)


# Executor-side cache: daemon instance id per (fit job, host, port).
# Scoping by JOB makes the cache safe under Spark python-worker reuse:
# a daemon restarted BETWEEN fits gets a fresh ping on the next fit
# (a stale id would make the driver treat the same daemon as a peer and
# fail spuriously), while within one fit — where a restart loses the
# job state and fails the fit anyway — passes and tasks share one ping.
_DAEMON_ID_CACHE: dict = {}


def _evict_daemon_id_cache(job: str, addr: Optional[str] = None,
                           prefix: bool = False) -> None:
    """Drop this fit's id-cache routes from THIS PROCESS's cache (all of
    them on fit exit; only a quarantined daemon's on amputation). The
    entries are job-scoped, so without the fit-exit sweep a long-lived
    driver-process deployment (tasks running in the driver's
    interpreter) leaks one per (fit, daemon) and a RECYCLED job name
    could inherit a stale daemon id from the fit that used the name
    before. Each process owns its own copy: the eviction that matters on
    real executors (reused Spark python workers) rides the replayed
    task itself — ``_FeedTask.evict_routes``. ``prefix`` sweeps every
    job under a uid prefix (the KNN fit shell, which exits outside the
    scope that minted the exact job name)."""
    if addr is not None:
        try:
            host, port = daemon_session._parse_addr(addr)
        except ValueError:
            return
        _DAEMON_ID_CACHE.pop((job, host, port), None)
        return
    match = (
        (lambda k: str(k[0]).startswith(job)) if prefix
        else (lambda k: k[0] == job)
    )
    for key in [k for k in _DAEMON_ID_CACHE if match(k)]:
        _DAEMON_ID_CACHE.pop(key, None)


class _FeedTask:
    """The executor-side partition feeder (a plain-pickle-able callable —
    shipped to tasks by Spark's closure serializer; imports happen on the
    executor).

    One task = one partition = one daemon connection: stream every Arrow
    batch to the stage keyed (partition, attempt), then commit. Retries
    restart the stage; duplicates of committed partitions are discarded
    daemon-side — Spark's at-least-once task execution becomes
    exactly-once accumulation (see serve/daemon.py)."""

    def __init__(self, host, port, token, job, algo, input_col, label_col,
                 params, pass_id, evict_routes=(), want_cache=False):
        self.host, self.port, self.token = host, port, token
        # Pass cache (docs/protocol.md "rescan"): the ack gains a column,
        # whether the daemon's commit ack said it holds every row of this
        # pass so far. Off (the default): the ack is the parent's.
        self.want_cache = bool(want_cache)
        self.job, self.algo = job, algo
        self.input_col, self.label_col = input_col, label_col
        self.params, self.pass_id = params, pass_id
        # Quarantined-daemon addresses (elastic degrade): evicted from
        # the EXECUTOR-side id cache at task start — the cache lives in
        # reused Spark python workers, where the driver's own eviction
        # cannot reach; a replacement daemon at the dead address must be
        # re-pinged, not answered from the ghost's cached id.
        self.evict_routes = tuple(evict_routes)
        # Distributed tracing: the driver's journal frame at task
        # construction rides the closure to the executor, whose client
        # stamps it on every wire op — the daemon's spans then parent
        # into THIS fit's run even though the executor process never
        # opened it (docs/protocol.md "trace_ctx").
        self.trace_ctx = journal.trace_ctx()

    def __call__(self, batches):
        import pyarrow as pa

        from spark_rapids_ml_tpu.serve.client import DataPlaneClient
        from spark_rapids_ml_tpu.spark import daemon_session as ds

        pid, attempt = ds.task_context()
        h, p = ds.executor_daemon_address(self.host, self.port)
        for bad in self.evict_routes:
            # Executor-side quarantine eviction (see __init__): runs in
            # the worker process that actually OWNS the cache.
            try:
                bh, bp = ds._parse_addr(bad)
            except ValueError:
                continue
            _DAEMON_ID_CACHE.pop((self.job, bh, bp), None)
        rows = 0
        # client_kwargs(): executor-env resilience tuning — per-op healing
        # deadline, socket timeout — so a daemon hiccup or busy-shed is
        # absorbed by the client before it ever costs a Spark task retry.
        with DataPlaneClient(h, p, token=self.token,
                             trace_ctx=self.trace_ctx,
                             **ds.client_kwargs()) as c:
            # The daemon's self-reported identity: the driver keys its
            # merge/reconcile on this, never on the address spelling (an
            # alias of the primary must not look like a peer).
            daemon_id = _DAEMON_ID_CACHE.get((self.job, h, p))
            if daemon_id is None:
                daemon_id = c.server_id() or f"{h}:{p}"
                if len(_DAEMON_ID_CACHE) > 256:  # bound worker-reuse growth
                    _DAEMON_ID_CACHE.clear()
                _DAEMON_ID_CACHE[(self.job, h, p)] = daemon_id
            for batch in batches:
                if batch.num_rows == 0:
                    continue
                c.feed(
                    self.job,
                    pa.Table.from_batches([batch]),
                    algo=self.algo,
                    input_col=self.input_col,
                    label_col=self.label_col,
                    params=self.params,
                    partition=pid,
                    attempt=attempt,
                    pass_id=self.pass_id,
                )
                rows += batch.num_rows
            cached = True  # nothing committed: nothing the cache lacks
            if rows > 0:
                _, meta = c.commit(
                    self.job, partition=pid, attempt=attempt,
                    pass_id=self.pass_id, with_meta=True,
                )
                cached = bool(meta.get("cached", False))
            if c.last_server_id and c.last_server_id != daemon_id:
                # The daemon ANSWERED with a different identity than the
                # cached ping: it restarted (volatile, new instance id)
                # under this reused worker. The ack must name who really
                # holds the rows, and later tasks must not keep
                # reporting the ghost id.
                daemon_id = c.last_server_id
                _DAEMON_ID_CACHE[(self.job, h, p)] = daemon_id
        # The ack names the daemon this task actually fed (id + a
        # reachable address): the driver merges partials from exactly
        # this set and reconciles the row counts — no daemon's rows can
        # be silently dropped. `boots` carries every daemon INCARNATION
        # the task's acks came from: two boots in one pass means the
        # daemon restarted under the scan and rows acked to the dead
        # incarnation are gone — the driver's fence (docs/protocol.md
        # "Crash recovery").
        ack = {
            "partition": pa.array([pid], pa.int32()),
            "rows": pa.array([rows], pa.int64()),
            "daemon": pa.array([f"{h}:{p}"], pa.string()),
            "daemon_id": pa.array([daemon_id], pa.string()),
            "boots": pa.array(
                [",".join(sorted(c.seen_boot_ids))], pa.string()
            ),
        }
        if self.want_cache:
            ack["cached"] = pa.array([cached], pa.bool_())
        yield pa.RecordBatch.from_pydict(ack)


class _LabelMaxTask:
    """O(1)-result label scan: each task reports its partitions' max
    label. One tiny Spark job, like the reference's numCols probe
    (RapidsPCA.scala:73-74) — how the driver learns n_classes without
    collecting labels."""

    def __init__(self, label_col):
        self._label = label_col

    def __call__(self, batches):
        import numpy as np
        import pyarrow as pa

        mx = -1.0
        for batch in batches:
            if batch.num_rows:
                arr = np.asarray(
                    pa.Table.from_batches([batch])
                    .column(self._label)
                    .to_numpy(zero_copy_only=False)
                )
                if arr.size:
                    mx = max(mx, float(np.max(arr)))
        yield pa.RecordBatch.from_pydict({"maxlabel": pa.array([mx], pa.float64())})


def _probe_num_classes(df, label_col) -> int:
    acks = df.select(label_col).mapInArrow(
        _LabelMaxTask(label_col), "maxlabel double"
    ).collect()
    mx = max((float(r["maxlabel"]) for r in acks), default=-1.0)
    return max(int(mx) + 1, 2)


def _ack_rows(acks):
    """(total rows, rows by daemon id, id → reachable address, partition →
    winning daemon id, daemon id → boot incarnations observed) from one
    feed pass's task acks. Daemons are keyed by their self-reported
    instance id — address spellings alias."""
    per: dict = {}
    addr_of: dict = {}
    owner: dict = {}
    boots: dict = {}
    for r in acks:
        did = r["daemon_id"]
        per[did] = per.get(did, 0) + int(r["rows"])
        addr_of.setdefault(did, r["daemon"])
        if int(r["rows"]) > 0:
            owner[int(r["partition"])] = did
        bs = boots.setdefault(did, set())
        for b in str(r["boots"] or "").split(","):
            if b:
                bs.add(b)
    return sum(per.values()), per, addr_of, owner, boots


def _incarnation_change(addr: str, boots) -> RuntimeError:
    """The fence: a pass whose acks span two incarnations of one daemon
    fed SOME rows to a state that died with the old incarnation — the
    acked row count is poisoned and must not be trusted (or silently
    reconciled). With recovery enabled the estimator replays the pass
    from the last boundary; otherwise this failure IS the answer."""
    return RuntimeError(
        f"daemon {addr} restarted mid-pass (incarnations "
        f"{sorted(boots)}): rows acked to the dead incarnation are gone "
        "from the accumulator while the tasks still count them. Enable "
        "fit recovery (SRML_FIT_RECOVERY_ATTEMPTS / "
        "spark.srml.fit.recovery_attempts) to replay the pass from the "
        "last boundary, or refit."
    )


def _split_brain(context: str, expected: int, got: int, detail: str) -> RuntimeError:
    """The loud failure the multi-daemon plane promises: committed rows
    and task-acked rows MUST reconcile — a mismatch means the model would
    silently miss (or double-count) data, and the fit must fail instead
    of returning it."""
    if got > expected:
        hint = (
            "the daemon holds MORE rows than this fit's winning task acks "
            "— a task likely committed here, lost its ack, and was re-run "
            "against a different daemon (cross-daemon retry), or rows were "
            "fed outside this fit. Keep executor→daemon routing sticky "
            "across retries (host-local daemons + Spark locality)."
        )
    else:
        hint = (
            "the daemon holds FEWER rows than tasks acked — its job was "
            "TTL-evicted or recreated mid-fit. Raise the daemon ttl "
            "relative to fit duration."
        )
    return RuntimeError(
        f"daemon row-count mismatch at {context}: tasks acked {expected} "
        f"rows ({detail}) but the daemon plane accounts {got}; {hint} "
        "Refit after fixing the cause."
    )


def _reduce_on_mesh(
    client, job, primary_id, per_daemon, addr_of, owner, boots,
    wire_algo, feed_params, drop_peer, cache,
):
    """Collective-first pass reduction (docs/mesh.md): when the primary
    and every row-holding peer are co-resident members of one mesh (one
    JAX runtime — multichip single-host daemons, or a multi-host
    jax.distributed plane), ONE ``reduce_mesh`` op folds all peer
    partials on the device plane and the O(d²) arrays never cross the
    wire. Returns True when the pass is reduced (or there was nothing to
    reduce); False hands the pass to the export/merge hub
    (:func:`_merge_peer_daemons`) — the degraded mode for daemons on
    separate runtimes or predating the op.

    The split-brain row accounting does not weaken on this path: the
    driver ships its task-ack view (rows + owned partitions per peer)
    and the daemon re-validates it against every peer's live
    ``(boot_id, pass_rows)`` in a pre-reduce gather, refusing the whole
    fold on any mismatch or on a membership-epoch change. A co-resident
    peer that REBOOTED since the scan acked raises the incarnation
    fence here — recovery (when enabled) replays the pass."""
    peer_rows = {
        d: n for d, n in per_daemon.items() if d != primary_id and n > 0
    }
    if not peer_rows:
        return True  # single-daemon pass: nothing to reduce on any path
    if "hub_only" not in cache:
        cache["hub_only"] = not bool(config.get("mesh_collectives"))
    if cache["hub_only"]:
        _M_MESH_PATHS.inc(path="hub")
        return False
    # Two attempts: the daemon's epoch fence is process-global, so an
    # UNRELATED daemon joining/leaving between our mesh_info and the
    # reduce refuses it spuriously — one re-read revalidates every
    # actual participant against the fresh epoch. A second mismatch
    # (sustained churn) surfaces; recovery treats it like any daemon
    # failure.
    for attempt in range(2):
        try:
            info = client.mesh_info()
        except Exception as e:
            logger.debug(
                "mesh_info unavailable on the primary (%s); this fit uses "
                "the driver-hub merge", e,
            )
            cache["hub_only"] = True
            _M_MESH_PATHS.inc(path="hub")
            return False
        members = {
            str(m["id"]): str(m["boot_id"]) for m in info.get("members", [])
        }
        if primary_id not in members:
            _M_MESH_PATHS.inc(path="hub")
            return False
        for did in sorted(peer_rows):
            if did not in members:
                # A genuinely remote daemon (its runtime is not this
                # mesh): the hub is the correct path, not a failure.
                _M_MESH_PATHS.inc(path="hub")
                return False
            ack_boot = next(iter(boots.get(did) or []), None)
            if ack_boot is not None and members[did] != ack_boot:
                raise _incarnation_change(
                    addr_of.get(did, did), {ack_boot, members[did]}
                )
        peers = {
            did: {
                "boot_id": members[did],
                "rows": int(n),
                "partitions": sorted(
                    int(p) for p, d in owner.items() if d == did
                ),
            }
            for did, n in peer_rows.items()
        }
        try:
            client.reduce_mesh(
                job, epoch=int(info["epoch"]), peers=peers, algo=wire_algo,
                params=feed_params, drop_peers=drop_peer,
            )
        except RuntimeError as e:
            if attempt == 0 and "membership changed" in str(e):
                continue
            raise
        _M_MESH_PATHS.inc(path="collective")
        return True


def _merge_peer_daemons(
    client, job, primary_id, per_daemon, addr_of, owner, get_peer,
    wire_algo, feed_params, drop_peer,
):
    """Pull every peer daemon's committed partials into the primary — the
    cross-daemon reduce (the any-number-of-executors ``RDD.reduce``,
    reference RapidsRowMatrix.scala:139, with daemons as leaves). Each
    peer's export is reconciled against what its tasks acked BEFORE it is
    folded — per partition, so a cross-daemon retry orphan or a lost
    partition is named precisely — and a short/overfull peer fails the
    fit instead of corrupting it."""
    for did, fed in sorted(per_daemon.items()):
        if did == primary_id or fed == 0:
            continue
        addr = addr_of[did]
        peer = get_peer(did, addr)
        arrays, meta = peer.export_state(job)
        if drop_peer:
            peer.drop(job)
        committed = {int(p): int(n) for p, n in (meta.get("committed") or {}).items()}
        owned = {p for p, d in owner.items() if d == did}
        orphans = sorted(p for p in committed if p not in owned)
        lost = sorted(p for p in owned if p not in committed)
        if int(meta["pass_rows"]) != fed or orphans or lost:
            parts = []
            if orphans:
                parts.append(
                    f"partitions {orphans} committed here but acked on "
                    "another daemon (cross-daemon retry orphans)"
                )
            if lost:
                parts.append(f"partitions {lost} acked here but not committed")
            raise _split_brain(
                f"peer daemon {addr} export", fed, int(meta["pass_rows"]),
                "; ".join(parts) or f"{addr}={fed}",
            )
        client.merge_state(
            job, arrays, rows=int(meta["pass_rows"]), algo=wire_algo,
            n_cols=int(meta["n_cols"]), params=feed_params,
        )


class _SparkAdapter:
    """Wraps a core estimator class with Spark DataFrame in/out.

    Non-Spark datasets pass straight through to the core estimator, so the
    Spark wrapper is a superset of the core API.
    """

    _core_cls = None  # override
    _model_attr = "model"
    # Daemon wire protocol this estimator's fit speaks; None → Arrow
    # collect (KNN: the fitted model IS the dataset; scaler: trivial).
    _daemon_algo: Optional[str] = None

    def __init__(self, **kwargs):
        self._core = type(self)._core_cls(**kwargs)

    def __getattr__(self, name):
        # Fluent setters return self (the wrapper), others pass through.
        attr = getattr(self._core, name)
        if callable(attr) and name.startswith("set"):
            def fluent(*a, **kw):
                attr(*a, **kw)
                return self

            return fluent
        return attr

    def fit(self, dataset):
        if _is_spark_df(dataset):
            if self._daemon_algo == "knn":
                return self._fit_knn(dataset)
            if self._daemon_algo is None:
                # Never collect a DataFrame to the driver to fit — every
                # shipped estimator speaks a daemon protocol; a custom
                # wrapper without one must opt into the core API.
                raise NotImplementedError(
                    f"{type(self).__name__} has no daemon fit protocol; "
                    "use the core estimator with in-memory data"
                )
            core_model = self._fit_distributed(dataset)
        else:
            _check_not_orphan_spark_df(dataset)
            core_model = self._core.fit(dataset)
        return _SparkModelAdapter(core_model)

    def _fit_knn(self, df):
        """Journal-wrapped shell — see :meth:`_fit_knn_inner`."""
        with journal.run(
            "fit", estimator=type(self).__name__, algo="knn",
            uid=self._core.uid,
        ):
            try:
                return self._fit_knn_inner(df)
            finally:
                # This fit's job is f"{uid}-{hex}" — sweep by prefix
                # (the exact name is minted inside the inner scope).
                _evict_daemon_id_cache(f"{self._core.uid}-", prefix=True)

    def _fit_knn_inner(self, df):
        """Daemon-fed KNN/ANN fit: executors stream partitions to a knn
        accumulation job; finalize BUILDS the index on the daemon's
        devices and registers it for kneighbors serving. The dataset (and
        the index, which is the same size) never reaches the driver —
        BASELINE config #5 (10M×768 ≈ 31 GB) would OOM it.

        Multi-daemon feeds build a SHARDED index (the pod-scale ANN path,
        BASELINE config #5 on v5e-64): each daemon builds and serves the
        shard holding ITS committed partitions, ids translated to global
        partition-major positions daemon-side, and ``kneighbors`` fans the
        query batch to every shard and merges top-k (models/knn.merge_topk
        — the daemon-level twin of the device merges). IVF shards bucket
        against ONE shared quantizer: the first daemon's build trains it
        and the driver forwards the (nlist, d) centroids — O(nlist·d) on
        the wire, never the data — so the union of per-shard probes equals
        the single-index candidate set."""
        core = self._core
        spark = getattr(df, "sparkSession", None)
        host, port, token = daemon_session.resolve(spark)
        ckw = daemon_session.client_kwargs(spark)
        job = f"{core.uid}-{uuid.uuid4().hex[:8]}"
        input_col = core.getOrDefault("featuresCol")
        sel = df.select(input_col)
        ivf = core.hasParam("nlist")
        metric = (
            core.getOrDefault("metric") if core.hasParam("metric")
            else "euclidean"
        )
        if ivf and metric == "inner_product":
            raise ValueError(
                "metric='inner_product' is supported by the exact "
                "NearestNeighbors only"
            )

        from spark_rapids_ml_tpu.serve.client import DataPlaneClient

        fn = _FeedTask(
            host, port, token, job, "knn", input_col, "label", {}, None
        )
        with trace_span("feed pass"):
            acks = sel.mapInArrow(
                fn,
                "partition int, rows long, daemon string, daemon_id string, "
                "boots string",
            ).collect()
        total, per_daemon, addr_of, _, _ = _ack_rows(acks)
        if total == 0:
            raise ValueError("cannot fit on an empty DataFrame")
        with DataPlaneClient(host, port, token=token, **ckw) as pc0:
            primary_id = pc0.server_id() or f"{host}:{port}"
        fed = {d: n for d, n in per_daemon.items() if n > 0}

        def _cleanup(drop_jobs=True, drop_models=()):
            # Free dataset-sized state BEFORE failing: a knn job/shard
            # holds the raw rows, and leaking them until TTL on every
            # daemon could OOM the corrected refit.
            for did in fed:
                try:
                    ah, ap = daemon_session._parse_addr(addr_of[did])
                    with DataPlaneClient(ah, ap, token=token, **ckw) as dc:
                        if drop_jobs:
                            _drop_quietly(dc, job, "knn_cleanup")
                        for m in drop_models:
                            dc.drop_model(m)
                except Exception as e:
                    _M_DROP_ERRORS.inc(stage="knn_cleanup")
                    logger.debug(
                        "knn cleanup on %s failed: %s", addr_of[did], e
                    )

        multi = len(fed) > 1
        if multi and any(":" in d for d in list(fed) + [primary_id]):
            _cleanup()
            raise RuntimeError(
                "knn fit fed multiple daemons but at least one does not "
                "self-report an instance id — it predates the sharded "
                "index serve. Upgrade every daemon, or route all "
                "executors to one daemon."
            )
        # Global ids are partition-major positions of the fitted DataFrame
        # (the single-daemon convention); each daemon's shard translates
        # its local positions through this base map.
        part_rows: dict = {}
        for r in acks:
            if int(r["rows"]) > 0:
                part_rows[int(r["partition"])] = int(r["rows"])
        id_base, cum = {}, 0
        for pid in sorted(part_rows):
            id_base[pid] = cum
            cum += part_rows[pid]
        name = f"knnidx-{job}"
        # Primary first (deterministic quantizer owner), then peers by id.
        daemon_ids = sorted(fed, key=lambda d: (d != primary_id, d))
        # The concurrent shard builds/samples below run on POOL threads,
        # whose journal stack is empty — capture the driver's fit frame
        # here so their clients still stamp it (trace_ctx ctor arg) and
        # the daemons' heaviest spans (index builds, sampling) parent
        # into the fit tree instead of orphaning.
        fit_ctx = journal.trace_ctx()

        def _finalize_shard(did, centroids=None, first=False,
                            train_rows_sample=None):
            ah, ap = daemon_session._parse_addr(addr_of[did])
            with DataPlaneClient(ah, ap, token=token, trace_ctx=fit_ctx,
                                 **ckw) as client:
                if ivf:
                    info = client.finalize_knn(
                        job, register_as=name, mode="ivf",
                        nlist=core.getNlist(), nprobe=core.getNprobe(),
                        seed=core.getSeed(), metric=metric,
                        row_id_base=id_base if multi else None,
                        centroids=centroids,
                        return_centroids=multi and first,
                        train_rows_sample=train_rows_sample,
                    )
                else:
                    info = client.finalize_knn(
                        job, register_as=name, mode="exact", metric=metric,
                        row_id_base=id_base if multi else None,
                    )
            n_shard = int(info["n_rows"][0])
            if n_shard != fed[did]:
                raise _split_brain(
                    f"knn shard build on {addr_of[did]}", fed[did], n_shard,
                    ", ".join(f"{addr_of[d]}={n}"
                              for d, n in sorted(fed.items())),
                )
            return info, (addr_of[did], n_shard)

        shards = []
        try:
            from concurrent.futures import ThreadPoolExecutor

            with trace_span("knn build"):
                if ivf and multi:
                    # The quantizer owner must not train on its OWN shard
                    # alone: locality-sticky routing makes that shard a
                    # non-random slice, skewing the shared centroids away
                    # from the peers' regions (ADVICE r5(b)). Sample every
                    # daemon in proportion to its committed rows and hand
                    # the union to the owning build — O(sample·d) on the
                    # wire, never the dataset.
                    with trace_span("quantizer sample"):
                        want = min(
                            total, max(64 * core.getNlist(), 4096), 65536
                        )

                        def _sample_shard(i, did):
                            # Ceil split: the union never rounds below
                            # ``want`` (the build's >= nlist floor).
                            n_d = (want * fed[did] + total - 1) // total
                            ah, ap = daemon_session._parse_addr(addr_of[did])
                            with DataPlaneClient(
                                ah, ap, token=token, trace_ctx=fit_ctx,
                                **ckw
                            ) as dc:
                                return dc.sample_rows(
                                    job, n_d, seed=core.getSeed() + i
                                )

                        # Independent per-daemon reads: pay the max RTT,
                        # not the sum (same pattern as the peer builds
                        # below). Ordered futures keep the union — and
                        # therefore the trained quantizer — deterministic.
                        with ThreadPoolExecutor(
                            max_workers=min(len(daemon_ids), 16)
                        ) as ex:
                            futs = [
                                ex.submit(_sample_shard, i, did)
                                for i, did in enumerate(daemon_ids)
                            ]
                            train_sample = np.concatenate(
                                [f.result() for f in futs], axis=0
                            )
                    # The first build is the quantizer owner — it must run
                    # before the peers; the peers' dataset-sized builds are
                    # then independent and run CONCURRENTLY (fit wall-clock =
                    # first + max of the rest, not the sum over daemons).
                    first_info, first_shard = _finalize_shard(
                        daemon_ids[0], first=True,
                        train_rows_sample=train_sample,
                    )
                    shards.append(first_shard)
                    cent = first_info["centroids"]
                    rest = daemon_ids[1:]
                    with ThreadPoolExecutor(max_workers=min(len(rest), 16)) as ex:
                        futs = [ex.submit(_finalize_shard, did, cent)
                                for did in rest]
                        shards.extend(f.result()[1] for f in futs)
                else:
                    # Exact mode (or one daemon): no cross-shard dependency —
                    # every build runs concurrently.
                    with ThreadPoolExecutor(
                        max_workers=min(len(daemon_ids), 16)
                    ) as ex:
                        futs = [ex.submit(_finalize_shard, did)
                                for did in daemon_ids]
                        shards.extend(f.result()[1] for f in futs)
        except Exception:
            _cleanup(drop_models=[name])
            raise
        if sum(n for _, n in shards) != total:
            _cleanup(drop_jobs=False, drop_models=[name])
            raise _split_brain(
                "knn index build", total, sum(n for _, n in shards),
                ", ".join(f"{a}={n}" for a, n in shards),
            )
        if multi:
            home_h, home_p = host, port
        else:
            # The index may have been built on an executor-override daemon
            # (not the driver-resolved one): the handle must query and
            # release where the index actually LIVES.
            home_h, home_p = daemon_session._parse_addr(shards[0][0])
        return _DaemonKNNModel(
            core, home_h, home_p, token, name,
            n_rows=total, input_col=input_col,
            shards=shards if multi else None, client_kw=ckw,
        )

    # -- distributed fit ---------------------------------------------------

    def _fit_distributed(self, df):
        """Journal-wrapped shell — the run journal (env
        ``SRML_RUN_JOURNAL``) gets one run per fit, with every feed
        pass / step / merge / finalize phase nested under it; see
        :meth:`_fit_distributed_inner` for the actual protocol."""
        with journal.run(
            "fit", estimator=type(self).__name__, algo=self._daemon_algo,
            uid=self._core.uid,
        ):
            return self._fit_distributed_inner(df)

    def _fit_distributed_inner(self, df):
        """Executor-fed fit: partition batches flow task→daemon, the
        driver sees only O(d²) finalize output — the reference's
        partition-Gram + small-partials property (RapidsRowMatrix.scala:
        118-139) with the daemon replacing the JVM tree-reduce."""
        core = self._core
        algo = self._daemon_algo
        # A scaler fit is a strict subset of the pca job's statistics —
        # it feeds the pca protocol and finalizes raw moments. Both
        # forest estimators speak the ONE "rf" job protocol (the params'
        # n_classes picks Gini vs variance daemon-side).
        wire_algo = (
            "pca" if algo == "scaler"
            else "rf" if algo in ("rf_classifier", "rf_regressor")
            else algo
        )
        spark = getattr(df, "sparkSession", None)
        host, port, token = daemon_session.resolve(spark)
        # Resilience tuning for every client this fit opens (driver AND,
        # via each task's own env read, executors): op deadlines bound the
        # healing, busy hints are honored with jittered waits.
        ckw = daemon_session.client_kwargs(spark)
        # Crash recovery: how many times one pass-boundary unit (scan +
        # step / finalize) may be REPLAYED after a daemon incarnation
        # change before the failure surfaces. 0 = off — and genuinely
        # zero-overhead: no ledger pulls, no extra wire ops.
        rec_attempts = daemon_session.recovery_attempts(spark)
        # Elastic degrade (docs/protocol.md "Permanent daemon loss"): how
        # many PEER daemons this fit may declare permanently dead and
        # amputate, and the reconnect/deadline budget a peer gets before
        # it escalates from *retrying* to *declared dead*. 0 (default) =
        # off: a lost daemon is today's loud error and no classification
        # probe ever runs. The recovery LEDGER arms for either feature —
        # an amputation rewinds survivors through the same boundary
        # replay a reboot does.
        loss_tolerance = daemon_session.daemon_loss_tolerance(spark)
        death_timeout = daemon_session.daemon_death_timeout_s(spark)
        elastic = loss_tolerance > 0
        # Elastic grow (docs/protocol.md "Mid-fit daemon join"): the
        # inverse direction — whether a daemon that APPEARS mid-fit
        # (dynamic allocation, a spot host coming up) may be admitted
        # at the next pass boundary. "off" (default) keeps the
        # unlisted-peer loud rejection byte-for-byte and runs no
        # discovery probe; the ledger arms for it like for the death
        # policy, because admission IS a boundary replay: the joiner is
        # seeded with the ledger iterate and the failed pass reruns on
        # the grown topology.
        join_policy = daemon_session.daemon_join_policy(spark)
        join_limit = daemon_session.daemon_join_limit(spark)
        grow = join_policy == "boundary"
        ledger_on = bool(rec_attempts) or elastic or grow
        # Pass cache (docs/protocol.md "rescan"): with a budget configured,
        # the passes after the first of a fit whose job algorithm says
        # `cacheable_for` its params (models/jobs.py: kmeans, logreg of
        # any class count, rf) are asked of the daemons' caches (`rescan`),
        # and rows cross the wire once. 0 (the default) = off: not one op, ack
        # column, import or branch more than before. Decided below, once
        # the fit's feed params are known.
        want_cache = False
        # What the last FED pass left in the daemons' caches, as its task
        # acks saw it — {"per", "addr_of", "owner", "boots"} — when every
        # commit ack said `cached: true`; empty = the next pass is fed.
        # Emptied by every recovery (a reboot, a quarantine, a join: the
        # fit's membership moved) and by any daemon's "no cached pass".
        cache_view: dict = {}
        job = f"{core.uid}-{uuid.uuid4().hex[:8]}"
        input_col = core.getOrDefault(
            "inputCol" if core.hasParam("inputCol") else "featuresCol"
        )
        label_col = (
            core.getOrDefault("labelCol")
            if algo in ("linreg", "logreg", "rf_classifier", "rf_regressor")
            else None
        )
        cols = [input_col] + ([label_col] if label_col else [])
        sel = df.select(*cols)
        multi_pass = algo in (
            "kmeans", "logreg", "rf_classifier", "rf_regressor",
        )
        if multi_pass:
            sel = sel.persist()

        from spark_rapids_ml_tpu.serve import protocol
        from spark_rapids_ml_tpu.serve.client import DataPlaneClient

        feed_params = {}
        # Peer daemons (executor-local routing): keyed by self-reported
        # instance id (address spellings alias); discovered from task
        # acks pass by pass, seeded up front for kmeans (resolve_all).
        peers: dict = {}
        total_fed = 0
        fed_by_daemon: dict = {}
        client = DataPlaneClient(host, port, token=token, **ckw)
        primary_id = client.server_id() or f"{host}:{port}"
        addr_by_id = {primary_id: f"{host}:{port}"}
        # One long-lived client per peer daemon for the whole fit (the
        # primary already has one): merges and iterate syncs happen every
        # pass, and per-op TCP connect churn would dominate small passes.
        peer_clients: dict = {}
        # Per-fit collective-path memory (_reduce_on_mesh): remembers a
        # "this plane has no mesh ops" verdict so a fit probes once, not
        # every pass.
        mesh_cache: dict = {}
        # Amputated daemons (id → last known address): a quarantined
        # daemon is out of the fit for good — its routes are evicted, it
        # is never synced or merged again, and a replayed pass that still
        # acks rows from it fails loudly (it is alive with unrewound
        # state; the routing must stop feeding it).
        quarantined: dict = {}
        # Mid-fit joiners (id → address), and the subset whose first
        # post-admission acked pass has not landed yet — the rebalanced-
        # rows metric counts exactly that first pass (the rows the task
        # layer actually moved onto the newcomer).
        joined: dict = {}
        awaiting_rebalance: set = set()

        def peer_client(did, addr=None):
            c = peer_clients.get(did)
            if c is None:
                h2, p2 = (
                    daemon_session._parse_addr(addr)
                    if addr is not None else peers[did]
                )
                c = DataPlaneClient(h2, p2, token=token, **ckw)
                peer_clients[did] = c
            return c

        def seed_peer_daemons(seed_fn):
            """Register + pre-seed every CONFIGURED peer daemon
            (spark.srml.daemon.addresses) before pass 0 — the one
            implementation of the alias-proof discovery both seeded
            protocols (kmeans centers, forest iterate) share: peers key
            by self-reported instance id (address spellings alias), a
            client that never registers closes here (including on an
            unreachable/unauthorized peer), registered ones are closed
            by the fit's outer finally."""
            for ph, pp in daemon_session.resolve_all(spark):
                pc = DataPlaneClient(ph, pp, token=token, **ckw)
                registered = False
                try:
                    pid_ = pc.server_id() or f"{ph}:{pp}"
                    if pid_ == primary_id or pid_ in peers:
                        continue  # an alias of a daemon already seeded
                    peers[pid_] = (ph, pp)
                    peer_clients[pid_] = pc
                    registered = True
                    seed_fn(pc)
                finally:
                    if not registered:
                        pc.close()

        # Driver-held recovery ledger: the last-known-good iterate and
        # the pass it opens, snapshotted from the same get_iterate pull
        # the peer sync already makes at every boundary. On a daemon
        # incarnation change the pass is replayed from HERE — the daemon
        # is re-seeded (set_iterate recreates the job if the restart lost
        # it entirely), so recovery works even without daemon-side
        # durable state.
        ledger: dict = {"arrays": None, "iteration": None}

        try:
            if algo == "logreg":
                # Spark ML infers numClasses from the labels; here one
                # O(1)-result probe job (per-partition max) picks the
                # binary-Newton vs multinomial-MM daemon protocol.
                n_classes = _probe_num_classes(sel, label_col)
                feed_params = {"n_classes": n_classes}
            if algo == "kmeans":
                k = core.getK()
                feed_params = {
                    "k": k,
                    "seed": core.getSeed(),
                    "init": core.getInitMode(),
                }
                # Deterministic driver-side seeding: a small prefix sample
                # (≥ k rows) — ONE tiny Spark job, like the reference's
                # numCols probe (RapidsPCA.scala:73-74). The SAME batch +
                # rng seed goes to every configured daemon
                # (spark.srml.daemon.addresses), so all hosts open pass 0
                # with bitwise-identical centers; a peer daemon NOT listed
                # there fails its tasks loudly (centers unseeded).
                seed_n = max(k, min(4096, 32 * k))
                seed_tbl = _df_to_arrow(sel.limit(seed_n), [input_col])
                client.seed_kmeans(
                    job, seed_tbl, k=k, input_col=input_col, params=feed_params
                )
                seed_peer_daemons(
                    lambda pc: pc.seed_kmeans(
                        job, seed_tbl, k=k, input_col=input_col,
                        params=feed_params,
                    )
                )
                if ledger_on:
                    # Ledger seed: pass 0 opens with the seeded centers —
                    # a pass-0 replay re-installs exactly these.
                    ledger["arrays"], ledger["iteration"] = (
                        client.get_iterate(job)
                    )
            if algo in ("rf_classifier", "rf_regressor"):
                from spark_rapids_ml_tpu.bridge.arrow import (
                    table_column_to_matrix,
                )
                from spark_rapids_ml_tpu.models import (
                    random_forest as rf_mod,
                )
                from spark_rapids_ml_tpu.ops.histogram import (
                    quantile_bin_edges,
                )

                # numClasses from an O(1)-result label probe (the logreg
                # pattern); 0 = regression (variance splits).
                n_classes = (
                    _probe_num_classes(sel, label_col)
                    if algo == "rf_classifier" else 0
                )
                feed_params = {
                    "num_trees": core.getNumTrees(),
                    "max_depth": core.getMaxDepth(),
                    "max_bins": core.getMaxBins(),
                    "n_classes": n_classes,
                    "subset": core.getFeatureSubsetStrategy(),
                    "seed": core.getSeed(),
                    "bootstrap": core.getBootstrap(),
                    "min_instances": core.getMinInstancesPerNode(),
                }
                # Deterministic driver-side binning seed: a bounded
                # prefix sample (ONE tiny Spark job — the kmeans-seed /
                # numCols-probe pattern, RapidsPCA.scala:73-74) trains
                # the quantile sketch, and set_iterate installs the
                # SAME (edges + empty node tables) iterate on every
                # configured daemon before pass 0 — all hosts bin
                # bitwise-identically; an unlisted peer daemon fails
                # its tasks loudly (iterate unseeded), exactly the
                # kmeans contract.
                sample_n = int(config.get("forest_seed_sample_rows"))
                seed_tbl = _df_to_arrow(sel.limit(sample_n), [input_col])
                sample = table_column_to_matrix(seed_tbl, input_col, None)
                if sample.shape[0] == 0:
                    raise ValueError("cannot fit on an empty DataFrame")
                rf_n_cols = int(sample.shape[1])
                rf_spec = rf_mod.forest_spec_from_params(
                    feed_params, rf_n_cols
                )
                init_arrays = rf_mod.init_forest_arrays(
                    rf_spec, quantile_bin_edges(sample, rf_spec.max_bins)
                )
                client.set_iterate(
                    job, init_arrays, 0, algo=wire_algo,
                    n_cols=rf_n_cols, params=feed_params,
                )
                seed_peer_daemons(
                    lambda pc: pc.set_iterate(
                        job, init_arrays, 0, algo=wire_algo,
                        n_cols=rf_n_cols, params=feed_params,
                    )
                )
                if ledger_on:
                    # Ledger seed: a pass-0 replay re-installs exactly
                    # the seeded (edges + empty tables) iterate.
                    ledger["arrays"], ledger["iteration"] = (
                        client.get_iterate(job)
                    )

            if daemon_session.pass_cache_mb(spark) > 0:
                from spark_rapids_ml_tpu.models.jobs import job_algorithm

                # the one answer the daemon's job gives itself (`_Job`)
                want_cache = job_algorithm(wire_algo).cacheable_for(feed_params)

            def run_pass(pass_id, merge=True, drop_peer=False):
                """One executor scan; folds peer-daemon partials into the
                primary and reconciles row counts. Returns the pass total."""
                nonlocal total_fed
                fn = _FeedTask(
                    host, port, token, job, wire_algo, input_col,
                    label_col or "label", feed_params, pass_id,
                    # Ship the amputation set to the executors: THEIR
                    # cache copies hold the dead daemon's id (reused
                    # python workers), not the driver's.
                    evict_routes=sorted(
                        addr for addr in quarantined.values() if addr
                    ),
                    want_cache=want_cache,
                )
                with trace_span("feed pass"):
                    acks = sel.mapInArrow(
                        fn,
                        "partition int, rows long, daemon string, "
                        "daemon_id string, boots string"
                        + (", cached boolean" if want_cache else ""),
                    ).collect()
                n, per, addr_of, owner, boots = _ack_rows(acks)
                # A fed pass refills the caches: what it leaves is what
                # the next pass may ask for.
                cache_view.clear()
                if want_cache and n > 0 and all(
                    bool(r["cached"]) for r in acks if int(r["rows"]) > 0
                ):
                    cache_view.update(
                        per=per, addr_of=addr_of, owner=owner, boots=boots
                    )
                for did, cnt in per.items():
                    if cnt > 0 and did in quarantined:
                        # The amputation's safety valve: a daemon that
                        # was declared dead but ANSWERS the replayed
                        # scan is alive with unrewound state — folding
                        # its rows would corrupt the model the rewind
                        # just repaired.
                        raise RuntimeError(
                            f"daemon {addr_of[did]} ({did}) was declared "
                            f"dead and quarantined, yet acked {cnt} rows "
                            "of the replayed pass: it is alive and holds "
                            "un-rewound state. Stop routing executors to "
                            "it (it left this fit for good), or refit."
                        )
                for did, cnt in per.items():
                    fed_by_daemon[did] = fed_by_daemon.get(did, 0) + cnt
                    addr_by_id.setdefault(did, addr_of[did])
                    # Only a daemon that actually holds rows becomes a
                    # peer: an all-empty-partitions executor acks rows=0
                    # without ever creating the job there — set_iterate
                    # against it would fail an otherwise-consistent fit.
                    if cnt > 0 and did != primary_id and did not in peers:
                        # An unknown id AT THE PRIMARY ADDRESS — or one
                        # the live primary now answers with (the
                        # alias-proof identity check; address spellings
                        # alias) — is not a peer: it is the primary
                        # having restarted WITHOUT durable state (a
                        # state_dir daemon keeps its instance id).
                        # Registering it would export the primary's
                        # state and merge it into itself. Fence it like
                        # any incarnation change; recover() re-resolves
                        # the identity. The ping runs once per newly
                        # seen id per fit — not per pass.
                        if addr_of[did] == f"{host}:{port}" or did == (
                            client.server_id() or primary_id
                        ):
                            raise _incarnation_change(
                                addr_of[did], {primary_id, did}
                            )
                        # Instance ids are opaque hex; a ":" means the
                        # address-string FALLBACK for a daemon that does
                        # not report an id — such a daemon predates the
                        # multi-host ops entirely, and an aliased
                        # spelling of the primary would masquerade as a
                        # peer. Refuse clearly instead of failing later
                        # with an opaque unknown-op error (or worse,
                        # merging the primary into itself).
                        if ":" in did or ":" in primary_id:
                            raise RuntimeError(
                                f"task acks name a second daemon "
                                f"({addr_of[did]} vs primary "
                                f"{addr_by_id[primary_id]}) but at least "
                                "one daemon does not report an instance "
                                "id — it predates the multi-host data "
                                "plane. Upgrade every daemon, or unify "
                                "the daemon address spelling and use one "
                                "daemon."
                            )
                        peers[did] = daemon_session._parse_addr(addr_of[did])
                # The grow metric's ground truth: the first pass a
                # joiner actually acks rows for IS the rebalance — the
                # task layer moved those rows onto the newcomer.
                for did in sorted(awaiting_rebalance):
                    if per.get(did, 0) > 0:
                        _M_FIT_REBALANCED.inc(per[did], algo=str(algo))
                        awaiting_rebalance.discard(did)
                # Incarnation fence AFTER peer registration (recover()
                # must know every daemon this pass touched, so it can
                # rewind/drop them all) but BEFORE any merge: partials
                # from a daemon that restarted under the scan are partial
                # in an unknowable way — folding them would poison the
                # primary.
                for did, bs in boots.items():
                    if len(bs) > 1:
                        raise _incarnation_change(addr_of.get(did, did), bs)
                if merge:
                    with trace_span("merge peers"):
                        # Collective first (docs/mesh.md): co-resident
                        # daemons reduce on the device plane; the
                        # export/merge hub is the fallback for peers on
                        # a different runtime (or predating the op).
                        if not _reduce_on_mesh(
                            client, job, primary_id, per, addr_of, owner,
                            boots, wire_algo, feed_params, drop_peer,
                            mesh_cache,
                        ):
                            _merge_peer_daemons(
                                client, job, primary_id, per, addr_of,
                                owner, peer_client, wire_algo, feed_params,
                                drop_peer=drop_peer,
                            )
                total_fed += n
                return n

            def _fed_detail():
                return ", ".join(
                    f"{addr_by_id.get(d, d)}={c}"
                    for d, c in sorted(fed_by_daemon.items())
                ) or "no acks"

            def finalize_guarded(params, pass_rows_expected=None):
                """Primary finalize + the split-brain row guard: the
                daemon-accounted total must equal what tasks acked.
                Replay-safe split: finalize with drop=False, validate,
                THEN drop — a guard failure leaves the job intact for a
                recovery replay. ``pass_rows_expected`` additionally pins
                the CURRENT pass's rows (the kmeans cost reads the
                current pass's state; a job resurrected at an empty
                boundary would silently answer cost 0)."""
                with trace_span("finalize"):
                    arrays, fin_rows, meta = client.finalize(
                        job, params, drop=False, with_meta=True
                    )
                if fin_rows != total_fed:
                    raise _split_brain(
                        "finalize", total_fed, fin_rows, _fed_detail()
                    )
                if (
                    pass_rows_expected is not None
                    and meta.get("pass_rows") is not None
                    and int(meta["pass_rows"]) != int(pass_rows_expected)
                ):
                    raise _split_brain(
                        "finalize (current pass)", int(pass_rows_expected),
                        int(meta["pass_rows"]), _fed_detail(),
                    )
                # Best-effort: the validated arrays are already in hand —
                # a cleanup failure here must not fail (or re-scan) the
                # fit. The outer finally retries the drop anyway.
                _drop_quietly(client, job, "finalize")
                return arrays, fin_rows

            def sync_and_record(push_peers=True):
                """Pass boundary: distribute the primary's post-step
                iterate to every peer AND snapshot it into the recovery
                ledger (one get_iterate serves both).
                ``push_peers=False`` records the ledger only — the
                converged-logreg boundary, where nothing will read a
                peer's iterate but a finalize replay still rewinds to
                exactly this iterate."""
                if not (peers and push_peers) and not ledger_on:
                    return
                arrays, iteration = client.get_iterate(job)
                if push_peers:
                    for did in sorted(peers):
                        peer_client(did).set_iterate(job, arrays, iteration)
                if ledger_on:
                    # The ledger advances ONLY once every daemon holds
                    # the new boundary: a half-pushed boundary (a peer
                    # died mid-sync) must replay from the OLD one — an
                    # early-advanced ledger would pin the daemons at
                    # iteration N+1 while the replay re-feeds pass N,
                    # turning every replay into a stale-pass rejection.
                    ledger["arrays"], ledger["iteration"] = arrays, iteration

            def _probe_alive(addr_tuple) -> bool:
                """Liveness verdict under the death policy: the probing
                client's op deadline IS ``fit_daemon_death_timeout_s``,
                so the daemon gets the WHOLE reconnect/backoff budget to
                answer one ping — a slow or busy daemon that makes it in
                time is never amputated on a hunch."""
                probe_kw = dict(ckw)
                probe_kw["op_deadline_s"] = death_timeout
                probe_kw["max_op_attempts"] = max(
                    int(probe_kw.get("max_op_attempts", 5)), 8
                )
                try:
                    with DataPlaneClient(*addr_tuple, token=token,
                                         **probe_kw) as pc:
                        pc.ping()
                    return True
                except Exception:
                    return False

            def try_admit(err) -> bool:
                """The grow policy's admission step (docs/protocol.md
                "Mid-fit daemon join"), run only after a pass unit
                already failed — a new daemon's unseeded-job rejection
                of its first feeds IS the detection signal, and the
                happy path stays zero-overhead (one env/conf re-read,
                no wire ops unless a genuinely new address appears).
                Re-reads the configured daemon set (Spark dynamic
                allocation re-points ``spark.srml.daemon.addresses``),
                identifies addresses that resolve to an instance id
                this fit does not know, and admits each at the CURRENT
                pass boundary: ``set_iterate`` seeds it with the ledger
                iterate (the same algo/n_cols/params creation fields a
                quarantine replay uses — the job is created from
                nothing on the joiner), membership registration bumps
                the mesh epoch daemon-side so the next collective
                reduce re-fences, and the caller's ``recover`` rewinds
                every daemon to the same boundary before the replay
                rebalances partitions onto the newcomer. True = at
                least one daemon admitted (replay on the grown
                topology); False = nothing new appeared — the loss
                policy or the transient replay budget rules."""
                if not grow or ledger["arrays"] is None:
                    # No boundary iterate to seed a joiner from (a
                    # single-pass algo, whose ack path already admits
                    # unknown peers natively, or a pre-seed failure).
                    return False
                known = {f"{host}:{port}"}
                known.update(f"{h2}:{p2}" for h2, p2 in peers.values())
                known.update(a for a in quarantined.values() if a)
                known.update(a for a in joined.values() if a)
                candidates = [
                    (ph, pp) for ph, pp in daemon_session.resolve_all(spark)
                    if f"{ph}:{pp}" not in known
                ]
                admitted = []
                for ph, pp in candidates:
                    addr = f"{ph}:{pp}"
                    pc = DataPlaneClient(ph, pp, token=token, **ckw)
                    registered = False
                    try:
                        try:
                            did = pc.server_id()
                        except Exception:
                            continue  # configured but not up yet
                        # Alias fences, in the run_pass order: an
                        # unknown ADDRESS may still be a spelling of a
                        # daemon this fit already knows.
                        if not did or did == primary_id or did in peers:
                            continue
                        if did in quarantined:
                            # A dead daemon's address re-answering with
                            # the same id is the quarantine safety
                            # valve's territory, not a joiner.
                            continue
                        if len(joined) + 1 > join_limit:
                            raise RuntimeError(
                                f"daemon {addr} ({did}) appeared mid-fit "
                                f"but this fit's join budget is spent "
                                f"(fit_daemon_join_limit={join_limit}, "
                                f"{len(joined)} already admitted). Raise "
                                "the limit, or stop routing executors "
                                "to it until the next fit."
                            ) from err
                        # The admission handshake: seed the joiner with
                        # the boundary iterate. A joiner that vanishes
                        # UNDER the handshake must not half-join — the
                        # set_iterate failure surfaces here, nothing
                        # was registered, and the original error's
                        # replay path resumes without it.
                        faults.checkpoint("daemon.join")
                        arrays = ledger["arrays"]
                        n_cols = int(
                            arrays["centers"].shape[1]
                            if "centers" in arrays
                            else arrays["bin_edges"].shape[0]
                            if "bin_edges" in arrays
                            else arrays["w"].shape[0]
                        )
                        pc.set_iterate(
                            job, arrays, int(ledger["iteration"]),
                            algo=wire_algo, n_cols=n_cols,
                            params=feed_params,
                        )
                        peers[did] = (ph, pp)
                        addr_by_id[did] = addr
                        peer_clients[did] = pc
                        registered = True
                        joined[did] = addr
                        awaiting_rebalance.add(did)
                        admitted.append(did)
                        _M_FIT_JOINS.inc(algo=str(algo))
                        journal.mark(
                            "fit daemon join", algo=algo, job=job,
                            daemon=did, addr=addr,
                            iteration=int(ledger["iteration"]),
                        )
                        logger.warning(
                            "fit elastic grow (%s): daemon %s (%s) "
                            "admitted at the pass-%d boundary — seeded "
                            "with the ledger iterate; replaying the "
                            "failed pass on the %d-daemon topology",
                            algo, addr, did, int(ledger["iteration"]),
                            len(peers) + 1,
                        )
                    finally:
                        if not registered:
                            pc.close()
                return bool(admitted)

            def try_quarantine(err) -> bool:
                """The death policy's classification step, run only after
                a pass unit already failed (zero wire ops on the happy
                path): probe every peer within the death deadline,
                corroborate with mesh membership when co-resident, and
                amputate the corroborated-dead peers if the loss budget
                allows. True = at least one daemon quarantined (the pass
                replays on the shrunken topology); False = nothing
                classified as dead — the transient replay budget (or the
                original error) rules."""
                if not peers:
                    return False
                # Mesh corroboration (docs/mesh.md): on the collective
                # path the membership registry is a second witness — a
                # peer the device plane still lists as a live member is
                # NOT dead, however its TCP probe fared.
                live_members = None
                if not mesh_cache.get("hub_only"):
                    try:
                        info = client.mesh_info()
                        live_members = {
                            str(m["id"]) for m in info.get("members", [])
                        }
                    except Exception:
                        live_members = None
                # Probes run CONCURRENTLY (independent reads): a pod-
                # scale fit partitioned away from several peers must
                # classify in ~one death deadline, not n_peers of them.
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(
                    max_workers=min(len(peers) + 1, 16)
                ) as ex:
                    primary_fut = ex.submit(_probe_alive, (host, port))
                    peer_futs = {
                        did: ex.submit(_probe_alive, peers[did])
                        for did in sorted(peers)
                    }
                    primary_ok = primary_fut.result()
                    alive = {d_: f.result() for d_, f in peer_futs.items()}
                # The primary is the reduce target and the rewind anchor:
                # its loss is not survivable by amputation — name that
                # clearly instead of burning the tolerance on peers.
                if not primary_ok:
                    raise RuntimeError(
                        f"primary daemon {host}:{port} is unreachable "
                        f"(no answer within the {death_timeout:.1f}s "
                        "death deadline): elastic degrade can only "
                        "amputate PEER daemons — the primary holds the "
                        "folded state. Restart it (crash recovery "
                        "resurrects durable jobs) or refit."
                    ) from err
                dead = []
                for did in sorted(peers):
                    if alive[did]:
                        continue
                    if live_members is not None and did in live_members:
                        logger.warning(
                            "peer daemon %s failed its liveness probe "
                            "but is still a live mesh member — treating "
                            "the failure as transient, not a death",
                            addr_by_id.get(did, did),
                        )
                        continue
                    dead.append(did)
                if not dead:
                    return False
                if len(quarantined) + len(dead) > loss_tolerance:
                    raise RuntimeError(
                        f"daemon(s) "
                        f"{[addr_by_id.get(d, d) for d in dead]} gave no "
                        f"answer within the {death_timeout:.1f}s death "
                        f"deadline, but this fit's loss budget is spent "
                        f"(fit_daemon_loss_tolerance={loss_tolerance}, "
                        f"{len(quarantined)} already quarantined). Raise "
                        "the tolerance, or refit on the surviving "
                        "daemons."
                    ) from err
                for did in dead:
                    addr = addr_by_id.get(did)
                    quarantined[did] = addr
                    peers.pop(did, None)
                    pc = peer_clients.pop(did, None)
                    if pc is not None:
                        pc.close()
                    if addr is not None:
                        # The replayed tasks must re-ping whatever now
                        # answers at the dead daemon's address — a cached
                        # id would resurrect the ghost.
                        _evict_daemon_id_cache(job, addr)
                    _M_DAEMON_LOSSES.inc(algo=str(algo))
                    journal.mark(
                        "fit daemon loss", algo=algo, job=job,
                        daemon=did, addr=addr,
                    )
                    logger.warning(
                        "fit elastic degrade (%s): peer daemon %s (%s) "
                        "declared dead — no answer within the %.1fs "
                        "death deadline; quarantining it and replaying "
                        "from the last pass boundary with its "
                        "partitions rerouted to the %d survivor(s)",
                        algo, addr, did, death_timeout, len(peers) + 1,
                    )
                return True

            def reseed(arrays, iteration):
                """Open pass ``iteration`` at ``arrays`` on EVERY daemon
                (set_iterate discards the pass-local state and recreates
                a lost job) and resynchronize the row accounting from
                the primary's authoritative total."""
                nonlocal total_fed
                # Registration-table shape dispatch: which array carries
                # the feature width per iterate layout (kmeans centers /
                # forest bin edges / logreg w).
                n_cols = int(
                    arrays["centers"].shape[1]
                    if "centers" in arrays
                    else arrays["bin_edges"].shape[0]
                    if "bin_edges" in arrays
                    else arrays["w"].shape[0]
                )
                client.set_iterate(
                    job, arrays, iteration, algo=wire_algo,
                    n_cols=n_cols, params=feed_params,
                )
                for did in sorted(peers):
                    peer_client(did).set_iterate(
                        job, arrays, iteration, algo=wire_algo,
                        n_cols=n_cols, params=feed_params,
                    )
                total_fed = int(client.status(job)["rows"])

            def recover(err):
                """Rewind the fit to the last pass boundary: re-seed the
                iterate from the driver ledger on EVERY daemon
                (set_iterate discards the poisoned pass-local state and
                recreates lost jobs), then resynchronize the row
                accounting from the daemon's authoritative total. With no
                ledger yet (pass 0 of a fresh fit, or a single-pass
                algo) the unit is re-runnable from nothing: drop the
                jobs and replay the whole scan."""
                nonlocal total_fed, primary_id
                _M_FIT_RECOVERIES.inc(algo=str(algo))
                logger.warning(
                    "fit recovery (%s): replaying from the last pass "
                    "boundary after: %s", algo, err,
                )
                journal.mark(
                    "fit recovery", algo=algo, job=job, error=str(err)[:300]
                )
                with trace_span("recovery"):
                    # Re-resolve the primary's identity: a volatile
                    # (no-state_dir) restart minted a new instance id,
                    # and the replay's acks must match it — otherwise
                    # the restarted primary would register as its own
                    # peer and be merged into itself.
                    new_id = client.server_id() or primary_id
                    if new_id != primary_id:
                        addr_by_id[new_id] = f"{host}:{port}"
                        peers.pop(new_id, None)
                        primary_id = new_id
                    cache_view.clear()  # the replay is a fed pass
                    arrays = ledger["arrays"]
                    if arrays is not None:
                        reseed(arrays, int(ledger["iteration"]))
                    else:
                        for c_ in [client] + [
                            peer_client(d) for d in sorted(peers)
                        ]:
                            _drop_quietly(c_, job, "recovery")
                        total_fed = 0
                    fed_by_daemon.clear()

            def with_recovery(body):
                """Run one pass-boundary-delimited unit (scan [+ step]
                [+ finalize]) under the bounded replay loop. Recovery
                off (the default) adds nothing: the first failure
                surfaces unchanged. Deterministic driver-side failures
                (validation/config/programming errors) are never
                replayed — a full-dataset re-scan cannot fix an empty
                DataFrame or a bad label column. Daemon/task failures
                (RuntimeError from acks, transport errors, job aborts)
                are the retryable class the replay exists for.

                Elastic degrade rides the same loop: a failure that
                classifies as a PERMANENT daemon death (try_quarantine)
                replays the pass on the shrunken topology without
                consuming the transient replay budget — each amputation
                consumes the loss tolerance instead, so both budgets
                stay bounded."""
                attempt = 0
                while True:
                    try:
                        return body()
                    except (ValueError, TypeError, KeyError,
                            AttributeError, AssertionError,
                            NotImplementedError):
                        raise  # deterministic — a replay cannot help
                    except Exception as e:
                        # Grow first: a failure caused by an unadmitted
                        # newcomer (its unseeded-job rejections failed
                        # the scan) is healed by ADMITTING it, and the
                        # admission consumes the join budget — not the
                        # transient replay budget, and never the loss
                        # tolerance (every incumbent is alive).
                        if grow and try_admit(e):
                            with trace_span("elastic grow"):
                                journal.mark(
                                    "fit elastic-grow", algo=algo,
                                    job=job, error=str(e)[:300],
                                )
                                recover(e)
                            continue
                        if elastic and try_quarantine(e):
                            with trace_span("elastic degrade"):
                                _M_FIT_REROUTES.inc(algo=str(algo))
                                journal.mark(
                                    "fit elastic-degrade", algo=algo,
                                    job=job, error=str(e)[:300],
                                )
                                recover(e)
                            continue
                        if attempt >= rec_attempts:
                            raise
                        attempt += 1
                        recover(e)

            def cached_pass(pass_id):
                """One pass asked of the daemons' caches (`rescan`,
                docs/protocol.md): every daemon that held rows in
                the last fed pass folds its cached pass against its
                current iterate, and the peers' partials are merged
                as after a scan. Each must answer for exactly the
                rows its tasks acked then, from the incarnation
                that acked them. Returns the pass total, or None
                when a daemon has no cached pass: the pass is then
                fed (daemons that already folded theirs are rewound
                to this pass's open boundary first)."""
                nonlocal total_fed
                view = dict(cache_view)
                per, addr_of = view["per"], view["addr_of"]
                asked = 0
                try:
                    with trace_span("rescan pass"):
                        for did in sorted(d for d, c in per.items() if c > 0):
                            c_ = (
                                client if did == primary_id
                                else peer_client(did, addr_of[did])
                            )
                            ack = c_.rescan(job, pass_id)
                            asked += 1
                            boot = ack.get("boot_id")
                            seen = view["boots"].get(did) or set()
                            if boot is not None and seen and str(boot) not in seen:
                                raise _incarnation_change(
                                    addr_of.get(did, did),
                                    set(seen) | {str(boot)},
                                )
                            if int(ack["pass_rows"]) != per[did]:
                                raise _split_brain(
                                    f"rescan (pass {pass_id}) on "
                                    f"{addr_of.get(did, did)}", per[did],
                                    int(ack["pass_rows"]), _fed_detail(),
                                )
                except protocol.NoCachedPass as e:
                    logger.info(
                        "pass %s is re-fed: %s", pass_id, e
                    )
                    cache_view.clear()
                    if asked:
                        arrays, iteration = client.get_iterate(job)
                        reseed(arrays, iteration)
                    return None
                with trace_span("merge peers"):
                    if not _reduce_on_mesh(
                        client, job, primary_id, per, addr_of,
                        view["owner"], view["boots"], wire_algo,
                        feed_params, False, mesh_cache,
                    ):
                        _merge_peer_daemons(
                            client, job, primary_id, per, addr_of,
                            view["owner"], peer_client, wire_algo,
                            feed_params, drop_peer=False,
                        )
                n = sum(per.values())
                for did, cnt in per.items():
                    fed_by_daemon[did] = fed_by_daemon.get(did, 0) + cnt
                total_fed += n
                return n

            def scan(pass_id):
                """This pass's rows into the daemons' statistics:
                from their caches when the last fed pass left every
                row there, else over the wire (which refills them)."""
                if cache_view:
                    n = cached_pass(pass_id)
                    if n is not None:
                        return n
                return run_pass(pass_id)

            if algo == "scaler":

                def scaler_shot():
                    n = run_pass(None, drop_peer=True)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    return finalize_guarded(
                        {"raw_moments": True}, pass_rows_expected=n
                    )

                arrays, _ = with_recovery(scaler_shot)
                from spark_rapids_ml_tpu.models.scaler import StandardScalerModel

                cnt = float(arrays["count"][0])
                mean = np.asarray(arrays["colsum"], np.float64) / cnt
                var = (
                    np.asarray(arrays["gram_diag"], np.float64)
                    - cnt * mean * mean
                ) / max(cnt - 1.0, 1.0)
                model = StandardScalerModel(
                    mean=mean, std=np.sqrt(np.maximum(var, 0.0))
                )
            elif algo == "pca":

                def pca_shot():
                    n = run_pass(None, drop_peer=True)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    return finalize_guarded(
                        {
                            "k": core.getK(),
                            "mean_center": core.getMeanCentering(),
                            "solver": core.getSolver(),
                        },
                        pass_rows_expected=n,
                    )

                arrays, _ = with_recovery(pca_shot)
                from spark_rapids_ml_tpu.models.pca import PCAModel

                model = PCAModel(
                    pc=arrays["pc"],
                    explained_variance=arrays["explained_variance"],
                    mean=arrays["mean"],
                )
            elif algo == "linreg":

                def linreg_shot():
                    n = run_pass(None, drop_peer=True)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    return finalize_guarded(
                        {
                            "reg": core.getRegParam(),
                            "elastic_net": core.getElasticNetParam(),
                            "fit_intercept": core.getFitIntercept(),
                            "max_iter": core.getMaxIter(),
                            "tol": core.getTol(),
                        },
                        pass_rows_expected=n,
                    )

                arrays, rows = with_recovery(linreg_shot)
                from spark_rapids_ml_tpu.models.linear_regression import (
                    LinearRegressionModel,
                    LinearRegressionTrainingSummary,
                )

                model = LinearRegressionModel(
                    coefficients=arrays["coefficients"],
                    intercept=float(arrays["intercept"][0]),
                )
                model._summary = LinearRegressionTrainingSummary(
                    rmse=float(arrays["rmse"][0]),
                    r2=float(arrays["r2"][0]),
                    rss=float("nan"),
                    tss=float("nan"),
                    n_rows=rows,
                )
            elif algo == "kmeans":
                tol2 = core.getTol() ** 2
                info = {"cost": float("nan"), "iteration": 0}

                def kmeans_pass(pass_id):
                    n = scan(pass_id)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    with trace_span("step"):
                        inf = client.step(job)
                    # The step's statistics must cover exactly the rows
                    # the scan acked: a job resurrected mid-pass (its
                    # pass-local state died with the old incarnation)
                    # answers short here instead of stepping on partial
                    # sums.
                    if int(inf["pass_rows"]) != n:
                        raise _split_brain(
                            f"step (pass {pass_id})", n,
                            int(inf["pass_rows"]), _fed_detail(),
                        )
                    # Every peer opens the new pass with the primary's
                    # post-step centers (set_iterate resets its pass
                    # stats) — the cross-host Lloyd lockstep — and the
                    # recovery ledger snapshots the same pull. Runs even
                    # on the converged pass: the final cost-only scan
                    # below feeds peers against the updated centers.
                    # INSIDE the recovery unit: a daemon dying in this
                    # window rewinds to the previous boundary and the
                    # whole scan+step+sync replays.
                    sync_and_record()
                    return inf

                for it in range(core.getMaxIter()):
                    info = with_recovery(lambda pid=it: kmeans_pass(pid))
                    if info["moved2"] <= tol2:
                        break

                # One final cost-only scan at the UPDATED centers (r2
                # advisor: step() evaluates cost against the pre-update
                # centers, so the last step's cost is one Lloyd iteration
                # stale). finalize reads the unstepped pass's inertia —
                # the exact fit_kmeans_stream trainingCost semantics.
                def kmeans_final():
                    n = scan(info["iteration"])
                    fin_arrays, _ = finalize_guarded(
                        {}, pass_rows_expected=n
                    )
                    return n, fin_arrays

                n_rows, arrays = with_recovery(kmeans_final)
                cost = float(arrays["cost"][0])
                from spark_rapids_ml_tpu.models.kmeans import (
                    KMeansModel,
                    KMeansSummary,
                )

                model = KMeansModel(centers=arrays["centers"])
                model._training_cost = cost
                model._n_iter = info["iteration"]
                model._summary = KMeansSummary(
                    trainingCost=cost,
                    numIter=info["iteration"],
                    k=core.getK(),
                    n_rows=n_rows,
                )
            elif algo in ("rf_classifier", "rf_regressor"):
                info = {"open_nodes": 1, "iteration": 0, "depth": 0}
                rows = 0

                def rf_pass(pass_id):
                    # every depth after the first from the daemons' caches
                    # where the last fed pass left every row there
                    n = scan(pass_id)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    with trace_span("step"):
                        inf = client.step(job)
                    # The step's histogram must cover exactly the rows
                    # the scan acked (the kmeans/logreg fence): a job
                    # resurrected mid-pass answers short here instead of
                    # splitting on partial histograms.
                    if int(inf["pass_rows"]) != n:
                        raise _split_brain(
                            f"step (pass {pass_id})", n,
                            int(inf["pass_rows"]), _fed_detail(),
                        )
                    # Boundary sync INSIDE the recovery unit: peers open
                    # the next depth with the primary's grown node
                    # tables, and the ledger snapshots the same pull —
                    # a daemon dying here rewinds to the previous
                    # boundary and the whole scan+step+sync replays.
                    sync_and_record()
                    return n, inf

                # One histogram pass per tree depth, until every
                # frontier closed (or maxDepth landed its last split).
                for it in range(core.getMaxDepth() + 1):
                    rows, info = with_recovery(lambda pid=it: rf_pass(pid))
                    if int(info["open_nodes"]) == 0:
                        break
                arrays, _ = with_recovery(lambda: finalize_guarded({}))
                from spark_rapids_ml_tpu.models.random_forest import (
                    RandomForestClassificationModel,
                    RandomForestRegressionModel,
                )

                arrays = dict(arrays)
                arrays.pop("n_iter", None)
                cls = (
                    RandomForestClassificationModel
                    if algo == "rf_classifier"
                    else RandomForestRegressionModel
                )
                model = cls(arrays=arrays)
            else:  # logreg
                info = {"loss": float("nan"), "iteration": 0}
                step_params = {
                    "reg": core.getRegParam(),
                    "fit_intercept": core.getFitIntercept(),
                }
                rows = 0

                def logreg_pass(pass_id):
                    n = scan(pass_id)
                    if n == 0:
                        raise ValueError("cannot fit on an empty DataFrame")
                    with trace_span("step"):
                        inf = client.step(job, params=step_params)
                    if int(inf["pass_rows"]) != n:
                        raise _split_brain(
                            f"step (pass {pass_id})", n,
                            int(inf["pass_rows"]), _fed_detail(),
                        )
                    # Boundary sync INSIDE the recovery unit (a daemon
                    # dying here rewinds to the previous boundary and the
                    # whole scan+step+sync replays). Converged: nothing
                    # reads a peer sync now, but the ledger still needs
                    # THIS iterate — a finalize replay rewinds to it.
                    # (Pass 0 needs no peer sync either way: every daemon
                    # starts at the zero iterate — a pass-0 replay just
                    # drops and recreates the job.)
                    sync_and_record(
                        push_peers=inf["delta"] > core.getTol()
                    )
                    return n, inf

                for it in range(core.getMaxIter()):
                    rows, info = with_recovery(
                        lambda pid=it: logreg_pass(pid)
                    )
                    if info["delta"] <= core.getTol():
                        break
                arrays, _ = with_recovery(lambda: finalize_guarded({}))
                from spark_rapids_ml_tpu.models.logistic_regression import (
                    LogisticRegressionModel,
                    LogisticTrainingSummary,
                )

                coef = arrays["coefficients"]
                model = LogisticRegressionModel(
                    coefficients=coef,
                    # Binary: scalar; multinomial ((C, d) coef): (C,) vector.
                    intercept=(
                        float(arrays["intercept"][0])
                        if coef.ndim == 1
                        else np.asarray(arrays["intercept"])
                    ),
                )
                model._summary = LogisticTrainingSummary(
                    loss=info["loss"], numIter=info["iteration"], n_rows=rows
                )
        finally:
            # The fit's id-cache routes die with the fit (success,
            # failure, or quarantine): the entries are job-scoped, so a
            # leaked one both grows forever on a long-lived driver and
            # could hand a RECYCLED job name a stale daemon id.
            _evict_daemon_id_cache(job)
            # no-op when finalize already dropped it; failures are
            # COUNTED (srml_client_drop_errors_total) — a swallowed drop
            # leaks the daemon job until the TTL reaper hides it.
            _drop_quietly(client, job, "primary")
            client.close()
            for did in list(peers):
                try:
                    _drop_quietly(peer_client(did), job, "peer")
                except Exception as e:  # peer_client() itself can fail
                    _M_DROP_ERRORS.inc(stage="peer")
                    logger.debug(
                        "cleanup drop on peer %s failed: %s", did, e
                    )
            for pc in peer_clients.values():
                pc.close()
            if multi_pass:
                sel.unpersist()
        model.uid = core.uid
        core._copy_params_to(model)
        return model


def _serve_spec(core_model):
    """(wire algo, [(role, output column name, kind)]) for models that
    declare the daemon serving contract (``_serve_algo``/``_serve_outputs``
    on the model class); None for models without one (KNN — no transform)."""
    algo = getattr(core_model, "_serve_algo", None)
    outs = getattr(core_model, "_serve_outputs", None)
    if not algo or not outs:
        return None
    return algo, [
        (role, core_model.getOrDefault(param), kind) for role, param, kind in outs
    ]


def _scalar_params(core_model):
    """Serving-behavior params of the model (``_serve_params`` on the
    model class, e.g. scaler withMean/withStd) — what a served daemon
    copy needs to transform identically. Cosmetic params (column names,
    k, ...) don't change the served output and are excluded so they don't
    fragment the daemon registry."""
    names = getattr(core_model, "_serve_params", ())
    return {n: core_model.getOrDefault(n) for n in names}


def _model_fingerprint(core_model) -> str:
    """Content hash of the fitted arrays + serving params: the daemon
    registry key. Two models with identical fits share a served copy;
    a refit under the same uid gets a fresh one."""
    import hashlib

    h = hashlib.md5()
    for k, v in sorted(core_model._model_data().items()):
        h.update(k.encode())
        if v is not None:
            h.update(np.ascontiguousarray(v).tobytes())
    for k, v in sorted(_scalar_params(core_model).items()):
        h.update(f"{k}={v!r}".encode())
    return h.hexdigest()[:12]


def _arrow_kind_type(kind):
    import pyarrow as pa

    return {
        "vec": pa.list_(pa.float64()),
        "ivec": pa.list_(pa.int64()),
        "int": pa.int32(),
        "double": pa.float64(),
    }[kind]


def _output_column(vals, kind, n_rows):
    """Build one canonical output column: the declared mapInArrow schema
    (vec → list<float64>, ivec → list<int64>, int → int32, double →
    float64) must hold regardless of the compute dtype the transform ran
    in."""
    import pyarrow as pa

    if n_rows == 0:
        return pa.array([], _arrow_kind_type(kind))
    if vals is None:
        raise RuntimeError(
            "daemon transform returned no array for a declared output role "
            "(client/daemon version skew?) — upgrade the daemon or set "
            "SRML_TRANSFORM_LOCAL=1 to score executor-side"
        )
    vals = np.asarray(vals)
    if kind in ("vec", "ivec"):
        from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

        dt = np.float64 if kind == "vec" else np.int64
        col = matrix_to_list_column(vals.astype(dt))
        return col.cast(_arrow_kind_type(kind))
    if kind == "int":
        return pa.array(vals.astype(np.int32))
    return pa.array(vals.astype(np.float64))


def _derive_output_schema(dataset, outputs):
    """Output schema = input schema + declared output fields, computed
    WITHOUT running a Spark job (the round-2 review flagged the old
    limit(1) probe as one job per transform call). Duck-typed test
    harnesses have no StructType schema — they ignore the argument."""
    try:
        from pyspark.sql import types as T

        base = dataset.schema
    except (ImportError, AttributeError):
        return None
    out_names = {name for _, name, _ in outputs}
    fields = [f for f in base.fields if f.name not in out_names]
    spark_types = {
        "vec": lambda: T.ArrayType(T.DoubleType()),
        "ivec": lambda: T.ArrayType(T.LongType()),
        "int": T.IntegerType,
        "double": T.DoubleType,
    }
    for _, name, kind in outputs:
        fields.append(T.StructField(name, spark_types[kind](), True))
    return T.StructType(fields)


def _append_outputs(table, role_arrays, outputs):
    """Append/replace the model's output columns on one batch table."""
    for role, colname, kind in outputs:
        if colname in table.column_names:
            table = table.drop_columns([colname])
        table = table.append_column(
            colname, _output_column(role_arrays.get(role), kind, table.num_rows)
        )
    return table


class _TransformTask:
    """Executor-side (CPU) batch transform — the EXPLICIT fallback when no
    daemon should be used (SRML_TRANSFORM_LOCAL=1). Pickle-able: the
    model's fitted arrays ride the closure to each task, resident for the
    task's lifetime — no per-batch re-upload (fixes rapidsml_jni.cu:85),
    but the compute runs on the executor's host backend, not the TPU."""

    def __init__(self, core_model, input_col, outputs):
        self._core = core_model
        self._input_col = input_col
        self._outputs = outputs

    def __call__(self, batches):
        import pyarrow as pa

        from spark_rapids_ml_tpu.core.dataset import as_matrix

        for batch in batches:
            table = pa.Table.from_batches([batch])
            if table.num_rows == 0:
                yield from _append_outputs(table, {}, self._outputs).to_batches()
                continue
            x = as_matrix(table, self._input_col)
            outs = self._core.transform_matrix(x)
            yield from _append_outputs(table, outs, self._outputs).to_batches()


class _DaemonTransformTask:
    """Executor-side feeder for TPU-served transform: batches stream to
    the data-plane daemon's ``transform`` op and the projected columns
    come back — the reference's accelerator-resident columnar UDF
    (RapidsPCA.scala:128-161 → rapidsml_jni.cu:75-107), with the model
    registered once (ensure_model) and device-resident across batches.
    Only the features column crosses the wire; passthrough columns never
    leave the executor."""

    def __init__(self, core_model, host, port, token, input_col, algo, outputs):
        self._core = core_model  # fitted arrays ride the closure (jit caches strip)
        self.host, self.port, self.token = host, port, token
        self._input_col = input_col
        self._algo = algo
        self._outputs = outputs
        self._name = f"{core_model.uid}-{_model_fingerprint(core_model)}"
        self._params = _scalar_params(core_model)

    def __call__(self, batches):
        import pyarrow as pa

        from spark_rapids_ml_tpu.serve.client import DataPlaneClient
        from spark_rapids_ml_tpu.spark import daemon_session as ds

        h, p = ds.executor_daemon_address(self.host, self.port)
        with DataPlaneClient(h, p, token=self.token, **ds.client_kwargs()) as c:
            registered = c.model_exists(self._name)
            for batch in batches:
                table = pa.Table.from_batches([batch])
                if table.num_rows == 0:
                    yield from _append_outputs(table, {}, self._outputs).to_batches()
                    continue
                if not registered:
                    c.ensure_model(
                        self._name, self._algo, self._core._model_data(),
                        params=self._params,
                    )
                    registered = True
                try:
                    outs = c.transform(
                        self._name,
                        table.select([self._input_col]),
                        input_col=self._input_col,
                    )
                except RuntimeError as e:
                    if "no such model" not in str(e):
                        raise
                    # Registrations are stateless and TTL-evictable; the
                    # documented recovery (docs/protocol.md) is to
                    # re-register and retry — the task has everything.
                    c.ensure_model(
                        self._name, self._algo, self._core._model_data(),
                        params=self._params,
                    )
                    outs = c.transform(
                        self._name,
                        table.select([self._input_col]),
                        input_col=self._input_col,
                    )
                yield from _append_outputs(table, outs, self._outputs).to_batches()


_KNN_OUTPUTS = (
    ("distances", "knn_distances", "vec"),
    ("indices", "knn_indices", "ivec"),
)


def _fanout_kneighbors(ex, shard_clients, name, queries, k, input_col,
                       descending):
    """Query every shard daemon concurrently and merge top-k — the ONE
    implementation both the executor task and the driver handle use.
    ``ex``: a ThreadPoolExecutor (caller-owned, reusable across batches);
    ``shard_clients``: [((addr, shard_rows), client)] with one client per
    shard (no socket sharing across threads). Per-batch latency is the
    slowest shard, not the sum."""
    from spark_rapids_ml_tpu.models.knn import merge_topk

    def one(entry):
        (_addr, n_shard), c = entry
        return c.kneighbors(name, queries, k=min(k, n_shard),
                            input_col=input_col)

    results = list(ex.map(one, shard_clients))
    return merge_topk(
        [d for d, _ in results], [i for _, i in results], k,
        descending=descending,
    )


class _DaemonKNNTask:
    """Executor-side query feeder: each batch's query rows go to the
    daemon's ``kneighbors`` op; neighbor distance/index columns come
    back. The database-sized index stays daemon-resident.

    Sharded index (``shards``: [(addr, shard_rows)]): the batch fans out
    to EVERY shard daemon and the task merges the per-shard top-k
    host-side (models/knn.merge_topk) — O(q·k·shards) merged per batch,
    independent of database size."""

    def __init__(self, host, port, token, name, input_col, k,
                 shards=None, descending=False):
        self.host, self.port, self.token = host, port, token
        self._name = name
        self._input_col = input_col
        self._k = k
        self._shards = shards
        self._descending = descending

    def __call__(self, batches):
        import contextlib
        from concurrent.futures import ThreadPoolExecutor

        import pyarrow as pa

        from spark_rapids_ml_tpu.serve.client import DataPlaneClient
        from spark_rapids_ml_tpu.spark import daemon_session as ds

        with contextlib.ExitStack() as stack:
            ckw = ds.client_kwargs()
            if self._shards:
                clients = [
                    (s, stack.enter_context(DataPlaneClient(
                        *ds._parse_addr(s[0]), token=self.token, **ckw)))
                    for s in self._shards
                ]
                # One pool for the task's lifetime (threads reused across
                # batches, like the clients above).
                ex = stack.enter_context(
                    ThreadPoolExecutor(max_workers=min(len(clients), 16))
                )
            else:
                h, p = ds.executor_daemon_address(self.host, self.port)
                clients = [
                    ((f"{h}:{p}", None), stack.enter_context(
                        DataPlaneClient(h, p, token=self.token, **ckw)))
                ]
            for batch in batches:
                table = pa.Table.from_batches([batch])
                if table.num_rows == 0:
                    yield from _append_outputs(table, {}, _KNN_OUTPUTS).to_batches()
                    continue
                q = table.select([self._input_col])
                if self._shards:
                    dists, idx = _fanout_kneighbors(
                        ex, clients, self._name, q, self._k,
                        self._input_col, self._descending,
                    )
                else:
                    dists, idx = clients[0][1].kneighbors(
                        self._name, q, k=self._k, input_col=self._input_col
                    )
                out = {"distances": dists, "indices": idx}
                yield from _append_outputs(table, out, _KNN_OUTPUTS).to_batches()


class _DaemonKNNModel:
    """Fitted KNN/ANN handle whose index lives ON the TPU-host daemon.

    The reference never materializes the dataset on the driver
    (RapidsRowMatrix.scala:118-139); for KNN the fitted model IS the
    dataset, so driver-side persistence is structurally impossible at
    config-#5 scale (10M×768 ≈ 31 GB) — queries are served remotely
    instead. Use the core (non-Spark) API for an in-memory, persistable
    index."""

    def __init__(self, core, host, port, token, name, n_rows, input_col,
                 shards=None, client_kw=None):
        self._core = core  # the estimator: param surface (k, featuresCol…)
        self._host, self._port, self._token = host, port, token
        self._name = name
        self._n_rows = n_rows
        self._input_col = input_col
        # [(addr, shard_rows)] when the index spans daemons (each daemon
        # serves the shard of ITS committed partitions); None = one daemon.
        self._shards = shards
        # Fit-time resilience tuning (spark conf + env, resolved by
        # _fit_knn): the handle has no spark session at query time, so
        # driver-side kneighbors/release reuse what the fit resolved —
        # the same capture pattern as host/port/token.
        self._client_kw = dict(client_kw or {})

    def __getattr__(self, name):
        return getattr(self._core, name)

    @property
    def daemon_model_name(self) -> str:
        return self._name

    @property
    def numRows(self) -> int:
        return self._n_rows

    @property
    def shards(self):
        """[(daemon address, rows served there)] for a cross-daemon
        sharded index; None when one daemon serves the whole database."""
        return None if self._shards is None else list(self._shards)

    def _descending(self) -> bool:
        return (
            self._core.hasParam("metric")
            and self._core.getOrDefault("metric") == "inner_product"
        )

    def kneighbors(self, queries, k=None):
        """Driver-side convenience for ndarray queries: (distances (q, k),
        indices (q, k)); indices are global partition-major row positions
        of the fitted DataFrame. A sharded index fans the batch to every
        shard daemon and merges top-k (exact given exact shard answers —
        models/knn.merge_topk)."""
        from spark_rapids_ml_tpu.serve.client import DataPlaneClient

        if _is_spark_df(queries):
            raise TypeError(
                "pass a DataFrame to transform() for distributed queries; "
                "kneighbors takes an (q, d) ndarray"
            )
        k = self._core.getOrDefault("k") if k is None else k
        queries = np.asarray(queries)
        ckw = self._client_kw
        if self._shards is None:
            with DataPlaneClient(self._host, self._port,
                                 token=self._token, **ckw) as c:
                return c.kneighbors(
                    self._name, queries, k=k, input_col=self._input_col
                )
        import contextlib
        from concurrent.futures import ThreadPoolExecutor

        with contextlib.ExitStack() as stack:
            clients = [
                (s, stack.enter_context(DataPlaneClient(
                    *daemon_session._parse_addr(s[0]), token=self._token,
                    **ckw)))
                for s in self._shards
            ]
            ex = stack.enter_context(
                ThreadPoolExecutor(max_workers=min(len(clients), 16))
            )
            return _fanout_kneighbors(
                ex, clients, self._name, queries, k, self._input_col,
                self._descending(),
            )

    def transform(self, dataset):
        """Distributed query: appends knn_distances (list<double>) and
        knn_indices (list<long>) columns via mapInArrow tasks that hit
        the daemon — no index download, no driver collect."""
        if not _is_spark_df(dataset):
            dists, idx = self.kneighbors(
                __import__(
                    "spark_rapids_ml_tpu.core.dataset", fromlist=["as_matrix"]
                ).as_matrix(dataset, self._input_col)
            )
            from spark_rapids_ml_tpu.core.dataset import with_column

            out = with_column(dataset, "knn_distances", dists)
            return with_column(out, "knn_indices", idx)
        fn = _DaemonKNNTask(
            self._host, self._port, self._token, self._name,
            self._input_col, self._core.getOrDefault("k"),
            shards=self._shards, descending=self._descending(),
        )
        return dataset.mapInArrow(
            fn, _derive_output_schema(dataset, _KNN_OUTPUTS)
        )

    def release(self) -> bool:
        """Free the daemon-resident index now (it is dataset-sized and
        otherwise held until the daemon's extended KNN TTL; a sharded
        index frees every shard). The handle is unusable afterwards."""
        from spark_rapids_ml_tpu.serve.client import DataPlaneClient

        addrs = (
            [f"{self._host}:{self._port}"] if self._shards is None
            else [a for a, _ in self._shards]
        )
        any_dropped = False
        for addr in addrs:
            try:
                h, p = daemon_session._parse_addr(addr)
                with DataPlaneClient(h, p, token=self._token,
                                     **self._client_kw) as c:
                    any_dropped = c.drop_model(self._name) or any_dropped
            except OSError:
                continue  # daemon already gone — nothing to free there
        return any_dropped

    def write(self):
        raise NotImplementedError(
            "a daemon-resident KNN index is dataset-sized and cannot be "
            "persisted from the driver; fit the core "
            "(spark_rapids_ml_tpu.NearestNeighbors / "
            "ApproximateNearestNeighbors) estimator on in-memory data for "
            "a persistable model"
        )


class _SparkModelAdapter:
    """Wraps a fitted core Model with Spark DataFrame transform."""

    def __init__(self, core_model):
        self._core = core_model

    def __getattr__(self, name):
        return getattr(self._core, name)

    def _transform_input_col(self):
        core = self._core
        return core.getOrDefault(
            "inputCol" if core.hasParam("inputCol") else "featuresCol"
        )

    def _derive_output_schema(self, dataset, outputs):
        return _derive_output_schema(dataset, outputs)

    def transform(self, dataset):
        if not _is_spark_df(dataset):
            _check_not_orphan_spark_df(dataset)
            return self._core.transform(dataset)
        import os

        core = self._core
        spec = _serve_spec(core)

        if hasattr(dataset, "mapInArrow") and spec is not None:
            # Distributed, lazy: one Arrow batch per executor partition —
            # served from the TPU via the daemon unless the explicit
            # executor-CPU fallback is requested.
            algo, outputs = spec
            input_col = self._transform_input_col()
            local = os.environ.get("SRML_TRANSFORM_LOCAL", "").lower() in (
                "1", "true",
            )
            if local:
                fn = _TransformTask(core, input_col, outputs)
            else:
                spark = getattr(dataset, "sparkSession", None)
                host, port, token = daemon_session.resolve(spark)
                fn = _DaemonTransformTask(
                    core, host, port, token, input_col, algo, outputs
                )
            return dataset.mapInArrow(
                fn, self._derive_output_schema(dataset, outputs)
            )

        # No collect-based fallback: every Spark code path must keep the
        # dataset off the driver (the reference's defining property,
        # RapidsRowMatrix.scala:118-139). mapInArrow exists since
        # pyspark 3.3; models without a serving contract have no Spark
        # transform at all.
        raise NotImplementedError(
            "distributed transform needs DataFrame.mapInArrow (pyspark "
            ">= 3.3) and a model with a serving contract; for in-memory "
            "data use the core estimators (spark_rapids_ml_tpu.*) directly"
        )


def _make_wrapper(name, core_cls, doc, daemon_algo=None):
    cls = type(
        name,
        (_SparkAdapter,),
        {"_core_cls": core_cls, "__doc__": doc, "_daemon_algo": daemon_algo},
    )
    return cls


from spark_rapids_ml_tpu.models.kmeans import KMeans as _KMeans
from spark_rapids_ml_tpu.models.knn import (
    ApproximateNearestNeighbors as _ApproximateNearestNeighbors,
    NearestNeighbors as _NearestNeighbors,
)
from spark_rapids_ml_tpu.models.linear_regression import (
    LinearRegression as _LinearRegression,
)
from spark_rapids_ml_tpu.models.logistic_regression import (
    LogisticRegression as _LogisticRegression,
)
from spark_rapids_ml_tpu.models.pca import PCA as _PCA
from spark_rapids_ml_tpu.models.random_forest import (
    RandomForestClassifier as _RandomForestClassifier,
    RandomForestRegressor as _RandomForestRegressor,
)
from spark_rapids_ml_tpu.models.scaler import StandardScaler as _StandardScaler

SparkPCA = _make_wrapper(
    "SparkPCA", _PCA, "PCA over PySpark DataFrames (ArrayType features column).",
    daemon_algo="pca",
)
SparkKMeans = _make_wrapper(
    "SparkKMeans", _KMeans, "KMeans over PySpark DataFrames.",
    daemon_algo="kmeans",
)
SparkLinearRegression = _make_wrapper(
    "SparkLinearRegression", _LinearRegression,
    "LinearRegression over PySpark DataFrames.", daemon_algo="linreg",
)
SparkLogisticRegression = _make_wrapper(
    "SparkLogisticRegression", _LogisticRegression,
    "LogisticRegression over PySpark DataFrames.", daemon_algo="logreg",
)
SparkNearestNeighbors = _make_wrapper(
    "SparkNearestNeighbors", _NearestNeighbors,
    "Exact KNN over PySpark DataFrames — daemon-fed fit, daemon-served "
    "queries (the dataset never reaches the driver).",
    daemon_algo="knn",
)
SparkApproximateNearestNeighbors = _make_wrapper(
    "SparkApproximateNearestNeighbors",
    _ApproximateNearestNeighbors,
    "IVF-Flat approximate KNN over PySpark DataFrames — daemon-fed fit "
    "(device-side quantizer + bucketize), daemon-served queries.",
    daemon_algo="knn",
)
SparkStandardScaler = _make_wrapper(
    "SparkStandardScaler", _StandardScaler,
    "StandardScaler over PySpark DataFrames (ArrayType features column).",
    daemon_algo="scaler",
)
SparkRandomForestClassifier = _make_wrapper(
    "SparkRandomForestClassifier", _RandomForestClassifier,
    "RandomForest classification over PySpark DataFrames — histogram "
    "trees on binned features, one daemon pass per depth (the `rf` job "
    "protocol).",
    daemon_algo="rf_classifier",
)
SparkRandomForestRegressor = _make_wrapper(
    "SparkRandomForestRegressor", _RandomForestRegressor,
    "RandomForest regression over PySpark DataFrames — variance-split "
    "histogram trees on binned features (the `rf` job protocol).",
    daemon_algo="rf_regressor",
)
