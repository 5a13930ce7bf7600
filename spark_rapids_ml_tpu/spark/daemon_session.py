"""Daemon resolution for Spark-driven fits.

Who runs the data-plane daemon depends on the deployment:

* **Cluster**: each TPU host runs one ``DataPlaneDaemon`` (one process owns
  the host's chips, like the reference's one-GPU-per-executor resource
  model, README.md:110-113). The driver learns the primary address from
  ``spark.srml.daemon.address`` / ``$SRML_DAEMON_ADDRESS`` and ships it to
  tasks; an executor colocated with a *different* TPU host overrides the
  target with its OWN host's daemon via the executor-local
  ``$SRML_DAEMON_ADDRESS`` (the executor→local-host routing rule — row
  data flows executor → nearest TPU host). At finalize the driver pulls
  each peer daemon's O(d²) partials (``export_state``) and folds them
  into the primary (``merge_state``) — the cross-daemon reduce that
  makes the Spark-fed fit span hosts (the any-number-of-executors
  ``RDD.reduce`` property, RapidsRowMatrix.scala:139); iterative fits
  sync the Lloyd/Newton iterate back out with ``get_iterate``/
  ``set_iterate`` at every pass boundary (spark/estimator.py). KMeans
  needs the full daemon set up front (centers must be seeded before the
  first scan): list it in ``spark.srml.daemon.addresses`` /
  ``$SRML_DAEMON_ADDRESSES`` (comma-separated; other algorithms discover
  peers from the task acks and need no list). Every daemon address must
  be reachable from BOTH its executors and the driver.
* **Local / tests**: nothing configured — the driver starts one in-process
  daemon, shared across fits (jit caches stay warm), torn down at exit.

An optional shared-secret token (``spark.srml.daemon.token`` /
``$SRML_DAEMON_TOKEN``) is checked by the daemon on every op.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional, Tuple

_lock = threading.Lock()
_owned_daemon = None  # in-process daemon for local mode


def _spark_conf_get(spark, key: str) -> Optional[str]:
    try:
        return spark.conf.get(key)
    except Exception:
        return None


def _parse_addr(addr: str) -> Tuple[str, int]:
    host, sep, port = addr.rpartition(":")
    if not sep or not port.isdigit():
        raise ValueError(
            f"daemon address {addr!r} must be 'host:port' (e.g. "
            "'tpu-host-0:9747')"
        )
    return host or "127.0.0.1", int(port)


def resolve(spark=None) -> Tuple[str, int, Optional[str]]:
    """Return (host, port, token) of the daemon this driver should use,
    starting an in-process one if nothing is configured."""
    addr = os.environ.get("SRML_DAEMON_ADDRESS")
    if not addr and spark is not None:
        addr = _spark_conf_get(spark, "spark.srml.daemon.address")
    token = os.environ.get("SRML_DAEMON_TOKEN")
    if token is None and spark is not None:
        token = _spark_conf_get(spark, "spark.srml.daemon.token")
    if addr:
        return (*_parse_addr(addr), token)
    return (*_local_daemon().address, token)


def client_kwargs(spark=None) -> dict:
    """Resilience tuning for every data-plane client a Spark fit or
    transform creates — how the Spark layer honors the daemon's
    backpressure/healing contract (docs/protocol.md "Client retry
    obligations"). Sources, env first then Spark conf:

    * ``$SRML_DAEMON_TIMEOUT_S`` / ``spark.srml.daemon.timeout_s`` —
      per-socket-syscall timeout (default 120 s).
    * ``$SRML_DAEMON_OP_DEADLINE_S`` / ``spark.srml.daemon.op_deadline_s``
      — per-op healing deadline: total time one op may spend across
      reconnects, replays, and honored `busy` retry_after_s waits before
      the failure surfaces to Spark's own task retry.
    * ``$SRML_DAEMON_OP_ATTEMPTS`` / ``spark.srml.daemon.op_attempts`` —
      reconnect attempts per op.

    Unset keys are omitted so the client's defaults rule. Executors call
    this with ``spark=None`` (env only — the executor's env, like the
    ``$SRML_DAEMON_ADDRESS`` routing rule)."""

    def _get(env_name: str, conf_key: str) -> Optional[str]:
        v = os.environ.get(env_name)
        if v is None and spark is not None:
            v = _spark_conf_get(spark, conf_key)
        return v

    out: dict = {}
    t = _get("SRML_DAEMON_TIMEOUT_S", "spark.srml.daemon.timeout_s")
    if t:
        out["timeout"] = float(t)
    d = _get("SRML_DAEMON_OP_DEADLINE_S", "spark.srml.daemon.op_deadline_s")
    if d:
        out["op_deadline_s"] = float(d)
    a = _get("SRML_DAEMON_OP_ATTEMPTS", "spark.srml.daemon.op_attempts")
    if a:
        out["max_op_attempts"] = int(a)
    return out


def recovery_attempts(spark=None) -> int:
    """Fit-level pass-replay budget (spark/estimator.py "Crash recovery"):
    how many times one pass-boundary unit may be replayed after a daemon
    incarnation change before the failure surfaces. 0 (the default) =
    recovery off — a daemon restart mid-fit fails loudly. Sources, env
    first then Spark conf then config: ``$SRML_FIT_RECOVERY_ATTEMPTS`` /
    ``spark.srml.fit.recovery_attempts`` /
    ``config "fit_recovery_attempts"``."""
    sources = [("$SRML_FIT_RECOVERY_ATTEMPTS",
                os.environ.get("SRML_FIT_RECOVERY_ATTEMPTS"))]
    if spark is not None:
        sources.append((
            "spark.srml.fit.recovery_attempts",
            _spark_conf_get(spark, "spark.srml.fit.recovery_attempts"),
        ))
    for src, v in sources:
        if v is None:
            continue
        try:
            return max(int(v), 0)
        except (TypeError, ValueError):
            # A typo'd value must not SILENTLY disable the crash
            # recovery the operator explicitly configured: warn and
            # fall through to the next source.
            from spark_rapids_ml_tpu.utils.logging import get_logger

            get_logger("spark.daemon_session").warning(
                "ignoring invalid fit recovery attempts %r from %s "
                "(want a non-negative integer)", v, src,
            )
    from spark_rapids_ml_tpu import config

    try:
        return max(int(config.get("fit_recovery_attempts")), 0)
    except (TypeError, ValueError):
        return 0


def _env_conf_config(spark, env_name: str, conf_key: str, config_key: str,
                     cast, floor=None):
    """Shared resolution ladder for fit-policy knobs (the
    ``recovery_attempts`` pattern): env, then Spark conf, then the
    process config default. A typo'd value warns and falls through —
    it must never SILENTLY disable a policy the operator configured."""
    sources = [(f"${env_name}", os.environ.get(env_name))]
    if spark is not None:
        sources.append((conf_key, _spark_conf_get(spark, conf_key)))
    for src, v in sources:
        if v is None:
            continue
        try:
            v = cast(v)
            return v if floor is None else max(v, floor)
        except (TypeError, ValueError):
            from spark_rapids_ml_tpu.utils.logging import get_logger

            get_logger("spark.daemon_session").warning(
                "ignoring invalid %s value %r from %s", config_key, v, src,
            )
    from spark_rapids_ml_tpu import config

    try:
        v = cast(config.get(config_key))
        return v if floor is None else max(v, floor)
    except (TypeError, ValueError):
        return floor if floor is not None else cast(0)


def pass_cache_mb(spark=None) -> int:
    """Whether this fit asks its daemons for cached passes
    (spark/estimator.py; docs/protocol.md "rescan"), as the budget the
    daemons were given: MiB per device, 0 (the default) = off — the fit
    sends the parent's ops and not one more. The daemon holds the budget
    itself (its own ``daemon_pass_cache_mb``); a fit that asks a daemon
    without one is told ``cached: false`` and re-feeds. Sources:
    ``$SRML_DAEMON_PASS_CACHE_MB`` / ``spark.srml.daemon.pass_cache_mb``
    / ``config "daemon_pass_cache_mb"``."""
    return _env_conf_config(
        spark, "SRML_DAEMON_PASS_CACHE_MB",
        "spark.srml.daemon.pass_cache_mb",
        "daemon_pass_cache_mb", int, floor=0,
    )


def daemon_loss_tolerance(spark=None) -> int:
    """Elastic-fit death budget (spark/estimator.py; docs/protocol.md
    "Permanent daemon loss"): how many peer daemons one fit may declare
    permanently dead and amputate. 0 (the default) = elastic degrade
    off — a lost daemon fails the fit loudly, and no classification
    probe ever runs. Sources, env first then Spark conf then config:
    ``$SRML_FIT_DAEMON_LOSS_TOLERANCE`` /
    ``spark.srml.fit.daemon_loss_tolerance`` /
    ``config "fit_daemon_loss_tolerance"``."""
    return _env_conf_config(
        spark, "SRML_FIT_DAEMON_LOSS_TOLERANCE",
        "spark.srml.fit.daemon_loss_tolerance",
        "fit_daemon_loss_tolerance", int, floor=0,
    )


def daemon_death_timeout_s(spark=None) -> float:
    """The death deadline: the TOTAL reconnect/healing budget a peer
    implicated in a failed pass gets on its liveness probe before it
    escalates from *retrying* to *declared dead*. Sources:
    ``$SRML_FIT_DAEMON_DEATH_TIMEOUT_S`` /
    ``spark.srml.fit.daemon_death_timeout_s`` /
    ``config "fit_daemon_death_timeout_s"``."""
    return _env_conf_config(
        spark, "SRML_FIT_DAEMON_DEATH_TIMEOUT_S",
        "spark.srml.fit.daemon_death_timeout_s",
        "fit_daemon_death_timeout_s", float, floor=0.1,
    )


def daemon_join_policy(spark=None) -> str:
    """Elastic-fit GROW policy (spark/estimator.py; docs/protocol.md
    "Mid-fit daemon join"): whether a daemon that appears mid-fit may be
    admitted into a running fit. ``off`` (the default) keeps the
    unlisted-peer loud rejection byte-for-byte and runs no discovery
    probe; ``boundary`` admits new daemons at the next pass boundary
    only, seeded from the recovery ledger. An unrecognized value warns
    and reads as ``off`` — a typo must not silently open the admission
    door. Sources: ``$SRML_FIT_DAEMON_JOIN_POLICY`` /
    ``spark.srml.fit.daemon_join_policy`` /
    ``config "fit_daemon_join_policy"``."""

    def _policy(v) -> str:
        v = str(v).strip().lower()
        if v not in ("off", "boundary"):
            raise ValueError(v)
        return v

    try:
        return _env_conf_config(
            spark, "SRML_FIT_DAEMON_JOIN_POLICY",
            "spark.srml.fit.daemon_join_policy",
            "fit_daemon_join_policy", _policy, floor=None,
        )
    except (TypeError, ValueError):
        # Every source (including the config default's last-resort
        # cast) was invalid — admission stays closed.
        return "off"


def daemon_join_limit(spark=None) -> int:
    """The join budget: how many daemons one fit may admit mid-fit
    before a further newcomer fails the fit loudly (the
    ``daemon_loss_tolerance`` contract, mirrored for growth). Sources:
    ``$SRML_FIT_DAEMON_JOIN_LIMIT`` /
    ``spark.srml.fit.daemon_join_limit`` /
    ``config "fit_daemon_join_limit"``."""
    return _env_conf_config(
        spark, "SRML_FIT_DAEMON_JOIN_LIMIT",
        "spark.srml.fit.daemon_join_limit",
        "fit_daemon_join_limit", int, floor=0,
    )


def resolve_all(spark=None) -> list:
    """The full daemon set for fits that must know every peer BEFORE the
    first scan (kmeans: centers are seeded on all daemons up front).
    Parsed from ``$SRML_DAEMON_ADDRESSES`` / ``spark.srml.daemon.addresses``
    (comma-separated host:port). Empty when unconfigured — single-pass
    algorithms then discover peers from task acks instead."""
    addrs = os.environ.get("SRML_DAEMON_ADDRESSES")
    if not addrs and spark is not None:
        addrs = _spark_conf_get(spark, "spark.srml.daemon.addresses")
    if not addrs:
        return []
    return [_parse_addr(a.strip()) for a in addrs.split(",") if a.strip()]


def fleet_seeds(spark=None) -> list:
    """Seed addresses for the gossiped-fleet bootstrap
    (``router.bootstrap_table`` — ONE reachable seed is enough; the
    seed's FleetView names the rest). The config/env/Spark-conf ladder:
    ``$SRML_FLEET_SEED_ADDRESSES`` / ``spark.srml.fleet.seed_addresses``
    / ``config "fleet_seed_addresses"`` (comma-separated host:port).
    Empty when unconfigured."""
    from spark_rapids_ml_tpu import config

    addrs = os.environ.get("SRML_FLEET_SEED_ADDRESSES")
    if not addrs and spark is not None:
        addrs = _spark_conf_get(spark, "spark.srml.fleet.seed_addresses")
    if not addrs:
        addrs = config.get("fleet_seed_addresses")
    if not addrs:
        return []
    return [a.strip() for a in str(addrs).split(",") if a.strip()]


def _local_daemon():
    global _owned_daemon
    with _lock:
        if _owned_daemon is None:
            from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon

            _owned_daemon = DataPlaneDaemon(ttl=3600.0).start()
            atexit.register(shutdown)
        return _owned_daemon


def shutdown() -> None:
    """Stop the in-process daemon (idempotent)."""
    global _owned_daemon
    with _lock:
        d, _owned_daemon = _owned_daemon, None
    if d is not None:
        d.stop()


def task_context() -> Tuple[int, int]:
    """(partition_id, attempt) for the CURRENT task, executor-side.

    Uses pyspark's TaskContext when running inside a real executor;
    otherwise falls back to ``$SRML_PARTITION_ID`` / ``$SRML_ATTEMPT``
    (set by non-Spark task runners, e.g. the test harness)."""
    try:
        from pyspark import TaskContext

        ctx = TaskContext.get()
        if ctx is not None:
            return int(ctx.partitionId()), int(ctx.attemptNumber())
    except ImportError:
        pass
    return (
        int(os.environ.get("SRML_PARTITION_ID", "0")),
        int(os.environ.get("SRML_ATTEMPT", "0")),
    )


def executor_daemon_address(default_host: str, default_port: int) -> Tuple[str, int]:
    """Executor-side routing rule: a task feeds ITS host's daemon when the
    executor env names one, else the driver-resolved address."""
    addr = os.environ.get("SRML_DAEMON_ADDRESS")
    if addr:
        return _parse_addr(addr)
    return default_host, default_port
