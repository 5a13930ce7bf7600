"""sparksim — a test double for the PySpark DataFrame surface the Spark
wrappers use, with REAL task isolation.

pyspark cannot be installed in this environment (no package installs; see
README "Spark integration testing" for the policy), so the integration
tests execute the wrappers' executor-side closures through this harness
instead. It is deliberately NOT a mock: partition tasks run in separate
OS processes (spawned, nothing shared with the driver), get their task
identity the same way a real executor does (``SRML_PARTITION_ID`` /
``SRML_ATTEMPT`` — the documented fallback of
spark.daemon_session.task_context), talk to the daemon over real TCP, and
are retried on failure with a bumped attempt number exactly like Spark's
at-least-once task scheduler. Failure injection (die after N feeds) and
duplicate/speculative execution are first-class so the exactly-once
commit protocol is exercised the way Spark would exercise it.

Surface implemented (what spark/estimator.py touches):
``sparkSession.conf.get``, ``select``, ``limit``, ``persist``/
``unpersist``, ``columns``, ``toArrow``, ``mapInArrow(fn, schema)`` +
``collect``, ``count``. Rows returned by ``collect`` support ``row[key]``.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa

_FORK_CTX = None


def _task_mp_context():
    """Forkserver with the heavy imports preloaded: each simulated task
    still gets a fresh OS process (nothing shared with the driver), but
    forks from a template that already paid the ~3 s jax/pyarrow import —
    the round-2 review measured the per-task import tax as the dominant
    cost of this suite (445 s for 10 tests)."""
    global _FORK_CTX
    if _FORK_CTX is None:
        ctx = mp.get_context("forkserver")
        ctx.set_forkserver_preload(
            [
                "numpy",
                "pyarrow",
                "jax",
                "spark_rapids_ml_tpu",
                "spark_rapids_ml_tpu.spark.estimator",
                "spark_rapids_ml_tpu.serve.client",
            ]
        )
        _FORK_CTX = ctx
    return _FORK_CTX


class SimRow(dict):
    """Row supporting row["col"] and row.col."""

    __getattr__ = dict.__getitem__


class _SimConf:
    def __init__(self, conf: Dict[str, str]):
        self._conf = dict(conf)

    def get(self, key: str, default=None):
        if key in self._conf:
            return self._conf[key]
        if default is not None:
            return default
        raise KeyError(key)

    def set(self, key: str, value: str):
        self._conf[key] = value


class SimSparkSession:
    def __init__(self, conf: Optional[Dict[str, str]] = None):
        self.conf = _SimConf(conf or {})
        # rows shipped driver-side via toArrow/toPandas/plain collect —
        # the "no collect-to-driver" assertions read this
        self.driver_rows_materialized = 0


def _dying_iter(batches, fail_after):
    """Deliver ``fail_after`` batches, then die MID-ITERATION — the way a
    real executor loss looks to the task body: the feed loop has staged
    rows at the daemon and never reaches its commit."""
    for i, b in enumerate(batches):
        if i >= fail_after:
            raise RuntimeError("injected executor death mid-partition")
        yield b
    raise RuntimeError("injected executor death at partition end")


def _run_task(fn, batches, pid, attempt, fail_after, out_q, env=None):
    """Worker-process entry: impersonate one Spark task.

    ``env``: driver-side SRML_*/JAX_* snapshot taken at task LAUNCH.
    Forkserver children freeze os.environ at forkserver start (unlike
    spawn), so without this pass-through a test's monkeypatched executor
    env var (e.g. SRML_DAEMON_ADDRESS) would silently not reach tasks —
    and a var UNSET driver-side must be unset here too, or a frozen
    template value leaks into later tests (order-dependent greens)."""
    env = env or {}
    for k in list(os.environ):
        if k.startswith(("SRML_", "JAX_")) and k not in env:
            del os.environ[k]
    for k, v in env.items():
        os.environ[k] = v
    os.environ["SRML_PARTITION_ID"] = str(pid)
    os.environ["SRML_ATTEMPT"] = str(attempt)
    # Tests run on the CPU: worker-side transforms must use the same
    # (virtual CPU) backend as the test session.
    import jax

    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
    if os.environ.get("JAX_ENABLE_X64", "").lower() in ("true", "1"):
        jax.config.update("jax_enable_x64", True)
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    try:
        it = (
            _dying_iter(batches, fail_after)
            if fail_after is not None
            else iter(batches)
        )
        results = [b for b in fn(it)]
        out_q.put(("ok", pid, [b.to_pydict() for b in results]))
    except Exception as e:  # noqa: BLE001 — faithfully report any task death
        out_q.put(("err", pid, repr(e)))


class SimDataFrame:
    """An in-memory, partitioned DataFrame executing tasks in processes."""

    def __init__(
        self,
        partitions: Sequence[pa.Table],
        session: Optional[SimSparkSession] = None,
        fail_plan: Optional[Dict[int, List[Optional[int]]]] = None,
        speculative: Optional[Sequence[int]] = None,
        max_attempts: int = 4,
        env_plan: Optional[Dict[int, Dict[str, str]]] = None,
        concurrency: Optional[int] = None,
    ):
        self._parts = [
            p if isinstance(p, pa.Table) else pa.Table.from_batches([p])
            for p in partitions
        ]
        self.sparkSession = session or SimSparkSession()
        # fail_plan: partition -> list of per-attempt injections; entry i is
        # "fail after N batches" for attempt i (None = run to completion).
        self._fail_plan = fail_plan or {}
        # speculative: partitions to ALSO run a duplicate copy of after the
        # primary succeeds (Spark speculation: same partition, new attempt).
        self._speculative = list(speculative or [])
        self._max_attempts = max_attempts
        # env_plan: partition -> extra task env (models executors on
        # DIFFERENT hosts: e.g. a per-executor SRML_DAEMON_ADDRESS that
        # routes the task to its host-local daemon). A LIST value is
        # per-ATTEMPT env — attempt i gets entry min(i, last) — which
        # models Spark rescheduling a failed task onto a different host
        # (the elastic-fit suite reroutes a dead daemon's partitions to
        # survivors this way).
        self._env_plan = env_plan or {}
        # Partition tasks run CONCURRENTLY like Spark's scheduler (each
        # still its own OS process); retries stay sequential within a
        # partition. concurrency=1 restores strictly ordered commits —
        # the mode the float-data bitwise-determinism tests need, since
        # concurrent commit arrival reorders f32 folds exactly as real
        # Spark would.
        self._concurrency = (
            concurrency if concurrency is not None
            else min(4, max(1, len(self._parts)))
        )
        self._mapped: Optional[Callable] = None

    # -- the DataFrame surface the wrappers use ---------------------------

    @property
    def columns(self) -> List[str]:
        return list(self._parts[0].schema.names)

    def select(self, *cols) -> "SimDataFrame":
        out = SimDataFrame(
            [p.select(list(cols)) for p in self._parts],
            self.sparkSession,
            self._fail_plan,
            self._speculative,
            self._max_attempts,
            self._env_plan,
            self._concurrency,
        )
        return out

    def limit(self, n: int) -> "SimDataFrame":
        taken, remaining = [], n
        for p in self._parts:
            if remaining <= 0:
                break
            t = p.slice(0, min(remaining, p.num_rows))
            taken.append(t)
            remaining -= t.num_rows
        return SimDataFrame(taken or [self._parts[0].slice(0, 0)], self.sparkSession)

    def persist(self) -> "SimDataFrame":
        return self

    def unpersist(self) -> "SimDataFrame":
        return self

    def count(self) -> int:
        return sum(p.num_rows for p in self._parts)

    def toArrow(self) -> pa.Table:
        t = pa.concat_tables(self._parts)
        self.sparkSession.driver_rows_materialized += t.num_rows
        return t

    def toPandas(self):
        return self.toArrow().to_pandas()

    def mapInArrow(self, fn, schema) -> "SimDataFrame":
        out = SimDataFrame(
            self._parts, self.sparkSession, self._fail_plan,
            self._speculative, self._max_attempts, self._env_plan,
            self._concurrency,
        )
        out._mapped = fn
        return out

    def collect(self) -> List[SimRow]:
        if self._mapped is None:
            table = self.toArrow()
            return [SimRow(r) for r in table.to_pylist()]
        return self._run_tasks()

    # -- the task scheduler ------------------------------------------------

    def _run_tasks(self) -> List[SimRow]:
        ctx = _task_mp_context()
        results: List[Optional[List[SimRow]]] = [None] * len(self._parts)
        errors: List[BaseException] = []
        gate = threading.Semaphore(self._concurrency)

        def run_partition(pid: int, part: pa.Table) -> None:
            with gate:
                try:
                    batches = part.to_batches(
                        max_chunksize=max(1, part.num_rows // 2 or 1)
                    )
                    plan = self._fail_plan.get(pid, [])
                    result, last_err = None, None
                    for attempt in range(self._max_attempts):
                        fail_after = plan[attempt] if attempt < len(plan) else None
                        result, last_err = self._one_attempt(
                            ctx, pid, attempt, batches, fail_after
                        )
                        if result is not None:
                            break
                    if result is None:
                        # Spark's job-abort message carries the most recent
                        # task failure — the operator must see WHY (e.g. a
                        # peer daemon rejecting unseeded kmeans feeds), not
                        # just that attempts ran out.
                        raise RuntimeError(
                            f"partition {pid} failed {self._max_attempts} "
                            "attempts (Spark would abort the job here); "
                            f"most recent failure: {last_err}"
                        )
                    results[pid] = result
                    if pid in self._speculative:
                        # a speculative duplicate finishing AFTER the
                        # original — its output is discarded (Spark keeps
                        # the first winner), but its daemon traffic
                        # happens for real
                        self._one_attempt(ctx, pid, attempt + 1, batches, None)
                except BaseException as e:  # noqa: BLE001 — surface on main
                    errors.append(e)

        threads = [
            threading.Thread(target=run_partition, args=(pid, part))
            for pid, part in enumerate(self._parts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        rows: List[SimRow] = []
        for result in results:
            rows.extend(result or [])
        return rows

    def _one_attempt(self, ctx, pid, attempt, batches, fail_after):
        q = ctx.Queue()
        env = {
            k: v for k, v in os.environ.items()
            if k.startswith(("SRML_", "JAX_"))
        }
        extra = self._env_plan.get(pid, {})
        if isinstance(extra, (list, tuple)):
            extra = extra[min(attempt, len(extra) - 1)] if extra else {}
        env.update(extra)
        proc = ctx.Process(
            target=_run_task,
            args=(self._mapped, list(batches), pid, attempt, fail_after, q, env),
        )
        proc.start()
        try:
            status, rpid, payload = q.get(timeout=120)
        except Exception:
            proc.terminate()
            raise
        finally:
            proc.join(timeout=30)
        if status != "ok":
            return None, payload  # payload = repr of the task's exception
        out = []
        for d in payload:
            n = len(next(iter(d.values()))) if d else 0
            for i in range(n):
                out.append(SimRow({k: v[i] for k, v in d.items()}))
        return out, None


def simdf_from_numpy(
    x: np.ndarray,
    n_partitions: int,
    features_col: str = "features",
    label: Optional[np.ndarray] = None,
    label_col: str = "label",
    session: Optional[SimSparkSession] = None,
    **kw,
) -> SimDataFrame:
    """Build a partitioned SimDataFrame with an ArrayType-like features
    column (list<float>), the reference's input contract (README.md:26-37)."""
    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    parts = []
    xs = np.array_split(np.asarray(x), n_partitions)
    ys = (
        np.array_split(np.asarray(label), n_partitions)
        if label is not None
        else [None] * n_partitions
    )
    for xi, yi in zip(xs, ys):
        cols = {features_col: matrix_to_list_column(xi)}
        if yi is not None:
            cols[label_col] = pa.array(np.asarray(yi).reshape(-1))
        parts.append(pa.table(cols))
    return SimDataFrame(parts, session=session, **kw)
