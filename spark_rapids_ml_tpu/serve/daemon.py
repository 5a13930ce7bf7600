"""The TPU-host data-plane daemon (see package docstring for the role).

Threading model: one acceptor thread + one thread per connection (Spark
task). Concurrent feeds to the same job serialize on the job's lock around
the device fold — the accumulate is associative, so arrival order doesn't
matter (the property the reference's ``RDD.reduce`` relied on,
RapidsRowMatrix.scala:139). Feeds to different jobs interleave on the
host side (Arrow decode, validation, staging bookkeeping); the DEVICE
dispatch itself single-files through a process-wide ``_DEVICE_LOCK`` —
one process owns the host's chips, and concurrent sharded programs on one
device set buy nothing.

Serving plane: with ``serve_batching`` on (the DEFAULT since the fleet
PR — ``SRML_SERVE_BATCHING=0`` is the documented opt-out), concurrent
``transform``/``kneighbors`` requests do NOT dispatch per connection —
they queue into the micro-batching scheduler (serve/scheduler.py), which
coalesces them across connections per model, pads to the bucket ladder,
runs ONE device dispatch, and scatters per-request slices back.
Admission overflow and deadline misses are shed with the existing
busy/retry_after_s contract; the additive ``warmup`` op pre-compiles the
ladder. Fleet deployments (serve/fleet.py) additionally register models
under VERSIONED names and stamp requests with the expected
``(version, fleet_epoch)``; this daemon enforces the version pin
(``serve_version_strict``) and echoes it on every serving ack, so a
replica that missed a rollout refuses instead of answering from the
wrong arrays (docs/protocol.md "Fleet & versioned serving").

Jobs: "pca" folds (count, Σx, XᵀX); "linreg" folds (XᵀX, Xᵀy, Σx, Σy,
Σy², n). ``finalize`` runs the algorithm's shared finalize (eigensolve /
normal-equations solve) and streams the result arrays back.

Iterative jobs: "kmeans" and "logreg" are MULTI-PASS — executors re-feed
the dataset once per iteration (Lloyd / Newton) against the job's current
iterate, and the driver calls ``step`` at each pass boundary to apply the
update and read convergence info (moved² / delta), deciding whether to
run another pass. ``finalize`` then returns the model. This is the
daemon-side face of models.kmeans.fit_kmeans_stream /
models.logistic_regression.fit_logistic_stream.

Exactly-once under Spark task retry: a feed may carry ``partition`` (the
Spark partition id) + ``attempt``. Partitioned feeds fold into a staged
per-partition state; ``commit`` merges the stage into the job state
(associative add, the same property the reference's ``RDD.reduce`` leans
on, RapidsRowMatrix.scala:139). A retried attempt restarts its stage; a
feed or commit for an already-committed partition is discarded (ack'd but
not folded), so task retries and speculative duplicates cannot
double-count rows — the daemon owns the idempotency Spark's recompute
model assumes. Iterative feeds also carry ``pass_id`` (= the job's
iteration); stale-pass traffic from zombie tasks is rejected.

KMeans center seeding: either the FIRST eager batch seeds the centers
(single-feeder convenience; nondeterministic under concurrent feeds), or
the driver sends an explicit ``seed`` op with ≥ k rows before fanning the
scan out — the deterministic path the Spark wrapper uses.

Operational hardening: jobs idle longer than ``ttl`` seconds are evicted
by a reaper thread (a driver that crashes between feed and finalize no
longer leaks d×d device buffers forever), and an optional shared-secret
``token`` is checked on every op (the transport-trust story Spark gave
the reference for free).

Crash recovery (docs/protocol.md "Crash recovery"): with a ``state_dir``
the daemon persists its instance identity and write-ahead-snapshots
iterative jobs at every pass boundary (seed/step/set_iterate — iterate +
pass counter + creation params, atomic tmp+rename via core/checkpoint),
restoring them lazily after a restart; every ack carries a per-boot
``boot_id`` so drivers can FENCE a pass that spanned two incarnations
instead of trusting its poisoned row count. Pass-local state (stages,
current-pass statistics, dedupe memories) deliberately dies with the
incarnation — the recovery unit is the pass, replayed by the estimator.
"""

from __future__ import annotations

import contextlib
import hashlib
import hmac
import json
import os
import random
import socket
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Optional

import jax
import numpy as np

from spark_rapids_ml_tpu.core import checkpoint as checkpoint_mod
from spark_rapids_ml_tpu.models.jobs import JOB_ALGORITHMS, job_algorithm
from spark_rapids_ml_tpu.parallel import membership as membership_mod
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel.sharding import row_sharding
from spark_rapids_ml_tpu.serve import gossip as gossip_mod
from spark_rapids_ml_tpu.serve import protocol
from spark_rapids_ml_tpu.serve import scheduler as scheduler_mod
from spark_rapids_ml_tpu.utils import faults
from spark_rapids_ml_tpu.utils import flight as flight_mod
from spark_rapids_ml_tpu.utils import journal
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils import slo as slo_mod
from spark_rapids_ml_tpu.utils import xprof as xprof_mod
from spark_rapids_ml_tpu.utils.logging import get_logger
from spark_rapids_ml_tpu.utils.profiling import trace_span

logger = get_logger("serve.daemon")

#: Daemon telemetry (docs/observability.md catalogs all of these). The
#: additive `metrics` wire op exposes the whole registry; `tools.top`
#: renders it live.
_M_REQUESTS = metrics_mod.counter(
    "srml_daemon_requests_total",
    "Requests dispatched, by op and outcome (ok|error|transport)",
)
_M_REQ_SECONDS = metrics_mod.histogram(
    "srml_daemon_request_seconds", "Request handling latency, by op"
)
_M_RX_BYTES = metrics_mod.counter(
    "srml_daemon_rx_bytes_total",
    "Payload bytes received (Arrow/raw frames, headers excluded), by op",
)
_M_TX_BYTES = metrics_mod.counter(
    "srml_daemon_tx_bytes_total",
    "Response array bytes sent (headers excluded), by op",
)
_M_BUSY_SHEDS = metrics_mod.counter(
    "srml_daemon_busy_sheds_total",
    "Ops shed with busy under a backpressure watermark, by op",
)
_M_REPLAY_HITS = metrics_mod.counter(
    "srml_daemon_replay_hits_total",
    "Deduplicated replays, by kind (feed|merge|step|committed_partition)",
)
_M_CONNS = metrics_mod.gauge(
    "srml_daemon_active_connections",
    "Concurrently open connections (at scrape)",
)
_M_STAGED = metrics_mod.gauge(
    "srml_daemon_staged_bytes", "Bytes held by uncommitted stages (at scrape)"
)
_M_JOBS = metrics_mod.gauge(
    "srml_daemon_active_jobs", "Registered accumulation jobs (at scrape)"
)
#: The pass cache (docs/protocol.md "rescan"); counted only for jobs that
#: were given a budget, so a daemon with the cache off exports none of them.
_M_PASS_ROWS = metrics_mod.counter(
    "srml_daemon_pass_rows_total",
    "Rows folded into a pass's statistics, by where they came from "
    "(source = wire: fed and committed | cache: scanned again from the "
    "job's cached pass)",
)
_M_PASSES = metrics_mod.counter(
    "srml_daemon_passes_total",
    "Passes that folded rows, by source (wire = the pass's first fed "
    "rows entered the statistics | cache = one rescan)",
)
_M_PASS_CACHE = metrics_mod.gauge(
    "srml_daemon_pass_cache_bytes",
    "Bytes per device held by jobs' pass caches, cached passes and "
    "uncommitted stages' batches together (at scrape)",
)
_M_MODELS = metrics_mod.gauge(
    "srml_daemon_served_models", "Registered served models (at scrape)"
)
_M_JOB_RESTORES = metrics_mod.counter(
    "srml_daemon_job_restores_total",
    "Jobs resurrected from durable pass-boundary state after a restart, "
    "by algo",
)
_M_MODEL_EVICTIONS = metrics_mod.counter(
    "srml_daemon_model_evictions_total",
    "Served models evicted from the registry, by reason (lru = over the "
    "daemon_max_models cap; ttl = idle past the reaper's deadline)",
)
_M_MESH_REDUCES = metrics_mod.counter(
    "srml_daemon_mesh_reduces_total",
    "On-mesh collective reduces applied (reduce_mesh op: co-resident "
    "peer partials folded on the device plane, no driver hub), by algo",
)
_M_GOSSIP_TICKS = metrics_mod.counter(
    "srml_gossip_ticks_total",
    "Gossip-thread ticks run, by outcome (ok = every contacted peer "
    "exchanged; partial = some peer push dropped this tick)",
)

#: Device-build cap for daemon-side IVF (bytes of raw f32 rows): past
#: this, the full (n, d) matrix would not fit one chip's HBM alongside
#: the build's working set, so the host build + shard-direct placement
#: path runs instead (docs/ann-capacity.md).
_IVF_DEVICE_BUILD_MAX_BYTES = int(
    os.environ.get("SRML_IVF_DEVICE_BUILD_MAX", 4 << 30)
)

#: Ops whose request JSON is followed by one Arrow-IPC payload frame
#: (docs/protocol.md). Rejection paths must drain that frame to keep the
#: connection framing aligned. (``ensure_model`` instead carries raw
#: array frames per its request's ``arrays`` spec — see _drain_payload.)
_PAYLOAD_OPS = ("feed", "seed", "transform", "kneighbors")

#: Ops shed with `busy` + retry_after_s when the daemon is over a
#: backpressure watermark: the ones that ADD load (new rows, new state,
#: device compute). Pressure-relieving ops (commit, finalize, drop) and
#: O(1) control ops (ping, health, status, step) always pass.
_SHEDDABLE_OPS = (
    "feed", "feed_raw", "seed", "transform", "kneighbors", "merge_state",
    "reduce_mesh", "ensure_model", "warmup",
)

#: Process-wide device-execution lock. One process owns the host's chips
#: (the daemon's deployment unit); concurrent sharded dispatches from
#: multiple connection threads buy no throughput — the device set is one
#: resource. (The lock was introduced against a CPU-backend deadlock of
#: an older jax; on the installed jax 0.9 the chaos / multidaemon / serve
#: / scheduler / mesh-collectives / elastic suites pass with it replaced
#: by a no-op — PR 21 — so "one resource" is the reason that remains.
#: Whether to keep it is ROADMAP D4, decided by a chip trace.) Every
#: device-touching section
#: (fold/step/merge/finalize/build/serve) takes this lock INNERMOST —
#: after any job/model lock, never before one — so lock order stays
#: acyclic. This contract is machine-checked: srml-check's
#: `device-lock`/`lock-order`/`compile-outside-lock` rules
#: (tools/analyze.py, docs/static_analysis.md) fail tier-1 on a dispatch
#: outside the lock, a lock acquired under it, or a compile inside it —
#: and the interprocedural passes extend the check through call edges:
#: `blocking-under-device-lock` fails on any TRANSITIVELY-blocking call
#: (socket I/O, sleeps, future waits) reachable while this lock is held
#: (blocking on the device itself is the exemption — that is the lock's
#: purpose), and `lock-graph-cycle` keeps the whole-program lock-order
#: graph over every daemon/scheduler/router/fleet lock acyclic.
_DEVICE_LOCK = threading.Lock()

#: Every op _dispatch understands — the clamp for metric labels: a
#: label from the wire would let any client (or fuzzer) mint unbounded
#: registry series; unknown op strings all land under op="unknown".
_KNOWN_OPS = frozenset((
    "ping", "health", "metrics", "status", "feed", "feed_raw", "seed",
    "commit", "rescan", "step", "finalize", "drop", "export_state",
    "merge_state",
    "get_iterate", "set_iterate", "ensure_model", "transform",
    "kneighbors", "model_status", "drop_model", "warmup", "sample_rows",
    "mesh_info", "reduce_mesh", "gossip_push", "gossip_pull",
    "telemetry_pull", "trace_pull",
))


def _op_label(op) -> str:
    op = str(op)
    return op if op in _KNOWN_OPS else "unknown"


#: Ops that never open a journal span even when the journal is on: O(1)
#: control-plane chatter (liveness probes, scrapes) that would bury the
#: fit tree under polling noise.
_UNJOURNALED_OPS = frozenset((
    "ping", "health", "metrics", "model_status", "gossip_push",
    "gossip_pull", "telemetry_pull", "trace_pull",
))


@contextlib.contextmanager
def _op_trace(op: str, req: Dict[str, Any]):
    """Distributed-tracing shell around one dispatched op: adopt the
    request's additive ``trace_ctx`` (docs/protocol.md) so this
    connection thread's journal lines — the op span opened here plus
    every ``trace_span`` the op's model code runs — parent into the
    CALLER's run. One fit then journals a single tree spanning driver +
    executors + N daemons, mergeable by ``tools/trace.py``. Without a
    ctx the span roots itself (the PR 3 standalone-daemon behavior);
    with the journal fully off everything here is an early return.

    Yields the op span's own ``{"run", "span"}`` identity (None when
    unjournaled): the request-latency histogram records it as the
    sample's EXEMPLAR, so a latency-bucket outlier on the scrape side
    links to the exact trace that caused it."""
    tc = req.get("trace_ctx")
    tc = tc if isinstance(tc, dict) else {}
    with journal.adopt(tc.get("run"), tc.get("span")):
        if op not in _UNJOURNALED_OPS and journal.active():
            fields = {
                k: req[k] for k in ("job", "model") if req.get(k) is not None
            }
            with journal.span(f"daemon.{op}", **fields):
                yield journal.trace_ctx()
        else:
            yield None


#: Cap on a request's declared raw-array frame count (_recv_arrays_aligned):
#: the widest legitimate op is a multinomial merge_state (7 state leaves) or
#: an ensure_model payload (~5 arrays); 16 leaves headroom without letting a
#: hostile spec queue hundreds of 2 GB frames.
_MAX_ARRAY_SPECS = 16


def _recv_arrays_aligned(conn, req: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Receive a request's raw array frames with framing-safe parsing:
    ALL declared frames are drained off the socket before any dtype/shape
    validation runs, so a bad spec (wrong byte count, bogus dtype — easy
    for the from-scratch clients feed_raw invites) errors cleanly and the
    connection stays usable, instead of leaving unread frames that desync
    every subsequent request's length header."""
    specs = req.get("arrays") or []
    # Bound what one request can make the daemon buffer BEFORE draining
    # (round-4 advisor): the spec list is client-controlled, and without a
    # cap a single feed_raw/merge_state request could declare many
    # MAX_FRAME-sized frames and hold them all in memory at once (the
    # Arrow feed path holds at most one). The legitimate ops carry a
    # handful of arrays whose summed bytes fit one Arrow feed's budget.
    import math

    specs = list(specs)
    over = None
    sizes = []
    if len(specs) > _MAX_ARRAY_SPECS:
        over = (
            f"request declares {len(specs)} array frames; the protocol ops "
            f"need at most {_MAX_ARRAY_SPECS}"
        )
    else:
        declared = 0
        for spec in specs:
            # Python-int arithmetic (no np.prod): hostile 2^33-scale dims
            # must not silently wrap an int64 product back under the cap.
            try:
                shape = [int(s) for s in spec["shape"]]
                if any(s < 0 for s in shape):
                    raise ValueError(f"negative dim in shape {shape}")
                nbytes = np.dtype(spec["dtype"]).itemsize * math.prod(shape)
            except (KeyError, TypeError, ValueError) as e:
                # Defer to the drain-then-error path: raising BEFORE the
                # declared frames are read would desync the framing for the
                # very from-scratch clients feed_raw invites.
                over = f"bad array spec: {e}"
                break
            sizes.append(nbytes)
            declared += nbytes
        if over is None and declared > protocol.MAX_FRAME:
            over = (
                f"request declares {declared} summed array bytes > "
                f"MAX_FRAME {protocol.MAX_FRAME}; split the batch"
            )
    if over is not None:
        # Drain-then-error with ONE frame in memory at a time (discarding
        # as we go): framing stays aligned for the error response without
        # ever holding the declared frames simultaneously — the buffering
        # bound this cap exists to enforce (round-4 advisor).
        for _ in specs:
            if protocol.recv_frame(conn) is None:
                break
        raise protocol.ProtocolError(over)
    frames = []
    for i in range(len(specs)):
        frame = protocol.recv_frame(conn)
        if frame is None:
            raise protocol.ProtocolError("connection closed mid-array")
        if len(frame) != sizes[i]:
            # The declared sizes are what the caps above validated; a frame
            # that disagrees re-opens the buffering bound (declare tiny,
            # send 2 GB × 16) — discard it and drain the rest aligned.
            got, want = len(frame), sizes[i]
            del frame
            for _ in range(i + 1, len(specs)):
                if protocol.recv_frame(conn) is None:
                    break
            raise protocol.ProtocolError(
                f"array frame {i} carries {got} bytes; its spec declared "
                f"{want}"
            )
        frames.append(frame)
    if sizes:
        _M_RX_BYTES.inc(sum(sizes), op=_op_label(req.get("op")))
    out: Dict[str, np.ndarray] = {}
    for spec, frame in zip(specs, frames):
        arr = np.frombuffer(frame, dtype=np.dtype(spec["dtype"]))
        out[str(spec["name"])] = arr.reshape(spec["shape"]).copy()
    return out


def _recv_payload_counted(conn, op: str) -> bytes:
    """One payload frame + the per-op RX byte accounting — the receive
    twin of :func:`_send_arrays_counted`, so no payload-carrying op can
    forget the accounting."""
    payload = protocol.recv_frame(conn)
    if payload is None:
        raise protocol.ProtocolError(f"connection closed before {op} payload")
    _M_RX_BYTES.inc(len(payload), op=op)
    return payload


def _send_arrays_counted(conn, op: str, arrays, meta) -> None:
    """protocol.send_arrays + per-op TX byte accounting (array bytes;
    JSON headers are noise next to the frames that matter here)."""
    protocol.send_arrays(conn, arrays, meta)
    _M_TX_BYTES.inc(
        sum(int(np.asarray(v).nbytes) for v in arrays.values()), op=op
    )


class _Stage:
    """One (partition, attempt) staged accumulation: the state, its row
    count, an estimate of the bytes it holds (staged-byte accounting for
    the backpressure watermark), and the feed_ids already folded into it
    (exactly-once REPLAY: a self-healing client that lost an ack resends
    the same feed_id, which must not double-count)."""

    __slots__ = ("state", "rows", "nbytes", "seen", "batches", "batch_bytes")

    def __init__(self, state, rows: int = 0, nbytes: int = 0):
        self.state = state
        self.rows = rows
        self.nbytes = nbytes
        self.seen: set = set()
        # Pass cache (off: stays empty): the batches this stage's folds
        # placed — (xs, ms) and the algorithm's per-row columns — kept
        # until its commit moves them into the job's cached pass — a
        # losing attempt's are freed with its stage — and their bytes per
        # device (counted apart from `nbytes`: not back-pressure).
        self.batches: list = []
        self.batch_bytes = 0


#: Cached batches a `rescan` dispatch folds. One program a batch leaves
#: the device waiting: at the documented 65,536-row batch a kmeans fold
#: takes the device ~0.1 ms (0.22 before the fold read x once, PR 29) and
#: the host 0.3–0.4 ms to dispatch (PERF.md §5). Eight in a program — the
#: fold's arithmetic batch by batch, in order — put the host at an eighth
#: of that. One constant, no option.
_RESCAN_GROUP = 8


def _rescan_groups(batches):
    """Runs of up to `_RESCAN_GROUP` consecutive cached batches of one
    shape (one compiled program per group length and shape), in order."""
    group: list = []
    for batch in batches:
        if group and (
            len(group) == _RESCAN_GROUP or batch[0].shape != group[0][0].shape
        ):
            yield group
            group = []
        group.append(batch)
    if group:
        yield group


class _PassCache:
    """One pass's batches as the fold placed them on the device, each a
    tuple of the fold's operands ``(xs, ms, *columns)`` — float32 rows
    padded to their bucket under the job's row sharding, the row mask,
    and the per-row columns the algorithm placed beside them
    (`JobAlgorithm.place_columns`: labels; none for kmeans) — in commit
    order.
    ``filled_at`` is the pass that fed them; ``pass_rows`` is what that
    pass committed from the wire, set when the pass closed (None while it
    is open): a cached pass answers `rescan` only if it holds exactly
    those rows. ``committed`` is that pass's partition → rows map, which
    a rescan restores so that `export_state` / `reduce_mesh` reconcile a
    cached pass against the same task acks as the fed one."""

    __slots__ = ("batches", "rows", "nbytes", "filled_at", "pass_rows",
                 "committed")

    def __init__(self, filled_at: int):
        self.batches: list = []
        self.rows = 0
        self.nbytes = 0
        self.filled_at = filled_at
        self.pass_rows: Optional[int] = None
        self.committed: Dict[int, int] = {}


def _state_nbytes(state) -> int:
    """Rough device-buffer footprint of a job/stage state tree."""
    try:
        return int(
            sum(getattr(leaf, "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(state))
        )
    except Exception:  # pragma: no cover - defensive; accounting only
        return 0


#: Bound on remembered unpartitioned feed_ids / merge_ids per job (those
#: ops fold immediately, so dedupe needs a memory; stages carry their own
#: sets and die with the stage). FIFO eviction — a replay arrives right
#: after its original, never 4096 ops later.
_MAX_SEEN_FEED_IDS = 4096


class _FifoSet:
    """Bounded membership memory for replay dedupe: `in` + add-with-FIFO-
    eviction. One implementation for feed_ids and merge_ids so the
    eviction policy cannot drift between them."""

    __slots__ = ("_set", "_order", "_cap")

    def __init__(self, cap: int = _MAX_SEEN_FEED_IDS):
        self._set: set = set()
        self._order: deque = deque()
        self._cap = cap

    def __contains__(self, item: str) -> bool:
        return item in self._set

    def add(self, item: str) -> None:
        if item in self._set:
            return
        self._set.add(item)
        self._order.append(item)
        if len(self._order) > self._cap:
            self._set.discard(self._order.popleft())


def _opt(req: Dict[str, Any], key: str, default):
    """Optional request field: docs/protocol.md promises that omitted and
    JSON null are equivalent, so a present-but-null field takes the
    default too (a third-party client may serialize absent options as
    null)."""
    value = req.get(key)
    return default if value is None else value


class _Job:
    """One accumulation job: device state + the algorithm that folds into
    it (a models/job_protocol.py `JobAlgorithm`, by wire name from
    models/jobs.py) + a lock. Everything here is the same for every
    algorithm: what differs is asked of `self.algorithm`, under the job
    lock, and under `_DEVICE_LOCK` wherever the call can dispatch."""

    def __init__(
        self, algo: str, n_cols: int, mesh,
        params: Optional[Dict[str, Any]] = None, clock=time.monotonic,
    ):
        from spark_rapids_ml_tpu import config

        params = params or {}
        self._clock = clock
        self.algo = algo
        self.n_cols = n_cols
        self.mesh = mesh
        #: Creation params, kept verbatim (JSON-able): a durable snapshot
        #: stores them so a restore can re-run this constructor.
        self.params = dict(params)
        #: Durability hook (None = off): called under the job lock at
        #: every pass boundary (seed / step / set_iterate) BEFORE the op
        #: acks — write-ahead, so an acked boundary is a recoverable one.
        self.snapshot_cb = None
        self.lock = threading.Lock()
        self.rows = 0
        self.dropped = False
        self.n_data = mesh.shape[DATA_AXIS]
        self.x_sharding = row_sharding(mesh)
        self.v_sharding = row_sharding(mesh, ndim=1)
        self.iteration = 0
        self.pass_rows = 0
        self.touched = self._clock()
        # Partition staging (exactly-once under task retry): keyed by
        # (partition, attempt) so CONCURRENT attempts of one partition
        # (Spark speculation runs a duplicate alongside the original)
        # accumulate independently instead of wiping each other — the
        # first to commit wins, the rest are discarded. Values: _Stage;
        # committed: partition → rows.
        self.staged: Dict[tuple, _Stage] = {}
        self.committed: Dict[int, int] = {}
        # Total bytes currently held by uncommitted stages (the `health`
        # op's staged_bytes and the backpressure watermark's input).
        self.staged_bytes = 0
        # Replay dedupe for UNPARTITIONED feeds (they fold immediately):
        # bounded FIFO memory. Staged feeds dedupe inside their _Stage.
        # Same memory shape for merge_state replays (merge_remote folds
        # immediately too — a replayed merge must not double-apply).
        self._seen_feed_ids = _FifoSet()
        self._seen_merge_ids = _FifoSet()
        # The algorithm: param validation, the capacity gate and the
        # update programs are its constructor's (a refusal there is the
        # first feed's clean error; an unknown name is the table's).
        self.algorithm = job_algorithm(algo)(n_cols, mesh, params)
        # Pass cache (docs/protocol.md "rescan"; docs/mesh.md "Capacity"):
        # the budget in bytes per device, 0 = off. Written over the fold's
        # device operands — (xs, ms) and the algorithm's per-row columns;
        # an algorithm that gives `fold_group` for these params says
        # `cacheable_for` them and takes it. `_cache_ok` falls for the rest of
        # the fit with the first batch that would pass the budget (all or
        # nothing); `_cache_bytes` counts the cached pass and the
        # uncommitted stages' batches together, apart from staged_bytes.
        self._cache_budget = (
            max(int(config.get("daemon_pass_cache_mb")), 0) << 20
            if self.algorithm.cacheable_for(params) else 0
        )
        self._cache: Optional[_PassCache] = None
        self._cache_ok = self._cache_budget > 0
        self._cache_bytes = 0
        # Rows this pass took from the wire (direct folds + commits): what
        # a cached pass must hold to stand for it.
        self._pass_wire_rows = 0
        # Rescan idempotency, as step's: a replayed rescan (ack lost)
        # gets the ack of the one already applied.
        self._last_rescan_id: Optional[str] = None
        self._last_rescan_ack: Optional[Dict[str, Any]] = None
        # Step idempotency: a replayed step (ack lost mid-connection)
        # carrying the step_id of the ALREADY-APPLIED step gets the
        # cached info back instead of double-advancing the iterate.
        self._last_step_id: Optional[str] = None
        self._last_step_info: Optional[Dict[str, Any]] = None
        # An unpublished job: no other thread can dispatch for it yet.
        self.state = self.algorithm.zero_state()  # srml: disable=device-lock

    def durable_arrays(self) -> Dict[str, np.ndarray]:
        """The iterate arrays a pass-boundary snapshot stores (call under
        the job lock). Pass-local accumulator state is deliberately
        excluded: at a boundary it is zero, or what the algorithm's
        `next_pass_state` carried over from the finished pass to save the
        next one work (the forest's parent histogram) — which a restored
        job does without: its pass opens from `zero_state()` and folds in
        full. So the snapshot is O(iterate) — the cheap-persistence
        property core/checkpoint.py already proved for the O(d²) case."""
        if not (self.algorithm.iterative and self.algorithm.installed):
            return {}
        with _DEVICE_LOCK:
            return self.algorithm.iterate_arrays()

    def _maybe_snapshot(self) -> None:
        """Write the durable pass-boundary snapshot when configured (call
        under the job lock, BEFORE the boundary op's ack goes out). A
        write failure fails the op — silently losing durability would
        turn the next crash into the data loss the snapshot exists to
        prevent."""
        cb = self.snapshot_cb
        if cb is not None:
            cb(self)

    def _bucket(self, n: int) -> int:
        """Pad target: next power of two (≥ data-axis size).

        Spark partitions are rarely equal-sized; padding each batch to its
        exact multiple-of-n_data size would compile one donated update per
        distinct shape — unbounded in a long-lived daemon. Power-of-two
        buckets bound compilations to ~log2(max_rows) shapes; the row mask
        keeps padded rows out of the statistics."""
        b = max(self.n_data, 1)
        while b < n:
            b <<= 1
        return b

    def _pad(self, x: np.ndarray, n: int):
        """A batch padded to its bucket, and its row mask (host side,
        before the job lock is taken)."""
        target = self._bucket(n)
        xb = np.zeros((target,) + x.shape[1:], dtype=x.dtype)
        xb[:n] = x
        mb = np.zeros((target,), dtype=np.float32)
        mb[:n] = 1.0
        return xb, mb

    def _stage_zero(self):
        """A fresh stage's empty statistics."""
        with _DEVICE_LOCK:
            return self.algorithm.zero_state()

    def _fold_batch(self, state, xb, mb, y, n, partition, offset):
        """Place a padded batch and fold it: → (state, batch), `batch`
        the fold's device operands ``(xs, ms, *columns)`` (what the pass
        cache keeps)."""
        with _DEVICE_LOCK:
            xs = jax.device_put(xb, self.x_sharding)
            ms = jax.device_put(mb, self.v_sharding)
            columns = self.algorithm.place_columns(
                xs.shape[0], y, n=n, partition=partition, offset=offset
            )
            state = self.algorithm.fold(state, xs, ms, columns, n=n)
        return state, (xs, ms, *columns)

    def _check_pass(self, pass_id: Optional[int]) -> None:
        """Reject traffic from a zombie task of an earlier pass: its batch
        was computed against a stale iterate and must not pollute this
        pass's statistics."""
        if pass_id is not None and int(pass_id) != self.iteration:
            if int(pass_id) > self.iteration:
                # The DAEMON is behind the task: either this daemon joined
                # an in-flight iterative fit (a task was rescheduled onto a
                # daemon that never saw the job — it cannot catch up
                # mid-fit) or it missed the driver's set_iterate.
                hint = (
                    " — this daemon is behind the fit (it never saw the "
                    "earlier passes). Keep executor→daemon routing sticky "
                    "across retries: a daemon cannot join an iterative fit "
                    "mid-flight."
                )
            else:
                hint = " (zombie task of an already-stepped pass)"
            raise ValueError(
                f"stale pass_id {pass_id} (job is on pass {self.iteration}); "
                f"feed rejected{hint}"
            )

    def seed_centers(self, x: np.ndarray) -> None:
        """Deterministic kmeans init from a driver-chosen batch: centers
        only, NO fold (the rows also live in some partition and will arrive
        through the scan — folding here would double-count them)."""
        self.algorithm.check_seed(x)
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            if self.algorithm.installed:
                return  # idempotent: a retried seed keeps the first init
            with _DEVICE_LOCK:
                self.algorithm.seed(x)
            # Seeded centers are the pass-0 boundary: persist them so a
            # restarted daemon reopens pass 0 with identical centers.
            self._maybe_snapshot()
            self.touched = self._clock()  # exit stamp (init can be slow)

    def _is_replay(self, feed_id: Optional[str], stage: Optional[_Stage]) -> bool:
        """Feed-level replay dedupe (call under the job lock): True when
        this feed_id already folded — a self-healing client resent an op
        whose first ack was lost. Stage-scoped for partitioned feeds,
        job-scoped (bounded FIFO) for direct feeds. Read-only: the id is
        recorded by :meth:`_mark_folded` only AFTER the fold succeeds —
        recording it up front would poison the id when the fold raises,
        making the replay a silent ack-without-fold."""
        if feed_id is None:
            return False
        feed_id = str(feed_id)
        hit = (
            feed_id in stage.seen
            if stage is not None
            else feed_id in self._seen_feed_ids
        )
        if hit:
            _M_REPLAY_HITS.inc(kind="feed")
        return hit

    def _mark_folded(self, feed_id: Optional[str], stage: Optional[_Stage]) -> None:
        """Record a successfully folded feed_id (under the job lock)."""
        if feed_id is None:
            return
        feed_id = str(feed_id)
        if stage is not None:
            stage.seen.add(feed_id)
            return
        self._seen_feed_ids.add(feed_id)

    def _drop_stage(self, key: tuple) -> Optional[_Stage]:
        """Remove one stage, keeping the staged-bytes account balanced
        (and the pass cache's: the stage's batches go with it)."""
        stage = self.staged.pop(key, None)
        if stage is not None:
            self.staged_bytes -= stage.nbytes
            self._cache_bytes -= stage.batch_bytes
        return stage

    def _clear_stages(self) -> None:
        """A pass boundary: every stage goes, with its batches."""
        self.staged.clear()
        self.staged_bytes = 0
        self._cache_bytes = 0 if self._cache is None else self._cache.nbytes

    # -- pass cache (docs/protocol.md "rescan") ----------------------------
    # All under the job lock. The cache changes the transport of a pass,
    # never its result: it holds the fold's own operands and `rescan`
    # folds them with the fold's own program.

    @property
    def pass_cache_bytes(self) -> int:
        """Bytes per device the cache holds (`health`, the gauge)."""
        return self._cache_bytes

    def cache_ack(self) -> Dict[str, Any]:
        """The additive fields of a feed / commit ack: none for a job
        without a budget; else whether the cache still holds every row
        the pass committed, and how many it holds. A driver that reads
        ``cached: true`` on every commit ack of a pass may `rescan`."""
        if not self._cache_budget:
            return {}
        cache = self._cache if self._cache_ok else None
        return {
            "cached": cache is not None,
            "cached_rows": 0 if cache is None else cache.rows,
        }

    def _wire_rows(self, n: int) -> None:
        """`n` rows from the wire entered this pass's statistics."""
        if self._pass_wire_rows == 0:
            _M_PASSES.inc(source="wire")
        self._pass_wire_rows += n
        _M_PASS_ROWS.inc(n, source="wire")

    def _free_cached_pass(self) -> None:
        if self._cache is not None:
            self._cache_bytes -= self._cache.nbytes
            self._cache = None

    def _open_cache(self) -> _PassCache:
        """The cache this pass fills. A pass that is fed again replaces
        the cached one (the driver re-feeds only when it will not rescan)."""
        if self._cache is not None and self._cache.filled_at != self.iteration:
            self._free_cached_pass()
        if self._cache is None:
            self._cache = _PassCache(self.iteration)
        return self._cache

    def _drop_cache(self) -> None:
        """Free everything the cache holds and keep nothing more this
        fit: a part of a pass is of no use to `rescan`."""
        self._cache = None
        self._cache_ok = False
        self._cache_bytes = 0
        for stage in self.staged.values():
            stage.batches, stage.batch_bytes = [], 0

    def _keep_batch(self, stage: Optional[_Stage], batch: tuple, n: int) -> None:
        """Keep the operands a fold has just placed: in the job's cached
        pass (direct feed) or in the stage until its commit."""
        if not self._cache_ok:
            return
        cache = self._open_cache()
        nbytes = sum(int(a.nbytes) for a in batch) // self.n_data
        if self._cache_bytes + nbytes > self._cache_budget:
            self._drop_cache()  # this stage's too: fold has published it
            logger.warning(
                "pass cache over its budget (%d MiB a device) after %d "
                "rows: dropped for this fit, every pass is re-fed",
                self._cache_budget >> 20, cache.rows,
            )
            return
        self._cache_bytes += nbytes
        if stage is None:
            cache.batches.append(batch)
            cache.rows += n
            cache.nbytes += nbytes
        else:
            stage.batches.append(batch)
            stage.batch_bytes += nbytes

    def _commit_batches(self, partition: int, stage: _Stage) -> None:
        """The winning stage's batches join the cached pass, in commit
        order (the stage has left `self.staged` and the byte account)."""
        if not self._cache_ok:
            return
        cache = self._open_cache()
        cache.batches.extend(stage.batches)
        cache.rows += stage.rows
        cache.nbytes += stage.batch_bytes
        cache.committed[partition] = stage.rows
        self._cache_bytes += stage.batch_bytes

    def _close_pass(self, opens: Optional[int] = None) -> None:
        """A pass boundary (step, set_iterate): the pass that filled the
        cache is over, and the cache stands for what it committed. A
        boundary that opens the filling pass again, or an earlier one
        (`opens`, a recovery rewind), finds a part of a pass: freed."""
        cache = self._cache
        if cache is not None and opens is not None and cache.filled_at >= opens:
            self._free_cached_pass()
        elif cache is not None and cache.pass_rows is None:
            cache.pass_rows = self._pass_wire_rows
        self._pass_wire_rows = 0
        self._last_rescan_id = self._last_rescan_ack = None

    def release(self) -> None:
        """The job is over (drop, finalize with drop, TTL eviction): no op
        folds into it again, and its cached pass is freed."""
        self.dropped = True
        self._free_cached_pass()

    def rescan(
        self, pass_id: Optional[int] = None, rescan_id: Optional[str] = None
    ) -> Dict[str, Any]:
        """One pass from the cache: fold every cached batch, in commit
        order, against the current iterate, with the fold's own program.
        Dispatches only — the wait for the device belongs to `step`."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self._check_pass(pass_id)
            self.touched = self._clock()
            if (
                rescan_id is not None
                and self._last_rescan_ack is not None
                and str(rescan_id) == self._last_rescan_id
            ):
                _M_REPLAY_HITS.inc(kind="rescan")
                return dict(self._last_rescan_ack)
            cache = self._cache
            if not self._cache_ok or cache is None:
                raise protocol.NoCachedPass(
                    "no cached pass: this job keeps none (cache off, over "
                    "its budget, or the job was restored from a snapshot, "
                    "which does not hold it); re-feed the pass"
                )
            if cache.pass_rows is None or cache.filled_at >= self.iteration:
                raise protocol.NoCachedPass(
                    f"no cached pass: pass {cache.filled_at}, which fills "
                    "it, is still open; re-feed the pass"
                )
            if cache.rows != cache.pass_rows:
                raise protocol.NoCachedPass(
                    f"no cached pass: it holds {cache.rows} rows, the pass "
                    f"that filled it committed {cache.pass_rows}; re-feed "
                    "the pass"
                )
            if self.pass_rows or self.staged or self.committed:
                raise ValueError(
                    f"rescan into pass {self.iteration}, which already "
                    f"holds {self.pass_rows} rows"
                )
            state = self.state
            fold_group = self.algorithm.fold_group
            with trace_span("pass.rescan"):
                with _DEVICE_LOCK:
                    for group in _rescan_groups(cache.batches):
                        # column-wise: the run's rows, its masks, and a
                        # tuple a column the algorithm placed
                        xs, ms, *columns = zip(*group)
                        state = fold_group(state, xs, ms, tuple(columns))
            self.state = state
            self.committed = dict(cache.committed)
            self.rows += cache.rows
            self.pass_rows = cache.rows
            _M_PASSES.inc(source="cache")
            _M_PASS_ROWS.inc(cache.rows, source="cache")
            ack = {
                "pass_rows": self.pass_rows,
                "cached_rows": cache.rows,
                "cached_batches": len(cache.batches),
            }
            self._last_rescan_id = None if rescan_id is None else str(rescan_id)
            self._last_rescan_ack = dict(ack)
            self.touched = self._clock()  # exit stamp (see fold)
            return ack

    def fold(
        self,
        x: np.ndarray,
        y: Optional[np.ndarray],
        partition: Optional[int] = None,
        attempt: int = 0,
        pass_id: Optional[int] = None,
        feed_id: Optional[str] = None,
    ) -> None:
        if x.shape[1] != self.n_cols:
            raise ValueError(f"batch width {x.shape[1]} != job n_cols {self.n_cols}")
        if self.algorithm.needs_labels and y is None:
            raise ValueError(f"{self.algo} feed needs a label column")
        n = x.shape[0]
        xb, mb = self._pad(x, n)
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped; rows not accepted")
            self._check_pass(pass_id)
            self.touched = self._clock()
            if partition is not None and partition in self.committed:
                # duplicate of a committed task (retry/speculation)
                _M_REPLAY_HITS.inc(kind="committed_partition")
                return
            if not self.algorithm.installed:
                # No iterate yet: the algorithm's own refusal, or — where
                # it takes its iterate from rows — the first unpartitioned
                # batch seeds it (same device section seed_centers locks).
                self.algorithm.require_iterate(
                    "feed" if partition is None else "staged_feed"
                )
                self.algorithm.check_first_batch(self.params, x)
                with _DEVICE_LOCK:
                    self.algorithm.seed(x)
            stage = None
            fresh_stage = False
            if partition is None:
                if self._is_replay(feed_id, None):
                    return
                state = self.state
            else:
                stage = self.staged.get((partition, attempt))
                if stage is None:
                    zero = self._stage_zero()
                    # NOT registered in self.staged yet: a fallible device
                    # update follows, and a phantom empty stage would both
                    # inflate staged_bytes and let a later commit of this
                    # (partition, attempt) succeed with 0 rows.
                    stage = _Stage(zero, 0, _state_nbytes(zero))
                    fresh_stage = True
                if self._is_replay(feed_id, stage):
                    return
                state = stage.state
            # The rows the batch's stage (the pass, for a direct feed)
            # held BEFORE this fold: an algorithm that gives rows an
            # identity counts from it, so a restarted stage replays the
            # same identities.
            offset = stage.rows if stage is not None else self.pass_rows
            state, batch = self._fold_batch(
                state, xb, mb, y, n, partition, offset
            )
            if partition is None:
                self.state = state
                self.rows += n
                self.pass_rows += n
            else:
                stage.state = state
                stage.rows += n
                # what the stage holds now (device statistics keep their
                # size; a stage of rows grows by the batch)
                held = _state_nbytes(state)
                self.staged_bytes += held - (0 if fresh_stage else stage.nbytes)
                stage.nbytes = held
                if fresh_stage:
                    # Published only after the update succeeded (see the
                    # creation comment above).
                    self.staged[(partition, attempt)] = stage
            if self._cache_budget:
                if partition is None:
                    self._wire_rows(n)
                self._keep_batch(stage, batch, n)
            # Only now — after the device fold succeeded — is the feed_id
            # burned; an id recorded before a failing update would turn
            # the client's replay into a silent ack-without-fold.
            self._mark_folded(feed_id, stage)
            # Refresh again on exit: the device update above can dominate
            # the op (first-compile can take tens of seconds), and a
            # touched stamp from the op's START would make a busy job look
            # idle the instant it finishes.
            self.touched = self._clock()

    def commit(
        self, partition: int, attempt: int = 0, pass_id: Optional[int] = None
    ) -> int:
        """Merge a partition's staged state into the job state. Idempotent:
        recommits (lost ack → task retry) and commits for already-committed
        partitions are acknowledged without folding. Returns total job rows."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self._check_pass(pass_id)
            self.touched = self._clock()
            if partition in self.committed:
                _M_REPLAY_HITS.inc(kind="committed_partition")
                return self.rows
            staged = self._drop_stage((partition, attempt))
            if staged is None:
                raise ValueError(
                    f"commit for partition {partition} attempt {attempt} "
                    "with no staged feed"
                )
            n = staged.rows
            self._merge_stage(partition, staged.state)
            self.committed[partition] = n
            self.rows += n
            self.pass_rows += n
            if self._cache_budget:
                self._wire_rows(n)
                self._commit_batches(partition, staged)
            # losing attempts' stages for this partition free their buffers
            for key in [k for k in self.staged if k[0] == partition]:
                self._drop_stage(key)
            self.touched = self._clock()  # exit stamp (see fold)
            return self.rows

    def _merge_stage(self, partition: int, state) -> None:
        """The winning stage's statistics join the job's (under the job
        lock). Every state here is a tree of additive sufficient
        statistics (counts, Σx, XᵀX, Xᵀy, per-center sums, gradient/Hessian
        blocks, inertia …), so the combine is an elementwise add — the
        ``accumulateCov`` the reference declared but never built
        (RAPIDSML.scala:95-97)."""
        import jax.numpy as jnp

        with _DEVICE_LOCK:  # the merge is a device program
            self.state = jax.tree_util.tree_map(jnp.add, self.state, state)

    def export_state(self):
        """Snapshot the job's COMMITTED accumulated state for a cross-daemon
        merge (multi-host data plane): the O(d²) partials leave as raw
        arrays, flattened in jax tree order. Uncommitted stages are
        deliberately excluded — the driver only accounts rows that were
        acked through commit. Read-only; the job keeps serving."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            leaves = jax.tree_util.tree_leaves(self.state)
            with _DEVICE_LOCK:
                arrays = {
                    f"s{i}": np.asarray(jax.device_get(a))
                    for i, a in enumerate(leaves)
                }
            meta = {
                "rows": self.rows,
                "pass_rows": self.pass_rows,
                "iteration": self.iteration,
                "algo": self.algo,
                "n_cols": self.n_cols,
                # Which partitions this state holds (this pass): lets the
                # driver name a cross-daemon-retry orphan precisely
                # instead of reporting a bare row-count mismatch.
                "committed": {str(p): n for p, n in self.committed.items()},
            }
            self.touched = self._clock()  # exit stamp (device_get can be slow)
            return arrays, meta

    def sample_rows(self, n: int, seed: int = 0) -> np.ndarray:
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
        raise ValueError(
            "sample_rows is a knn-job op (other algos hold O(d²) "
            "statistics, not rows)"
        )

    def seen_reduce(self, reduce_id: Optional[str]) -> Optional[int]:
        """Replay-dedupe probe for ``reduce_mesh`` (call BEFORE any peer
        validation): an already-applied reduce_id returns the cached row
        total — with ``drop_peers`` the first apply dropped the peer
        jobs, so re-validating a replay against them would fail an op
        that SUCCEEDED (the ack was merely lost). None = not seen."""
        if reduce_id is None:
            return None
        with self.lock:
            if self.dropped:
                return None
            if str(reduce_id) in self._seen_merge_ids:
                _M_REPLAY_HITS.inc(kind="merge")
                self.touched = self._clock()
                return self.rows
        return None

    def peek_pass_state(self):
        """Pre-reduce gather read (docs/protocol.md "reduce_mesh"): this
        pass's committed device state + accounting, under the job lock —
        ``(state ref, pass_rows, committed copy, iteration)``. The state
        reference is the fold input for a co-resident collective reduce;
        the driver only reduces after every commit of the pass acked, so
        traffic after this read is next-pass (or fenced zombie) traffic."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            return self.state, self.pass_rows, dict(self.committed), self.iteration

    def merge_mesh(self, contributions, reduce_id: Optional[str] = None) -> int:
        """Fold co-resident peers' DEVICE states into this job — the
        on-mesh twin of :meth:`merge_remote`, minus its device→host→wire→
        device round-trip: the peer's accumulator arrays add directly on
        the device plane. ``contributions``: ``[(peer_id, state, rows)]``
        in the driver's (sorted-by-id) order — the same fold order the
        export/merge hub uses, so the two paths are bitwise-identical.
        ``reduce_id`` dedupes a self-healing client's replay exactly like
        ``merge_id`` (at most one apply; same FIFO memory)."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            if reduce_id is not None and str(reduce_id) in self._seen_merge_ids:
                _M_REPLAY_HITS.inc(kind="merge")
                return self.rows
            leaves, treedef = jax.tree_util.tree_flatten(self.state)
            peer_leaves = []
            for pid, state, _rows in contributions:
                ol = jax.tree_util.tree_leaves(state)
                if len(ol) != len(leaves):
                    raise ValueError(
                        f"peer {pid} state has {len(ol)} leaves; job state "
                        f"has {len(leaves)} (algo/params mismatch between "
                        "daemons?)"
                    )
                for a, b in zip(leaves, ol):
                    if tuple(a.shape) != tuple(b.shape):
                        raise ValueError(
                            f"peer {pid} state shape {tuple(b.shape)} != "
                            f"job state shape {tuple(a.shape)}"
                        )
                peer_leaves.append(ol)
            with _DEVICE_LOCK:
                for ol in peer_leaves:
                    leaves = [a + b for a, b in zip(leaves, ol)]
            self.state = jax.tree_util.tree_unflatten(treedef, leaves)
            self.algorithm.state_merged()
            for _pid, _state, rows in contributions:
                self.rows += int(rows)
                self.pass_rows += int(rows)
            if reduce_id is not None:
                # Burned only after the fold APPLIED (same rule as
                # merge_remote): a replay of a rejected reduce must not
                # become a silent ack-without-apply.
                self._seen_merge_ids.add(str(reduce_id))
            self.touched = self._clock()  # exit stamp
            return self.rows

    def merge_remote(
        self, arrays: Dict[str, np.ndarray], rows: int,
        merge_id: Optional[str] = None,
    ) -> int:
        """Fold another daemon's exported state into this job — the
        associative add that makes the data plane span hosts (the
        ``RDD.reduce`` across executors, RapidsRowMatrix.scala:139, with
        daemons as the leaves). ``rows`` is the contributed committed-row
        count; it joins both the job total and the current pass.
        ``merge_id`` (additive) dedupes a self-healing client's replay:
        the same id folds at most once — without it, a merge whose ack
        was lost would double-apply the peer's partials on replay."""
        import jax.numpy as jnp

        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            if merge_id is not None and str(merge_id) in self._seen_merge_ids:
                _M_REPLAY_HITS.inc(kind="merge")
                return self.rows
            leaves, treedef = jax.tree_util.tree_flatten(self.state)
            if len(arrays) != len(leaves):
                raise ValueError(
                    f"merge_state carried {len(arrays)} arrays; job state "
                    f"has {len(leaves)} (algo/params mismatch between "
                    "daemons?)"
                )
            merged = []
            with _DEVICE_LOCK:
                for i, leaf in enumerate(leaves):
                    inc = arrays.get(f"s{i}")
                    if inc is None:
                        raise ValueError(f"merge_state missing array 's{i}'")
                    if tuple(inc.shape) != tuple(leaf.shape):
                        raise ValueError(
                            f"merge_state array s{i} shape {tuple(inc.shape)} "
                            f"!= job state shape {tuple(leaf.shape)}"
                        )
                    merged.append(leaf + jnp.asarray(inc, leaf.dtype))
            self.state = jax.tree_util.tree_unflatten(treedef, merged)
            self.algorithm.state_merged()
            self.rows += int(rows)
            self.pass_rows += int(rows)
            if merge_id is not None:
                # Burned only after the merge APPLIED: recording it before
                # validation would make a replay of a rejected merge a
                # silent ack-without-apply.
                self._seen_merge_ids.add(str(merge_id))
            self.touched = self._clock()  # exit stamp
            return self.rows

    def get_iterate(self):
        """Current iterate of an iterative job (kmeans centers / logreg
        coefficients) + its pass counter — what a driver pushes to peer
        daemons with ``set_iterate`` at each pass boundary."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            if not self.algorithm.iterative:
                raise ValueError(
                    f"algo {self.algo!r} is single-pass; it has no iterate"
                )
            self.algorithm.require_iterate("get_iterate")
            with _DEVICE_LOCK:
                arrays = self.algorithm.iterate_arrays()
            return arrays, {"iteration": self.iteration}

    def _install_iterate(self, arrays: Dict[str, np.ndarray]) -> None:
        """Validate + install an iterate and take the pass's zero state at
        it (under the job lock) — the tail the wire's set_iterate and the
        durable restore share: a tampered snapshot errors as cleanly as a
        mis-shaped push, and both reopen the pass with statistics of the
        INSTALLED iterate's shape."""
        if not self.algorithm.iterative:
            raise ValueError(
                f"algo {self.algo!r} is single-pass; set_iterate not applicable"
            )
        with _DEVICE_LOCK:
            self.algorithm.install_iterate(arrays)
            self.state = self.algorithm.zero_state()

    def set_iterate(self, arrays: Dict[str, np.ndarray], iteration: int) -> None:
        """Install a driver-pushed iterate and open the given pass: reset
        the pass statistics and staging, set the pass counter. This is the
        peer-daemon face of ``step`` — the primary daemon steps, every
        other daemon ``set_iterate``s the result, and the next scan's
        feeds carry the new pass_id everywhere."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            self._install_iterate(arrays)
            # The cached pass outlives the boundary (a rewind of the pass
            # that was filling it does not); stages go.
            self._close_pass(opens=int(iteration))
            self._clear_stages()
            self.committed.clear()
            self.iteration = int(iteration)
            self.pass_rows = 0
            self._maybe_snapshot()  # a pushed iterate is a pass boundary too
            self.touched = self._clock()  # exit stamp

    def step(
        self, params: Dict[str, Any], step_id: Optional[str] = None
    ) -> Dict[str, Any]:
        """Pass boundary for iterative jobs: apply the update at the end of
        one full dataset scan, reset the pass accumulator, and report
        convergence info for the driver's stop decision. ``step_id``
        (additive) makes a lost-ack REPLAY safe: the id of the last
        applied step returns its cached info instead of double-stepping."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            if not self.algorithm.iterative:
                raise ValueError(
                    f"algo {self.algo!r} is single-pass; step not applicable"
                )
            if (
                step_id is not None
                and self._last_step_info is not None
                and str(step_id) == self._last_step_id
            ):
                _M_REPLAY_HITS.inc(kind="step")
                return dict(self._last_step_info)
            # A new pass re-feeds every partition against the new iterate:
            # clear this pass's staging + committed set (zombie traffic from
            # the finished pass is fenced by pass_id, not by these maps).
            self._clear_stages()
            self.committed.clear()
            if self.pass_rows == 0:
                # A retried/premature step over an empty pass would corrupt
                # the iterate (zero Hessian solve / moved2=0 fake converge).
                raise ValueError(
                    "step with no rows fed this pass (duplicate step retry, "
                    "or executors have not fed yet)"
                )
            self.algorithm.require_iterate("step")
            # The boundary's span is the algorithm's to name; what it wraps
            # is the job's: the wait for the pass's folds, the update and
            # the next pass's opening state in ONE hold of the device lock
            # (it is of the iterate the update left: zeros, unless the
            # algorithm starts it from the pass just finished), the
            # fields' scalars to the host, the snapshot.
            #
            # Its four parts are children `<span>.<part>`, named here for
            # every algorithm (docs/observability.md "Phases"): `.update`
            # and `.state` DISPATCH — nothing in them waits unless the
            # algorithm reads a result itself (logreg's loss, the forest's
            # scorer) — `.read` is where the job waits for the device, and
            # `.snapshot` the durability point. What stands outside a
            # child is bookkeeping: the parent's self time reads near 0.
            span = self.algorithm.boundary_span

            def part(name: str):
                return trace_span(f"{span}.{name}") if span else contextlib.nullcontext()

            with trace_span(span) if span else contextlib.nullcontext():
                with _DEVICE_LOCK:
                    with part("update"):
                        fields = self.algorithm.step(self.state, params)
                    with part("state"):
                        self.state = self.algorithm.next_pass_state()
                self._close_pass()
                self.iteration += 1
                with part("read"):
                    scalars = {
                        k: float(v) if isinstance(v, jax.Array) else v
                        for k, v in fields.items()
                    }
                info = {
                    "iteration": self.iteration,
                    **scalars,
                    "pass_rows": self.pass_rows,
                }
                self.pass_rows = 0
                self.touched = self._clock()  # exit stamp (see fold)
                # Recorded for lost-ack replay. Also the per-pass
                # durability point: the snapshot lands BEFORE the step ack
                # (write-ahead), so a daemon that dies anywhere after here
                # resurrects at this exact boundary.
                with part("snapshot"):
                    self._maybe_snapshot()
                self._last_step_id = None if step_id is None else str(step_id)
                self._last_step_info = dict(info)
                return info

    def finalize(self, params: Dict[str, Any], drop: bool = False) -> Dict[str, np.ndarray]:
        with self.lock:
            self.algorithm.require_iterate("finalize")
            with _DEVICE_LOCK:
                result = self.algorithm.finalize(
                    self.state, params, self.rows, self.iteration
                )
            # The model is out: the fit scans no further pass.
            self._free_cached_pass()
            if drop:
                # set under the same lock acquisition so a straggler feed
                # blocked on it sees the flag and errors instead of folding
                # rows into a model that was already returned
                self.dropped = True
            return result


class _RowsJob(_Job):
    """A job whose algorithm is not `mergeable`: its state is the fed
    rows themselves, host blocks in arrival order (knn — the model is the
    database), not a device accumulator. `_Job`'s lock, stages,
    `(partition, attempt)` exactly-once, fencing and replay memories apply
    unchanged (a block only counts at commit); a block is neither padded
    nor placed, a stage's blocks are kept by partition instead of added,
    nothing merges across daemons (a multi-daemon fit builds a shard per
    daemon), and finalize builds the index and consumes the job.
    (ROADMAP D1: the one algorithm the daemon still knows.)"""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.part_rows: Dict[int, list] = {}  # partition → row blocks

    def _pad(self, x: np.ndarray, n: int):
        return np.ascontiguousarray(x, dtype=np.float32), None

    def _stage_zero(self):
        return []

    def _fold_batch(self, state, xb, mb, y, n, partition, offset):
        return state + [xb], ()  # host rows: no device, no device lock

    def _merge_stage(self, partition: int, state) -> None:
        # Keyed by partition (not arrival order) so the finalize
        # concatenation — and therefore the global row ids the index
        # returns — is deterministic partition-major, however the
        # concurrent commits interleaved.
        self.part_rows[partition] = state

    def export_state(self):
        raise ValueError(
            "knn job state is the dataset itself and does not "
            "merge across daemons — multi-daemon knn fits instead "
            "BUILD A SHARD per daemon (finalize with row_id_base; "
            "docs/protocol.md 'Sharded index across daemons')"
        )

    def peek_pass_state(self):
        raise ValueError(
            "knn job state is the dataset itself and does not "
            "reduce across daemons (build per-daemon shards "
            "instead; docs/protocol.md)"
        )

    def merge_remote(self, arrays, rows, merge_id=None) -> int:
        raise ValueError("knn jobs cannot merge remote state")

    def sample_rows(self, n: int, seed: int = 0) -> np.ndarray:
        """Seeded uniform sample of this knn job's COMMITTED rows
        (read-only; the job keeps accumulating). The cross-daemon
        quantizer-training op: a sharded IVF fit samples EVERY daemon's
        shard in proportion to its rows, so the shared quantizer's
        centroids cover the whole dataset instead of whichever slice
        locality-sticky routing parked on the primary (ADVICE r5(b))."""
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            blocks = list(self.state)
            for pid in sorted(self.part_rows):
                blocks.extend(self.part_rows[pid])
            total = sum(b.shape[0] for b in blocks)
            if total == 0:
                raise ValueError("sample_rows before any committed feed")
            if int(n) <= 0:
                raise ValueError(f"sample_rows n must be positive, got {n}")
            n = min(int(n), total)
            # shuffle=False: Floyd's O(n) sampling (same rationale as
            # build_ivf_flat's training pick).
            pick = np.sort(
                np.random.default_rng(int(seed)).choice(
                    total, n, replace=False, shuffle=False
                )
            )
            out = np.empty((n, blocks[0].shape[1]), blocks[0].dtype)
            base = 0
            taken = 0
            for b in blocks:
                hi = base + b.shape[0]
                j = np.searchsorted(pick, hi, side="left")
                if j > taken:
                    out[taken:j] = b[pick[taken:j] - base]
                    taken = j
                base = hi
            return out

    def build_knn_model(
        self, params: Dict[str, Any],
        extra_arrays: Optional[Dict[str, np.ndarray]] = None,
    ):
        """Build the KNN/ANN model from the accumulated rows and consume
        the job. Returns (core model, info arrays, global-id map); the
        daemon registers the model for `kneighbors` serving — the
        ~dataset-sized index never crosses to the driver (BASELINE config
        #5: 10M×768 would OOM it, the round-2 full-collect gap).

        Cross-daemon sharded build (the index SPANNING daemons —
        BASELINE config #5's pod-scale path):

        * ``params["row_id_base"]``: {partition: global row base} — this
          daemon holds only SOME partitions of the DataFrame; the id map
          translates its local (partition-major) row positions to the
          global partition-major ids every shard of the index reports, so
          a cross-daemon top-k merge needs no translation.
        * ``extra_arrays["centroids"]``: the shared pretrained quantizer
          (trained by the first daemon's build, O(nlist·d) on the wire) —
          every daemon buckets against identical centroids, making the
          union of per-daemon probes equal the single-index candidate set.
        * ``extra_arrays["train_rows"]``: an explicit quantizer training
          set — the driver's cross-shard sample (``sample_rows`` op per
          daemon, ADVICE r5(b)) so the trained quantizer covers the WHOLE
          dataset, not just the shard this daemon happens to hold.
          Ignored when ``centroids`` is supplied (nothing trains).
        * ``params["return_centroids"]``: ship the quantizer back in the
          info arrays (what the driver forwards to the peer builds).
        """
        extra_arrays = extra_arrays or {}
        with self.lock:
            if self.dropped:
                raise KeyError("job was finalized/dropped")
            self.touched = self._clock()
            blocks = list(self.state)
            for pid in sorted(self.part_rows):
                blocks.extend(self.part_rows[pid])
            if not blocks:
                raise ValueError("finalize before any feed: no rows")
            id_base = params.get("row_id_base") or None
            id_map = None
            if id_base is not None:
                if self.state:
                    raise ValueError(
                        "row_id_base needs fully partitioned feeds (direct "
                        "unpartitioned rows have no global position)"
                    )
                pieces = []
                for pid in sorted(self.part_rows):
                    n_p = sum(b.shape[0] for b in self.part_rows[pid])
                    base = id_base.get(str(pid), id_base.get(pid))
                    if base is None:
                        raise ValueError(
                            f"row_id_base missing partition {pid} "
                            f"(this daemon committed it)"
                        )
                    pieces.append(
                        np.arange(base, base + n_p, dtype=np.int64)
                    )
                id_map = (
                    np.concatenate(pieces) if pieces
                    else np.zeros(0, np.int64)
                )
            rows = np.concatenate(blocks)
            mode = str(params.get("mode", "exact"))
            metric = str(params.get("metric") or "euclidean")
            info = {
                "n_rows": np.asarray([rows.shape[0]], np.int64),
                "n_cols": np.asarray([rows.shape[1]], np.int64),
            }
            if mode == "ivf":
                import jax.numpy as jnp

                from spark_rapids_ml_tpu.models.knn import (
                    ApproximateNearestNeighborsModel,
                    _normalized_rows,
                    build_ivf_flat,
                    build_ivf_flat_device,
                )

                if metric == "inner_product":
                    raise ValueError(
                        "metric='inner_product' needs mode='exact' (IVF "
                        "partitions by L2 proximity)"
                    )
                if metric == "cosine":
                    # Same contract as the core fit: the index stores
                    # unit-normalized (augmented) rows; kneighbors
                    # normalizes queries into the query slot.
                    rows = _normalized_rows(rows, zero_slot=0)
                nlist = int(params["nlist"])
                seed = int(params.get("seed") or 0)
                cent_in = extra_arrays.get("centroids")
                if cent_in is not None:
                    cent_in = np.asarray(cent_in, np.float32)
                train_in = extra_arrays.get("train_rows")
                if train_in is not None:
                    train_in = np.asarray(train_in)
                    if metric == "cosine":
                        # Train in the same embedded space the index rows
                        # were just normalized into.
                        train_in = _normalized_rows(train_in, zero_slot=0)
                # Build-path choice (docs/ann-capacity.md): the device
                # build materializes the FULL (n, d) matrix on one chip —
                # fast, but capped by single-chip HBM. Past the cap
                # (config #5: 10M×768 f32 ≈ 31 GB vs 16 GB/chip) the host
                # build buckets in host RAM (quantizer still trains on a
                # device-sized sample) and no full copy ever lands on one
                # device: shard_index below placements each list shard
                # straight onto its own chip.
                build = str(params.get("build") or "auto")
                device_ok = rows.nbytes <= _IVF_DEVICE_BUILD_MAX_BYTES
                with _DEVICE_LOCK:
                    if build == "device" or (build == "auto" and device_ok):
                        index = build_ivf_flat_device(
                            jnp.asarray(rows), nlist=nlist, seed=seed,
                            centroids=cent_in, train_data=train_in,
                        )
                    elif build in ("host", "auto"):
                        index = build_ivf_flat(rows, nlist=nlist, seed=seed,
                                               mesh=self.mesh,
                                               centroids=cent_in,
                                               train_data=train_in)
                    else:
                        raise ValueError(
                            f"unknown build {build!r} (auto|device|host)"
                        )
                    model = ApproximateNearestNeighborsModel(index=index)
                    model._set(metric=metric)
                    model._index_metric = metric
                    if params.get("nprobe"):
                        model._set(nprobe=int(params["nprobe"]))
                    # Databases ≫ one chip's HBM serve from the whole mesh:
                    # the inverted lists shard over the data axis and
                    # queries run the sharded bucketed executor with an
                    # O(q·k·devices) all_gather merge (BASELINE config #5's
                    # capacity path).
                    if self.mesh.shape[DATA_AXIS] > 1:
                        model.shard_index(self.mesh)
                    info["nlist"] = np.asarray([nlist], np.int64)
                    info["maxlen"] = np.asarray(
                        [index.lists.shape[1]], np.int64
                    )
                    info["sharded"] = np.asarray(
                        [1 if model._shard_mesh is not None else 0], np.int64
                    )
                    if params.get("return_centroids"):
                        info["centroids"] = np.asarray(
                            jax.device_get(index.centroids), np.float32
                        )
            elif mode == "exact":
                from spark_rapids_ml_tpu.models.knn import NearestNeighborsModel

                model = NearestNeighborsModel(database=rows, mesh=self.mesh)
                model._set(metric=metric)
            else:
                raise ValueError(f"unknown knn mode {mode!r} (exact|ivf)")
            self.dropped = True  # rows are consumed by the built index
            return model, info, id_map


def _new_job(
    algo: str, n_cols: int, mesh,
    params: Optional[Dict[str, Any]] = None, clock=time.monotonic,
) -> _Job:
    """The job for a request's ``algo``: `_Job` over device statistics,
    `_RowsJob` where the algorithm keeps the rows themselves."""
    cls = _Job if job_algorithm(algo).mergeable else _RowsJob
    return cls(algo, n_cols, mesh, params, clock=clock)

def _model_class(algo: str):
    """Wire algo → core model class for daemon-side reconstruction from
    ``_model_data()`` arrays (the same payload model persistence stores)."""
    if algo == "pca":
        from spark_rapids_ml_tpu.models.pca import PCAModel

        return PCAModel
    if algo == "kmeans":
        from spark_rapids_ml_tpu.models.kmeans import KMeansModel

        return KMeansModel
    if algo == "linreg":
        from spark_rapids_ml_tpu.models.linear_regression import LinearRegressionModel

        return LinearRegressionModel
    if algo == "logreg":
        from spark_rapids_ml_tpu.models.logistic_regression import (
            LogisticRegressionModel,
        )

        return LogisticRegressionModel
    if algo == "scaler":
        from spark_rapids_ml_tpu.models.scaler import StandardScalerModel

        return StandardScalerModel
    if algo == "rf_classifier":
        from spark_rapids_ml_tpu.models.random_forest import (
            RandomForestClassificationModel,
        )

        return RandomForestClassificationModel
    if algo == "rf_regressor":
        from spark_rapids_ml_tpu.models.random_forest import (
            RandomForestRegressionModel,
        )

        return RandomForestRegressionModel
    raise ValueError(
        f"unknown model algo {algo!r} "
        "(pca|kmeans|linreg|logreg|scaler|rf_classifier|rf_regressor)"
    )


class _ServedModel:
    """A registered model serving ``transform``: fitted arrays live on
    device inside the core model's jit caches, resident across batches —
    the accelerator-resident columnar UDF of the reference
    (RapidsPCA.scala:128-161 → rapidsml_jni.cu:75-107), minus its
    per-batch PC re-upload (rapidsml_jni.cu:85)."""

    def __init__(
        self, algo: str, arrays: Dict[str, np.ndarray], params: Dict[str, Any],
        clock=time.monotonic,
    ):
        self._clock = clock
        cls = _model_class(algo)
        self.algo = algo
        self.model = cls._from_model_data("served", arrays)
        # Params configure serving behavior (e.g. scaler withMean/withStd);
        # unknown names are ignored so client and daemon can skew.
        known = {k: v for k, v in (params or {}).items() if self.model.hasParam(k)}
        if known:
            self.model._set(**known)
        self.lock = threading.Lock()
        self.touched = self._clock()
        self.id_map = None
        # Re-creatable registration (client holds the arrays): plain TTL.
        self.ttl_scale = 1.0
        # Fleet version pin (docs/protocol.md "Fleet & versioned
        # serving"): None = unversioned (the pre-fleet registration).
        # Immutable once set — a version under one name never changes;
        # new versions get new names (the fleet's `model@vN` convention).
        self.version: Optional[int] = None
        # AOT compile ledger (docs/protocol.md "AOT at registration"):
        # None until aot_warm runs; then {"buckets", "compiled", "jits"}.
        self.aot: Optional[Dict[str, Any]] = None

    @classmethod
    def from_model(
        cls, algo: str, model, clock=time.monotonic, id_map=None
    ) -> "_ServedModel":
        """Wrap an already-built core model (daemon-built KNN index) —
        bypasses the arrays/params reconstruction path. NOT re-creatable
        by clients (the source rows were consumed by the build), so the
        reaper holds it 8× longer than ordinary registrations before
        reclaiming the dataset-sized memory; owners should drop_model
        explicitly when done. ``id_map``: local row position → global
        partition-major row id, for an index shard that holds only some
        partitions (cross-daemon sharded serve)."""
        obj = cls.__new__(cls)
        obj._clock = clock
        obj.algo = algo
        obj.model = model
        obj.lock = threading.Lock()
        obj.touched = clock()
        obj.id_map = None if id_map is None else np.asarray(id_map, np.int64)
        obj.ttl_scale = 8.0
        obj.version = None
        obj.aot = None
        return obj

    def aot_warm(
        self, n_cols: int, buckets, k, dtype: str = "float32",
    ) -> Optional[Dict[str, Any]]:
        """True AOT of the serve bucket ladder: ``lower().compile()`` every
        reachable bucket's serving program via the model's
        ``_serve_aot_plan`` and hold the executables on the plan's jit
        wrappers. For the transform models those wrappers live in
        per-model-INSTANCE caches, so the executables die with the
        registration (a version pin keeps ITS executables); the exact-KNN
        plan's wrapper is the process-level ``_exact_knn_fn`` cache, where
        executables are shape-keyed and shared exactly like that jit's own
        dispatch cache (bounded by distinct index/query shapes, not by
        registration churn). Nothing executes here: unlike the zero-batch
        trace warmup, no garbage dispatch ever touches the device, and the
        primed shapes are immune to jit-cache churn. Returns the ack's
        ``{"buckets", "compiled"}`` (compiled = fresh executables built by
        THIS call), or None when the model publishes no plan — the caller
        then degrades to trace warmup."""
        plan_fn = getattr(self.model, "_serve_aot_plan", None)
        if plan_fn is None:
            return None
        jits: list = []
        compiled = 0
        buckets = [int(b) for b in buckets]
        for bucket in buckets:
            # Plan building may touch the device (the KNN plan's index
            # upload) — that part single-files with live dispatches; the
            # lower().compile() primes are pure host work and run
            # unlocked so a registration never stalls serving traffic.
            with _DEVICE_LOCK:
                entries = plan_fn(bucket, int(n_cols), dtype=dtype, k=k)
            if entries is None:
                return None
            for jit_obj, args in entries:
                if jit_obj.aot_prime(*args):
                    compiled += 1
                if all(j is not jit_obj for j in jits):
                    jits.append(jit_obj)
        # Hit/miss BASELINES per wrapper: a shared wrapper (the KNN case
        # above) carries other registrations' counts — this instance's
        # ledger reports only what happened since ITS warm. Published
        # under the model lock: aot_warm runs on the registering
        # connection's thread while other connection threads read
        # aot_status() (model_status/health), and an unlocked publish is
        # exactly the srml-check thread-shared-state class.
        with self.lock:
            self.aot = {
                "buckets": buckets,
                "compiled": compiled,
                "jits": [(j, j.aot_hits, j.aot_misses) for j in jits],
            }
        return {"buckets": buckets, "compiled": compiled}

    def aot_status(self) -> Optional[Dict[str, Any]]:
        """The served instance's compile ledger: primed buckets +
        executables, and the serve-time hit/miss counts since this
        registration's warm (a miss = a dispatch at a shape nothing
        primed, OR a held executable that rejected its args and degraded
        to the lazy jit — either way at most one lazy compile). None
        when AOT never ran for this registration. Caveat for plans whose
        wrapper is process-shared (exact KNN): two CONCURRENTLY-served
        registrations with identical index/query shapes pool their
        counts on the shared wrapper — the baselines separate
        sequential churn, not simultaneous same-shape traffic."""
        # The reader half of aot_warm's locked publish: ONE reference
        # snapshot, deliberately WITHOUT self.lock — transform/
        # kneighbors hold that lock across whole device dispatches, and
        # a monitoring scrape must never park behind in-flight
        # inference. The single read is safe: aot_warm builds the dict
        # fully before publishing the reference, so this sees one
        # complete generation of the ledger (never a mix), just
        # possibly the previous one for an instant.
        aot = self.aot
        if aot is None:
            return None
        return {
            "buckets": aot["buckets"],
            "compiled": aot["compiled"],
            "hits": sum(j.aot_hits - h0 for j, h0, _ in aot["jits"]),
            "misses": sum(j.aot_misses - m0 for j, _, m0 in aot["jits"]),
        }

    def transform(self, x: np.ndarray) -> Dict[str, np.ndarray]:
        # Serialize per-model: the jit caches aren't thread-safe to build
        # concurrently; steady-state calls just take the lock briefly.
        # _DEVICE_LOCK (innermost) single-files the device dispatch with
        # every other device-touching op in the process.
        with self.lock:
            self.touched = self._clock()
            with _DEVICE_LOCK:
                return self.model.transform_matrix(x)

    def kneighbors(self, queries: np.ndarray, k):
        with self.lock:
            self.touched = self._clock()
            if not hasattr(self.model, "kneighbors"):
                raise ValueError(
                    f"model algo {self.algo!r} does not serve kneighbors"
                )
            with _DEVICE_LOCK:
                dists, idx = self.model.kneighbors(queries, k)
            if self.id_map is not None:
                idx = np.asarray(idx)
                # −1 = "fewer than k found" padding stays −1.
                idx = np.where(
                    idx >= 0, self.id_map[np.maximum(idx, 0)], -1
                )
            return dists, idx


def _model_width(algo: str, arrays: Dict[str, np.ndarray]) -> Optional[int]:
    """Fitted feature width of a registered model's arrays — what a
    warmup-on-register pre-compile warms without the client having to
    say. None when the algo's arrays don't carry an unambiguous width
    (the registration then skips the eager warmup, never fails)."""
    try:
        if algo == "pca":
            return int(np.asarray(arrays["pc"]).shape[0])
        if algo == "scaler":
            return int(np.asarray(arrays["mean"]).shape[0])
        if algo == "linreg":
            return int(np.asarray(arrays["coefficients"]).reshape(-1).shape[0])
        if algo == "logreg":
            c = np.asarray(arrays["coefficients"])
            return int(c.shape[-1] if c.ndim == 2 else c.shape[0])
        if algo == "kmeans":
            # The wire payload key is the Spark-facing "clusterCenters"
            # (models/kmeans._model_data); "centers" kept as a fallback
            # for hand-built payloads.
            c = arrays.get("clusterCenters")
            if c is None:
                c = arrays["centers"]
            return int(np.asarray(c).shape[1])
        if algo in ("rf_classifier", "rf_regressor"):
            return int(np.asarray(arrays["bin_edges"]).shape[0])
    except (KeyError, IndexError):
        return None
    return None


def _resolve_k(served, k):
    """Canonical ``k`` for kneighbors dispatch and scheduler keying:
    ``None`` means the model's fitted k, resolved HERE so k-omitted and
    explicit-fitted-k traffic land in one batch queue (and a warmup with
    k omitted covers both)."""
    if k is not None:
        return int(k)
    getk = getattr(served.model, "getK", None)
    return int(getk()) if getk is not None else None


class DataPlaneDaemon:
    """Arrow-over-TCP accumulation server on the TPU host.

    Binds loopback by default; on a cluster, bind the host's NIC and keep
    the port executor-reachable only (the daemon trusts its callers the
    way the reference trusts its executors).
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        mesh=None,
        ttl: Optional[float] = None,
        token: Optional[str] = None,
        clock=time.monotonic,
        reap_interval: Optional[float] = None,
        max_connections: Optional[int] = None,
        max_staged_bytes: Optional[int] = None,
        retry_after_s: Optional[float] = None,
        state_dir: Optional[str] = None,
        serve_batching: Optional[bool] = None,
        max_models: Optional[int] = None,
        gossip_interval_s: Optional[float] = None,
        gossip_fanout: Optional[int] = None,
    ):
        from spark_rapids_ml_tpu import config

        self._host, self._port = host, port
        self._mesh = mesh
        self._ttl = ttl
        self._token = token
        # Injectable clock: TTL tests advance a fake clock instead of
        # wall-sleeping (r2 review weak #7); production uses monotonic.
        self._clock = clock
        self._reap_interval = reap_interval
        # Backpressure watermarks (0/None = unlimited): past either, the
        # daemon answers heavy ops with `busy` + a retry_after_s hint
        # instead of accepting work it will thrash on — graceful
        # degradation beats queueing until the host OOMs or every op
        # times out at once. Defaults come from config
        # (SRML_TPU_DAEMON_MAX_CONNECTIONS / _MAX_STAGED_BYTES).
        self._max_connections = int(
            config.get("daemon_max_connections")
            if max_connections is None else max_connections
        ) or None
        self._max_staged_bytes = int(
            config.get("daemon_max_staged_bytes")
            if max_staged_bytes is None else max_staged_bytes
        ) or None
        self._retry_after_s = float(
            config.get("daemon_retry_after_s")
            if retry_after_s is None else retry_after_s
        )
        # Serving scheduler (serve/scheduler.py): cross-connection
        # micro-batching for transform/kneighbors. Off by default — the
        # frozen protocol goldens (and every single-caller deployment)
        # behave byte-identically with it off.
        self._serve_batching = bool(
            config.get("serve_batching")
            if serve_batching is None else serve_batching
        )
        self._scheduler: Optional[scheduler_mod.RequestScheduler] = None
        # Served-model registry LRU cap (0/None = unbounded): the TTL
        # reaper only runs when a ttl is configured, so without this a
        # long-lived daemon's model registry grows without bound.
        self._max_models = int(
            config.get("daemon_max_models") if max_models is None
            else max_models
        ) or None
        self._active_conns = 0
        self._conn_socks: set = set()
        self._conn_threads: set = set()
        self._conns_lock = threading.Lock()
        self._started = self._clock()
        # Self-reported identity: host:port spellings alias (localhost vs
        # 127.0.0.1 vs FQDN), so the driver keys daemons by this id (from
        # ping) — never by the address string a client happened to use.
        # With a state_dir the id is PERSISTED there: a restarted daemon
        # is the same logical daemon (it resurrects its jobs), so it must
        # not masquerade as a new peer mid-fit.
        self.instance_id = uuid.uuid4().hex[:12]
        #: Incarnation id, fresh every start (durable or not): stamped on
        #: feed/seed/commit/step/finalize acks and exposed via ping +
        #: health, so a driver can detect that one pass's traffic spanned
        #: a restart — the fence that turns a poisoned row count into an
        #: explicit replay trigger (docs/protocol.md "Crash recovery").
        self.boot_id = uuid.uuid4().hex[:12]
        sd = config.get("daemon_state_dir") if state_dir is None else state_dir
        self._state_dir = str(sd) if sd else None
        if self._state_dir is not None:
            os.makedirs(self._state_dir, exist_ok=True)
            self.instance_id = self._durable_identity()
        self._jobs: Dict[str, _Job] = {}
        self._jobs_lock = threading.Lock()
        # Serializes durable restores (rare: post-restart only): without
        # it, the first scan's N feed tasks would all miss the registry
        # and run N npz-load + device-install restores for one job,
        # overcounting srml_daemon_job_restores_total N-fold.
        self._restore_lock = threading.Lock()
        self._models: Dict[str, _ServedModel] = {}
        self._models_lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._reaper_thread: Optional[threading.Thread] = None
        # Fleet gossip plane (serve/gossip.py; docs/protocol.md "Fleet
        # gossip & bootstrap"): this daemon's resident FleetView plus
        # the anti-entropy thread that exchanges it with peers. Interval
        # 0 (the default) runs NO thread — the view still answers
        # gossip_pull and merges gossip_push, so synchronous control
        # planes work with zero background traffic.
        self._gossip_interval_s = float(
            config.get("gossip_interval_s")
            if gossip_interval_s is None else gossip_interval_s
        )
        self._gossip_fanout = max(int(
            config.get("gossip_fanout")
            if gossip_fanout is None else gossip_fanout
        ), 1)
        self.fleet_view = gossip_mod.FleetView()
        # Peer selection rng: seeded from the boot id so two daemons
        # sharing a process never walk identical peer sequences.
        self._gossip_rng = random.Random(self.boot_id)
        self._gossip_thread: Optional[threading.Thread] = None
        # Telemetry plane (docs/observability.md): the journal-event
        # ring backing trace_pull + the flight recorder, the SLO
        # evaluator, and the evaluation thread's cadence. 0 interval =
        # no thread (pull ops still answer).
        self._trace_buffer = int(config.get("telemetry_trace_buffer") or 0)
        self._telemetry_eval_s = float(
            config.get("telemetry_eval_interval_s") or 0.0
        )
        self._telemetry_thread: Optional[threading.Thread] = None
        self._flight: Optional[flight_mod.FlightRecorder] = None
        self._slo: Optional[slo_mod.SloEvaluator] = None
        self._last_telemetry_ts: Optional[float] = None
        self._prev_deadline_sheds = 0.0
        self._ring_armed = False
        self._stop = threading.Event()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        self._mesh = self._mesh or default_mesh()
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((self._host, self._port))
        s.listen(64)
        self._sock = s
        self._port = s.getsockname()[1]
        # After the bind: a failed start() (port in use) is never
        # stop()ped by the caller, so nothing may be running yet — the
        # scheduler's dispatcher thread would leak per attempt.
        if self._serve_batching:
            self._scheduler = scheduler_mod.RequestScheduler(
                retry_after_s=self._retry_after_s
            ).start()
        # Mesh membership (docs/mesh.md): this daemon is now a peer on
        # the process's device plane. Registration — including a
        # re-registration of a durable identity after a restart — bumps
        # the membership epoch, so any in-flight collective fit
        # re-resolves instead of folding a rebooted daemon's (freshly
        # zeroed) partials.
        membership_mod.registry().register(
            self.instance_id, self.boot_id, self
        )
        # Gossip: this daemon's own replica record enters its resident
        # view AT START (post-bind — the advertised port is now real),
        # at an epoch minted from the same membership plane the
        # register() above just bumped, so a rebooted daemon's fresh
        # record dominates every view that still carries its old boot.
        adv_host = (
            "127.0.0.1" if self._host in ("0.0.0.0", "::", "")
            else self._host
        )
        self.fleet_view.observe_replica(
            self.instance_id, f"{adv_host}:{self._port}", self.boot_id,
            liveness="up",
        )
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="srml-dataplane-accept", daemon=True
        )
        self._accept_thread.start()
        if self._ttl is not None:
            self._reaper_thread = threading.Thread(
                target=self._reap_loop, name="srml-dataplane-reaper", daemon=True
            )
            self._reaper_thread.start()
        if self._gossip_interval_s > 0:
            self._gossip_thread = threading.Thread(
                target=self._gossip_loop, name="srml-dataplane-gossip",
                daemon=True,
            )
            self._gossip_thread.start()
        # Telemetry plane: arm the in-memory journal ring (the event
        # source for trace_pull and incident bundles — works with no
        # journal FILE at all), install the flight recorder as this
        # process's default, subscribe it to fired fault sites, and run
        # the evaluation thread (SLO burn rates + automatic triggers).
        if self._trace_buffer > 0:
            journal.ring_arm(self._trace_buffer)
            self._ring_armed = True
        self._flight = flight_mod.FlightRecorder(
            state_dir=self._state_dir,
            providers={
                "identity": lambda: {
                    **self._identity(),
                    "addr": f"{adv_host}:{self._port}",
                },
                "gossip": self.fleet_view.to_wire,
            },
        )
        flight_mod.set_default(self._flight)
        faults.subscribe(self._flight.on_fault)
        self._flight.arm_fatal()
        self._slo = slo_mod.SloEvaluator()
        if self._telemetry_eval_s > 0:
            self._telemetry_thread = threading.Thread(
                target=self._telemetry_loop, name="srml-dataplane-telemetry",
                daemon=True,
            )
            self._telemetry_thread.start()
        logger.info("data-plane daemon listening on %s:%d", self._host, self._port)
        return self

    @property
    def address(self):
        return self._host, self._port

    def stop(self) -> None:
        self._stop.set()
        # Leave the mesh FIRST (epoch bump): a reduce_mesh racing this
        # stop fails the epoch fence instead of folding a dying daemon.
        # Incarnation-scoped: a superseded object's late stop() must not
        # deregister the successor holding the same durable id.
        membership_mod.registry().unregister(
            self.instance_id, boot_id=self.boot_id
        )
        if self._scheduler is not None:
            # First: queued serving requests fail out and unblock their
            # connection threads before the sockets are torn down.
            self._scheduler.stop()
        if self._sock is not None:
            # Wake a blocked accept(): on Linux, close() alone does not
            # reliably interrupt a thread parked in accept() — every stop
            # then eats the full join timeout (measured: exactly 5 s per
            # daemon teardown across the whole test suite). A self-connect
            # pokes the acceptor, which re-checks _stop and exits.
            try:
                host = (
                    "127.0.0.1"
                    if self._host in ("0.0.0.0", "::", "")
                    else self._host
                )
                socket.create_connection((host, self._port), timeout=0.5).close()
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
        # A stopped daemon must STOP: shut down live connections too, so
        # in-flight clients see the death immediately (and heal against
        # the replacement) instead of talking to a zombie registry.
        # shutdown() — not close() — reliably unblocks a thread parked in
        # recv() on the same socket.
        with self._conns_lock:
            conns = list(self._conn_socks)
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        # ... and WAIT for the connection threads to unwind (bounded).
        # A thread that just acked its last request still owes trailing
        # side effects — the op span's journal line, request metrics —
        # and a stop() that returns before they land races every
        # stopped-then-inspect sequence (tests reading the journal file
        # the moment the daemon scope closes; an autoscaler draining a
        # replica then releasing its host). The sockets are already shut
        # above, so each thread is unwinding; the deadline only bounds a
        # thread parked in a long device dispatch.
        with self._conns_lock:
            conn_threads = list(self._conn_threads)
        deadline = self._clock() + 5.0
        me = threading.current_thread()
        for t in conn_threads:
            if t is me:
                continue
            while True:
                try:
                    t.join(timeout=max(0.0, deadline - self._clock()))
                    break
                except RuntimeError:
                    # Registered by the acceptor but not yet started: it
                    # starts momentarily and exits at once (the sockets
                    # are already shut) — re-join under the same
                    # deadline instead of leaking it past stop().
                    if self._clock() >= deadline:
                        break
                    time.sleep(0.002)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        if self._reaper_thread is not None:
            self._reaper_thread.join(timeout=5)
        if self._gossip_thread is not None:
            self._gossip_thread.join(timeout=5)
        if self._telemetry_thread is not None:
            self._telemetry_thread.join(timeout=5)
        if self._flight is not None:
            faults.unsubscribe(self._flight.on_fault)
            flight_mod.set_default(None)
        if self._ring_armed:
            journal.ring_disarm()
            self._ring_armed = False

    # -- telemetry evaluation ----------------------------------------------

    def _telemetry_loop(self) -> None:
        """The telemetry-evaluation thread: each tick snapshots the
        registry, evaluates SLO burn rates (publishing ``srml_slo_*``
        gauges), rolls the flight recorder's metrics delta, and checks
        the automatic incident triggers. Host-side math only — it never
        touches the device plane or a daemon lock, so it cannot stall
        serving traffic."""
        while not self._stop.wait(self._telemetry_eval_s):
            try:
                self._telemetry_tick()
            except Exception:
                logger.exception("telemetry tick failed")

    def _telemetry_tick(self) -> None:
        from spark_rapids_ml_tpu import config

        now = time.time()
        elapsed = (
            now - self._last_telemetry_ts
            if self._last_telemetry_ts is not None
            else self._telemetry_eval_s
        )
        # Tick bookkeeping is single-writer: only the telemetry thread
        # reaches this method (start() runs one), so the unlocked writes
        # here cannot race anything.
        self._last_telemetry_ts = now  # srml: disable=thread-shared-state
        elapsed = max(elapsed, 1e-6)
        snap = metrics_mod.snapshot()
        deltas = self._flight.observe(snap, now) if self._flight else {}
        # SLO burn rates: a breach is itself a flight-recorder trigger.
        if self._slo is not None and self._slo.objectives:
            evals = self._slo.tick(snap, now)
            breaches = [e["objective"] for e in evals if e["breach"]]
            if breaches and self._flight is not None:
                self._flight.trigger("slo_breach", {"objectives": breaches})
        if self._flight is None:
            return
        # Shed storm: total sheds/second over the tick across all ops.
        shed_cap = float(config.get("incident_shed_rate") or 0.0)
        if shed_cap > 0:
            sheds = sum(d["shed"] for d in deltas.values())
            if sheds / elapsed >= shed_cap:
                self._flight.trigger(
                    "shed_storm",
                    {"sheds": sheds, "window_s": elapsed},
                )
        # Deadline-breach rate: scheduler sheds with reason="deadline"
        # (requests whose deadline the backlog would already miss).
        dl_cap = float(config.get("incident_deadline_rate") or 0.0)
        if dl_cap > 0:
            dl_now = sum(
                float(s["value"])
                for s in snap.get("srml_scheduler_sheds_total", {}).get(
                    "samples", []
                )
                if s["labels"].get("reason") == "deadline"
            )
            dl_delta = max(0.0, dl_now - self._prev_deadline_sheds)
            # Same single-writer bookkeeping as _last_telemetry_ts.
            self._prev_deadline_sheds = dl_now  # srml: disable=thread-shared-state
            if dl_delta / elapsed >= dl_cap:
                self._flight.trigger(
                    "deadline_breach",
                    {"breaches": dl_delta, "window_s": elapsed},
                )

    def _reap_loop(self) -> None:
        """Evict jobs idle > ttl: a driver that crashed between feed and
        finalize must not leak d×d device buffers forever."""
        interval = (
            self._reap_interval
            if self._reap_interval is not None
            else max(min(self._ttl / 4.0, 30.0), 0.05)
        )
        while not self._stop.wait(interval):
            now = self._clock()
            evicted = []
            # Atomic check-and-remove under BOTH locks (round-2 advisor:
            # the old pop-then-revalidate left a window where a concurrent
            # feed saw "no such job" or recreated the name and lost rows).
            # Lock order is registry → job everywhere; the non-blocking
            # acquire skips jobs mid-op (their touched is being refreshed
            # anyway) instead of stalling the registry.
            with self._jobs_lock:
                for name, job in list(self._jobs.items()):
                    if now - job.touched <= self._ttl:
                        continue
                    if not job.lock.acquire(blocking=False):
                        continue  # op in flight — it refreshes touched
                    try:
                        if now - job.touched > self._ttl:
                            # Snapshot first (see the drop op): an
                            # evicted job must not be resurrectable, so
                            # the file dies before the registry entry.
                            self._discard_job_state(name)
                            job.release()
                            del self._jobs[name]
                            evicted.append((name, job))
                    finally:
                        job.lock.release()
            for name, job in evicted:
                logger.warning(
                    "evicted idle job %r (%.1fs > ttl %.1fs, %d rows fed)",
                    name, now - job.touched, self._ttl, job.rows,
                )
            # ensure_model registrations are stateless (clients re-register
            # on miss) and reap at the plain TTL; daemon-built KNN indexes
            # are NOT re-creatable — ttl_scale holds them 8× longer before
            # their dataset-sized memory is reclaimed (queries after that
            # get a clear evicted-refit error, not silent wrong answers).
            with self._models_lock:
                stale_models = [
                    n for n, m in self._models.items()
                    if now - m.touched > self._ttl * m.ttl_scale
                ]
                for n in stale_models:
                    del self._models[n]
            for n in stale_models:
                _M_MODEL_EVICTIONS.inc(reason="ttl")
                # An evicted durable index becomes disk-only NOW: its
                # snapshot's retention clock restarts so the sweep below
                # grants the full 8×-TTL window from this moment.
                self._touch_model_state(n)
                logger.warning("evicted idle served model %r", n)
            if self._state_dir is not None:
                # LIVE registrations keep their snapshot fresh (the
                # model-snapshot twin of boundary writes refreshing job
                # snapshots): without this, an index that stays live —
                # and therefore unswept — past 8× the TTL would carry a
                # build-time mtime, and a SIGKILL would let the next
                # boot's sweep reclaim it BEFORE first mention restores
                # it. With the refresh, the retention clock effectively
                # counts from eviction or death, never from the build.
                with self._models_lock:
                    live_now = list(self._models)
                for n in live_now:
                    self._touch_model_state(n)
            self._sweep_orphan_snapshots()

    def _sweep_orphan_snapshots(self) -> None:
        """Durable-state leak guard: a crashed fit whose driver also died
        leaves a job snapshot that is never mentioned again — never
        lazily restored, so never TTL-evicted through the registry.
        Sweep snapshot files with no live job once they have sat
        unmodified longer than the TTL (boundary writes refresh mtime,
        so an in-flight fit's snapshot is never swept) — the on-disk
        twin of the in-memory reaper above."""
        if self._state_dir is None:
            return
        with self._jobs_lock:
            live = {self._job_state_path(n) for n in self._jobs}
        with self._models_lock:
            live_models = {self._model_state_path(n) for n in self._models}
        try:
            names = os.listdir(self._state_dir)
        except OSError:
            return
        now_wall = time.time()  # file mtimes are wall-clock
        for fname in names:
            path = os.path.join(self._state_dir, fname)
            if fname.startswith("model-") and fname.endswith(".npz"):
                # Served-model snapshots: a LIVE registration's file is
                # never swept; an evicted one keeps an 8×-TTL disk
                # retention window (mtime refreshed at eviction — the
                # old in-memory "not re-creatable" hold, moved to disk)
                # before the dataset-sized file is reclaimed.
                if path in live_models:
                    continue
                try:
                    if now_wall - os.path.getmtime(path) > self._ttl * 8.0:
                        os.unlink(path)
                        logger.warning(
                            "swept served-model snapshot %s (evicted "
                            "> 8x ttl %.1fs ago with no drop_model)",
                            fname, self._ttl,
                        )
                except OSError:
                    pass  # raced a restore/drop, or already gone
                continue
            if fname.endswith(".tmp"):
                # A writer SIGKILLed between mkstemp and the atomic
                # rename (exactly the crash window this feature
                # engineers) leaves a .tmp the except-path cleanup
                # never ran for. In-flight writes are milliseconds
                # old; anything TTL-stale is litter.
                try:
                    if now_wall - os.path.getmtime(path) > self._ttl:
                        os.unlink(path)
                        logger.warning(
                            "swept stale temp file %s (crashed "
                            "mid-write)", fname,
                        )
                except OSError:
                    pass
                continue
            if not (fname.startswith("job-") and fname.endswith(".npz")):
                continue
            if path in live:
                continue
            try:
                if now_wall - os.path.getmtime(path) > self._ttl:
                    os.unlink(path)
                    logger.warning(
                        "swept orphan job snapshot %s (idle > ttl %.1fs "
                        "with no live job)", fname, self._ttl,
                    )
            except OSError:
                pass  # raced a restore/drop, or already gone

    # -- fleet gossip (serve/gossip.py; docs/protocol.md) -------------------

    def _gossip_peers(self) -> list:
        """Up to ``gossip_fanout`` peer addresses drawn from THIS
        daemon's view: live replica records that are not me. Reads a
        snapshot — no lock is held across the exchanges."""
        peers = [
            r["addr"] for r in self.fleet_view.replicas(liveness="up")
            if r["server_id"] != self.instance_id and r["addr"]
        ]
        if len(peers) <= self._gossip_fanout:
            return peers
        return self._gossip_rng.sample(peers, self._gossip_fanout)

    def _gossip_tick(self) -> Dict[str, int]:
        """One anti-entropy round: push this view to each chosen peer
        and merge the peer's view from the ack (push-pull in one RTT).
        A failed peer — dead, busy, or the ``gossip.push`` fault site —
        just drops THAT exchange for this tick: the view only ever
        merges complete acks, so a torn push cannot corrupt it."""
        from spark_rapids_ml_tpu.serve.client import DataPlaneClient

        pushed = dropped = 0
        for addr in self._gossip_peers():
            host, _, port = addr.rpartition(":")
            try:
                faults.checkpoint("gossip.push")
                with DataPlaneClient(
                    host or "127.0.0.1", int(port), token=self._token,
                    timeout=5.0, op_deadline_s=5.0, max_op_attempts=1,
                ) as c:
                    ack = c.gossip_push(self.fleet_view.to_wire())
                remote = ack.get("view")
                if isinstance(remote, dict):
                    self.fleet_view.merge(remote)
                pushed += 1
            except Exception as e:
                dropped += 1
                logger.debug("gossip push to %s dropped: %s", addr, e)
        _M_GOSSIP_TICKS.inc(outcome="partial" if dropped else "ok")
        return {"pushed": pushed, "dropped": dropped}

    def _gossip_loop(self) -> None:
        """The per-daemon gossip thread: one tick per
        ``gossip_interval_s`` until stop. Socket I/O only — it never
        touches the device plane or takes a daemon lock, so it can
        never stall (or deadlock against) serving traffic."""
        while not self._stop.wait(self._gossip_interval_s):
            try:
                self._gossip_tick()
            except Exception:
                # One bad tick must not kill anti-entropy forever.
                logger.exception("gossip tick failed")

    def _op_gossip_push(self, conn, req: Dict[str, Any]) -> None:
        """Additive anti-entropy op: merge the sender's view, answer
        with mine — the ack IS the pull half of push-pull. Never shed
        (it carries the fleet's control state) and never journaled
        (periodic chatter)."""
        remote = req.get("view")
        merged = 0
        if isinstance(remote, dict):
            merged = self.fleet_view.merge(remote)
        protocol.send_json(conn, {
            "ok": True, "merged": merged,
            "view": self.fleet_view.to_wire(), **self._identity(),
        })

    def _op_gossip_pull(self, conn) -> None:
        """Additive bootstrap/resync op: this daemon's FleetView,
        read-only — what a stateless client builds its routing table
        from (docs/protocol.md "Fleet gossip & bootstrap")."""
        protocol.send_json(conn, {
            "ok": True, "view": self.fleet_view.to_wire(),
            **self._identity(),
        })

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- durable job state (crash recovery; docs/protocol.md) --------------

    def _identity(self) -> Dict[str, str]:
        """The ack identity stamp: durable instance id + per-boot
        incarnation id. Stamped on every state-touching ack so a client
        (and the executor-side id cache above it) always learns who is
        REALLY holding its rows — a cached ping from before a restart
        must never outrank a live ack."""
        return {"id": self.instance_id, "boot_id": self.boot_id}

    def _durable_identity(self) -> str:
        """Load (or first-write) the persisted instance id: a restarted
        durable daemon keeps its identity so mid-fit drivers don't
        mistake it for a new peer. Atomic write via tmp+rename."""
        path = os.path.join(self._state_dir, "identity.json")
        try:
            with open(path, encoding="utf-8") as f:
                ident = str(json.load(f)["instance_id"])
            if ident:
                return ident
        except (OSError, ValueError, KeyError, TypeError):
            pass
        tmp = f"{path}.{uuid.uuid4().hex[:8]}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"instance_id": self.instance_id}, f)
        os.replace(tmp, path)
        return self.instance_id

    def _job_state_path(self, name: str) -> str:
        """Snapshot file for one job. Job names are caller-chosen strings:
        keep a readable sanitized prefix, disambiguate with a digest so
        two names that sanitize identically cannot share a snapshot."""
        safe = "".join(
            c if c.isalnum() or c in "._-" else "_" for c in name
        )[:64]
        digest = hashlib.sha1(name.encode()).hexdigest()[:10]
        return os.path.join(self._state_dir, f"job-{safe}-{digest}.npz")

    def _save_job_state(self, name: str, job: _Job) -> None:
        """The snapshot_cb target (runs under the job lock at every pass
        boundary, before the boundary op acks): iterate + the metadata a
        restore needs to re-run the job constructor."""
        checkpoint_mod.save_state(
            self._job_state_path(name),
            job.durable_arrays(),
            {
                "name": name,
                "algo": job.algo,
                "n_cols": job.n_cols,
                "params": job.params,
                "iteration": job.iteration,
                "rows": job.rows,
                "boot_id": self.boot_id,
            },
        )

    def _discard_job_state(self, name: str) -> None:
        """A finalized/dropped/evicted job must not resurrect."""
        if self._state_dir is not None:
            checkpoint_mod.discard_state(self._job_state_path(name))

    def _attach_durability(self, name: str, job: _Job) -> None:
        """Arm pass-boundary snapshots on an iterative job. Single-pass
        jobs (pca/linreg/knn) have no boundary before finalize — their
        recovery unit is the whole (re-runnable) scan, driver-side."""
        if self._state_dir is None or not job.algorithm.iterative:
            return
        job.snapshot_cb = lambda j, _n=name: self._save_job_state(_n, j)

    def _restore_job(self, name: str) -> Optional[_Job]:
        """Resurrect a job from its pass-boundary snapshot: re-run the
        constructor from the persisted creation params, install the
        iterate and pass counter. Pass-LOCAL state (stages, current-pass
        statistics, dedupe memories, the step replay cache) died with the
        old incarnation by design — the job reopens exactly at the
        boundary the snapshot recorded."""
        data = checkpoint_mod.load_state(self._job_state_path(name))
        if data is None:
            return None
        arrays, meta = data
        job = _new_job(
            str(meta["algo"]), int(meta["n_cols"]), self._mesh,
            meta.get("params") or {}, clock=self._clock,
        )
        with job.lock:
            if arrays:
                # The same validate+install+zero-state tail the wire
                # set_iterate runs — a tampered/truncated snapshot errors
                # cleanly here instead of crashing inside the next feed's
                # update, and the pass reopens with statistics of the
                # INSTALLED iterate's shape (a forest's frontier depth).
                job._install_iterate(arrays)
            job.iteration = int(meta["iteration"])
            job.rows = int(meta["rows"])
            job.touched = self._clock()
        self._attach_durability(name, job)
        # label is safe un-clamped: the _Job constructor only accepts the
        # closed algo set, so a tampered snapshot cannot mint series
        _M_JOB_RESTORES.inc(algo=str(job.algo))
        logger.warning(
            "restored job %r from durable state at pass %d "
            "(%d rows committed; snapshot by boot %s, this boot %s)",
            name, job.iteration, job.rows, meta.get("boot_id"), self.boot_id,
        )
        return job

    # -- durable served-model state (daemon-built KNN/ANN indexes) ---------

    def _model_state_path(self, name: str) -> str:
        """Snapshot file for one daemon-built index registration (same
        sanitize+digest scheme as job snapshots)."""
        safe = "".join(
            c if c.isalnum() or c in "._-" else "_" for c in name
        )[:64]
        digest = hashlib.sha1(name.encode()).hexdigest()[:10]
        return os.path.join(self._state_dir, f"model-{safe}-{digest}.npz")

    def _save_model_state(self, name: str, served: _ServedModel) -> bool:
        """Persist a daemon-BUILT index registration (the finalize-knn
        path — ``ensure_model`` registrations stay volatile: their
        clients hold the arrays and re-register on miss). Written
        BEFORE the finalize ack (write-ahead, like job snapshots): an
        acked build is a restorable one, so a durable daemon's index
        survives a SIGKILL and the 8×-TTL "not re-creatable" special
        case retires — the snapshot IS the re-creation source. Returns
        True when a snapshot was written."""
        if self._state_dir is None:
            return False
        model = served.model
        with _DEVICE_LOCK:  # index arrays may be device-resident
            arrays = {
                k: np.asarray(jax.device_get(v))
                for k, v in model._model_data().items()
                if v is not None
            }
        if served.id_map is not None:
            arrays["id_map"] = np.asarray(served.id_map, np.int64)
        params = {
            p: model.getOrDefault(p)
            for p in ("metric", "nprobe") if model.hasParam(p)
        }
        checkpoint_mod.save_state(
            self._model_state_path(name),
            arrays,
            {
                "name": name,
                "algo": served.algo,
                "params": params,
                "sharded": getattr(model, "_shard_mesh", None) is not None,
                "boot_id": self.boot_id,
            },
        )
        return True

    def _discard_model_state(self, name: str) -> None:
        """A dropped model must not resurrect (same contract as
        _discard_job_state; drop_model discards even with no live model
        — the abort must not leave a restorable ghost)."""
        if self._state_dir is not None:
            checkpoint_mod.discard_state(self._model_state_path(name))

    def _touch_model_state(self, name: str) -> None:
        """Restart an evicted registration's disk-retention clock: the
        moment the index leaves memory (TTL/LRU eviction) is when the
        snapshot becomes the only copy — the orphan sweep's 8×-TTL
        window counts from here, not from the build."""
        if self._state_dir is None:
            return
        try:
            os.utime(self._model_state_path(name), None)
        except OSError:
            pass

    def _restore_model(self, name: str) -> Optional[_ServedModel]:
        """Resurrect a daemon-built index from its snapshot: rebuild the
        core model from the persisted arrays, re-pin its serving params
        and (for ANN) the baked-in fit metric + sharded placement. The
        restored registration reaps at the PLAIN TTL — it is
        re-creatable from disk now, so the dataset-sized memory can be
        reclaimed and resurrected on the next query."""
        data = checkpoint_mod.load_state(self._model_state_path(name))
        if data is None:
            return None
        arrays, meta = data
        arrays = dict(arrays)
        id_map = arrays.pop("id_map", None)
        algo = str(meta["algo"])
        if algo == "ann":
            from spark_rapids_ml_tpu.models.knn import (
                ApproximateNearestNeighborsModel,
            )

            model = ApproximateNearestNeighborsModel._from_model_data(
                "served", arrays
            )
        else:
            from spark_rapids_ml_tpu.models.knn import NearestNeighborsModel

            model = NearestNeighborsModel._from_model_data("served", arrays)
            model._mesh = self._mesh
        params = meta.get("params") or {}
        known = {k: v for k, v in params.items() if model.hasParam(k)}
        if known:
            model._set(**known)
        if (
            algo == "ann"
            and meta.get("sharded")
            and self._mesh.shape[DATA_AXIS] > 1
        ):
            with _DEVICE_LOCK:
                model.shard_index(self._mesh)
        served = _ServedModel.from_model(
            algo, model, clock=self._clock, id_map=id_map
        )
        served.ttl_scale = 1.0  # re-creatable from disk: plain TTL
        logger.warning(
            "restored served model %r from durable snapshot (%s index; "
            "snapshot by boot %s, this boot %s)",
            name, algo, meta.get("boot_id"), self.boot_id,
        )
        return served

    def _lookup_model(self, name: str) -> Optional[_ServedModel]:
        """Registry lookup with a lazy durable restore — the served-model
        twin of :meth:`_lookup_job` (same single-filed restore, same
        race-safe publication, same honor-a-raced-drop re-check)."""
        with self._models_lock:
            served = self._models.get(name)
        if served is not None or self._state_dir is None:
            return served
        with self._restore_lock:
            with self._models_lock:
                served = self._models.get(name)
            if served is not None:
                return served
            restored = self._restore_model(name)
        if restored is None:
            return None
        evicted: list = []
        with self._models_lock:
            current = self._models.get(name)
            if current is None:
                self._models[name] = restored
                current = restored
                evicted = self._enforce_model_cap_locked(keep=name)
        self._log_lru_evictions(evicted)
        if current is restored and not os.path.exists(
            self._model_state_path(name)
        ):
            # A drop_model raced this restore and already discarded the
            # snapshot: honor the drop.
            with self._models_lock:
                if self._models.get(name) is restored:
                    del self._models[name]
            return None
        return current

    def _lookup_job(self, name: str) -> Optional[_Job]:
        """Registry lookup, falling back to a lazy durable restore. The
        restore happens outside the registry lock (it builds device
        state) but single-files on the restore lock with a re-check, so
        concurrent first-mentions after a restart produce ONE restore;
        publication is still race-safe against a concurrent create."""
        with self._jobs_lock:
            job = self._jobs.get(name)
        if job is not None or self._state_dir is None:
            return job
        with self._restore_lock:
            with self._jobs_lock:
                job = self._jobs.get(name)
            if job is not None:
                return job  # another thread restored/created it first
            restored = self._restore_job(name)
        if restored is None:
            return None
        with self._jobs_lock:
            current = self._jobs.get(name)
            if current is None:
                self._jobs[name] = restored
                current = restored
        if current is restored and not os.path.exists(
            self._job_state_path(name)
        ):
            # A drop/finalize raced this restore and already discarded
            # the snapshot (discard happens BEFORE unregistration, so a
            # missing file is authoritative): honor the abort — the
            # resurrected copy must not outlive it.
            with self._jobs_lock:
                if self._jobs.get(name) is restored:
                    del self._jobs[name]
            with restored.lock:
                restored.dropped = True
            return None
        return current

    # -- serving -----------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return  # socket closed
            t = threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True,
                name=f"srml-dataplane-{addr[1]}",
            )
            with self._conns_lock:
                # Re-checked under the registration lock: stop() sets
                # _stop BEFORE its self-connect poke and snapshots the
                # thread roster under this same lock, so a connection
                # landing after the stop (the poke itself, or a client
                # racing the shutdown) must NOT spawn a thread stop()
                # would never join.
                if self._stop.is_set():
                    try:
                        conn.close()
                    except OSError:
                        pass
                    return
                self._conn_threads.add(t)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        with self._conns_lock:
            self._active_conns += 1
            self._conn_socks.add(conn)
        try:
            faults.checkpoint("daemon.conn")
            self._serve_conn_inner(conn)
        except OSError:
            pass  # injected/real transport failure: the conn is simply gone
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._conns_lock:
                self._active_conns -= 1
                self._conn_socks.discard(conn)
                self._conn_threads.discard(threading.current_thread())

    def _serve_conn_inner(self, conn: socket.socket) -> None:
        with conn:
            while True:
                try:
                    req = protocol.recv_json(conn)
                except protocol.ProtocolError as e:
                    protocol.send_json(conn, {"ok": False, "error": str(e)})
                    return
                except OSError:
                    return  # transport died mid-read
                if req is None:
                    return  # client done
                op = _op_label(req.get("op"))
                t0 = time.perf_counter()
                outcome = "ok"
                exemplar = None
                try:
                    with _op_trace(op, req) as exemplar:
                        self._dispatch(conn, req)
                except (ConnectionError, TimeoutError):
                    # A transport-level failure (peer died mid-frame,
                    # injected drop) means the CONNECTION is broken, not
                    # the request — close it rather than answering on a
                    # dead or desynced wire. (NOT the whole OSError tree:
                    # PermissionError — the auth rejection — must reach
                    # the generic handler below and be ANSWERED.) Job
                    # state is untouched; the healed client replays on a
                    # fresh connection.
                    outcome = "transport"
                    return
                except Exception as e:  # surface to the caller, keep serving
                    outcome = "error"
                    logger.exception("request failed: %s", req.get("op"))
                    try:
                        protocol.send_json(conn, {"ok": False, "error": str(e)})
                    except OSError:
                        return
                finally:
                    # Per-op request accounting (a shed op counts "ok"
                    # here; srml_daemon_busy_sheds_total carries the shed).
                    # The op span's trace identity rides along as the
                    # sample's exemplar (utils/metrics.py).
                    _M_REQ_SECONDS.observe(
                        time.perf_counter() - t0, exemplar=exemplar, op=op
                    )
                    _M_REQUESTS.inc(op=op, outcome=outcome)

    def _dispatch(self, conn, req: Dict[str, Any]) -> None:
        op = req.get("op")

        def _drain_payload():
            # Keep the connection framing aligned for the error response:
            # payload-carrying ops already have their payload frame(s) in
            # flight when the JSON header is rejected.
            if op in _PAYLOAD_OPS:
                protocol.recv_frame(conn)
            elif op in ("ensure_model", "merge_state", "set_iterate",
                        "feed_raw", "finalize"):
                for _ in req.get("arrays") or []:
                    protocol.recv_frame(conn)

        # Auth first: an unauthenticated peer learns nothing (not even the
        # protocol version) beyond "unauthorized". Constant-time compare.
        if self._token is not None and not hmac.compare_digest(
            str(req.get("token", "")), self._token
        ):
            _drain_payload()
            raise PermissionError("unauthorized: bad or missing token")
        if op != "ping" and req.get("v") != protocol.PROTOCOL_VERSION:
            # ping is version-exempt (it's the hello: clients discover the
            # server version from its response before speaking further).
            # Missing v is rejected too: the freeze starts at v1 and every
            # conforming client declares its dialect (docs/protocol.md).
            _drain_payload()
            raise protocol.ProtocolError(
                f"protocol version mismatch: server speaks v{protocol.PROTOCOL_VERSION}, "
                f"request carried v={req.get('v')!r}; see docs/protocol.md"
            )
        faults.checkpoint("daemon.op")
        # Backpressure: past a watermark, shed HEAVY ops with a busy +
        # retry_after_s hint instead of accepting work the host will
        # thrash on. Ops that RELIEVE pressure (commit folds and frees
        # stages, finalize/drop free jobs) and O(1) control ops always
        # pass — shedding them would wedge the very recovery that brings
        # the daemon back under its watermark.
        if op in _SHEDDABLE_OPS:
            reason = self._overloaded()
            if reason is not None:
                _M_BUSY_SHEDS.inc(op=_op_label(op))
                _drain_payload()
                protocol.send_json(
                    conn,
                    {
                        "ok": False,
                        "busy": True,
                        "retry_after_s": self._retry_after_s,
                        "error": f"busy: {reason}",
                    },
                )
                return
        if op == "feed":
            self._op_feed(conn, req)
        elif op == "feed_raw":
            self._op_feed_raw(conn, req)
        elif op == "seed":
            self._op_seed(conn, req)
        elif op == "commit":
            job = self._get_job(req)
            rows = job.commit(
                int(req["partition"]),
                int(_opt(req, "attempt", 0)),
                req.get("pass_id"),
            )
            protocol.send_json(
                conn,
                {"ok": True, "rows": rows, **self._identity(),
                 **job.cache_ack()},
            )
        elif op == "rescan":
            self._op_rescan(conn, req)
        elif op == "finalize":
            self._op_finalize(conn, req)
        elif op == "step":
            job = self._get_job(req)
            info = job.step(_opt(req, "params", {}), step_id=req.get("step_id"))
            # The crash-between-passes chaos site: the step applied and
            # its durable snapshot (if armed) landed — a crash HERE is a
            # daemon dying at the exact pass boundary, ack unsent.
            faults.checkpoint("daemon.pass_boundary")
            protocol.send_json(conn, {"ok": True, **self._identity(), **info})
        elif op == "status":
            job = self._get_job(req)
            protocol.send_json(
                conn, {"ok": True, "rows": job.rows, "algo": job.algo, "n_cols": job.n_cols}
            )
        elif op == "drop":
            dropped = self._drop_job(str(req.get("job")))
            protocol.send_json(conn, {"ok": True, "dropped": dropped})
        elif op == "export_state":
            # The permanent-loss chaos site (with set_iterate and
            # reduce_mesh below): a crash HERE is a peer daemon dying at
            # the cross-daemon coordination moment — the elastic-fit
            # death the driver must classify, quarantine, and survive
            # (docs/protocol.md "Permanent daemon loss"). Unlike
            # daemon.op crashes, chaos tests pair this site with NO
            # restart.
            faults.checkpoint("daemon.vanish")
            job = self._get_job(req)
            arrays, meta = job.export_state()
            _send_arrays_counted(conn, "export_state", arrays, {"ok": True, **meta})
        elif op == "sample_rows":
            job = self._get_job(req)
            rows = job.sample_rows(
                int(_opt(req, "n", 1024)), int(_opt(req, "seed", 0) or 0)
            )
            _send_arrays_counted(
                conn, "sample_rows", {"rows": rows}, {"ok": True}
            )
        elif op == "merge_state":
            self._op_merge_state(conn, req)
        elif op == "mesh_info":
            self._op_mesh_info(conn)
        elif op == "reduce_mesh":
            self._op_reduce_mesh(conn, req)
        elif op == "gossip_push":
            self._op_gossip_push(conn, req)
        elif op == "gossip_pull":
            self._op_gossip_pull(conn)
        elif op == "get_iterate":
            job = self._get_job(req)
            arrays, meta = job.get_iterate()
            _send_arrays_counted(conn, "get_iterate", arrays, {"ok": True, **meta})
        elif op == "set_iterate":
            self._op_set_iterate(conn, req)
        elif op == "ensure_model":
            self._op_ensure_model(conn, req)
        elif op == "transform":
            self._op_transform(conn, req)
        elif op == "kneighbors":
            self._op_kneighbors(conn, req)
        elif op == "warmup":
            self._op_warmup(conn, req)
        elif op == "model_status":
            with self._models_lock:
                m = self._models.get(str(req.get("model")))
            status = {"ok": True, "exists": m is not None,
                      "algo": None if m is None else m.algo}
            # Additive: the registration's AOT compile ledger (primed
            # buckets + serve-time hits/misses), absent when AOT never
            # ran for this instance.
            aot = None if m is None else m.aot_status()
            if aot is not None:
                status["aot"] = aot
            protocol.send_json(conn, status)
        elif op == "drop_model":
            # Snapshot discard FIRST, and unconditionally (even with no
            # live model): drop is the release op, and an orphan model
            # snapshot would resurrect the released index at its next
            # mention (same ordering contract as the job `drop`).
            model_name = str(req.get("model"))
            self._discard_model_state(model_name)
            with self._models_lock:
                m = self._models.pop(model_name, None)
            protocol.send_json(conn, {"ok": True, "dropped": m is not None})
        elif op == "health":
            self._op_health(conn)
        elif op == "metrics":
            self._op_metrics(conn, req)
        elif op == "telemetry_pull":
            self._op_telemetry_pull(conn)
        elif op == "trace_pull":
            self._op_trace_pull(conn, req)
        elif op == "ping":
            protocol.send_json(
                conn,
                {"ok": True, "v": protocol.PROTOCOL_VERSION,
                 "id": self.instance_id, "boot_id": self.boot_id},
            )
        else:
            raise ValueError(f"unknown op {op!r}")

    # -- health & backpressure --------------------------------------------

    def _staged_bytes_total(self) -> int:
        with self._jobs_lock:
            return sum(j.staged_bytes for j in self._jobs.values())

    def _pass_cache_bytes_total(self) -> Optional[int]:
        """Bytes per device in jobs' pass caches; None when the key is off
        and no job was given a budget (the field and the gauge then stay
        away)."""
        from spark_rapids_ml_tpu import config

        with self._jobs_lock:
            held = [j.pass_cache_bytes for j in self._jobs.values()
                    if j._cache_budget]
        if not held and not int(config.get("daemon_pass_cache_mb")):
            return None
        return sum(held)

    def _overloaded(self, staged: Optional[int] = None) -> Optional[str]:
        """The watermark breach (None = healthy). Reads counters without
        job locks — a watermark is a load signal, not an invariant.
        ``staged``: a precomputed staged-bytes total, so callers that
        also REPORT the number (health) read it once — one _jobs_lock
        pass, and the reported value is the one the verdict used."""
        if self._max_connections is not None:
            with self._conns_lock:
                n = self._active_conns
            if n > self._max_connections:
                return (
                    f"{n} concurrent connections exceed the watermark "
                    f"({self._max_connections})"
                )
        if self._max_staged_bytes is not None:
            if staged is None:
                staged = self._staged_bytes_total()
            if staged > self._max_staged_bytes:
                return (
                    f"{staged} staged bytes exceed the watermark "
                    f"({self._max_staged_bytes}); commit or drop stages"
                )
        return None

    def _op_health(self, conn) -> None:
        """Additive observability op: load + liveness in O(jobs) time.
        Never shed — health is how a load balancer decides where to send
        traffic, and a daemon too busy to say "busy" looks dead."""
        staged_bytes = self._staged_bytes_total()
        reason = self._overloaded(staged=staged_bytes)
        with self._jobs_lock:
            active_jobs = len(self._jobs)
        with self._models_lock:
            served_models = len(self._models)
        with self._conns_lock:
            queue_depth = self._active_conns
        mesh_snap = membership_mod.registry().snapshot()
        resp = {
            "ok": True,
            "v": protocol.PROTOCOL_VERSION,
            "id": self.instance_id,
            "boot_id": self.boot_id,
            "durable": self._state_dir is not None,
            "queue_depth": queue_depth,
            "staged_bytes": staged_bytes,
            "active_jobs": active_jobs,
            "served_models": served_models,
            "uptime_s": float(self._clock() - self._started),
            "busy": reason is not None,
            # Additive: serving-scheduler state (config echo, per-model
            # queue depths, dispatched batches) — what a load balancer
            # or tools.top reads next to the watermark fields above.
            "scheduler": (
                {"enabled": False} if self._scheduler is None
                else self._scheduler.snapshot()
            ),
            # Additive: mesh membership (docs/mesh.md) — the epoch a
            # driver fences reduce_mesh with and how many co-resident
            # peers share this device plane (mesh_info has the roster).
            "mesh": {
                "epoch": mesh_snap["epoch"],
                "members": len(mesh_snap["members"]),
            },
        }
        if reason is not None:
            resp["retry_after_s"] = self._retry_after_s
            resp["busy_reason"] = reason
        cache_bytes = self._pass_cache_bytes_total()
        if cache_bytes is not None:
            # Additive, and apart from staged_bytes: cached passes are
            # not back-pressure, they are what the budget was given for.
            resp["pass_cache_bytes"] = cache_bytes
        protocol.send_json(conn, resp)

    def _op_metrics(self, conn, req: Dict[str, Any]) -> None:
        """Additive observability op: the process-wide metrics registry
        (per-op request counts + latency histograms, byte counters, busy
        sheds, replay hits, phase durations — docs/observability.md has
        the catalog). Level gauges are refreshed at scrape time, so the
        snapshot is self-consistent with what `health` would report.
        ``format``: "json" (default — the registry snapshot, histogram
        buckets cumulative) or "prometheus" (text exposition v0.0.4 in
        ``text``). Never shed: a scrape is O(registry) host work and is
        exactly what an operator needs most when the daemon is busy."""
        self._refresh_level_gauges()
        fmt = str(_opt(req, "format", "json"))
        base = {
            "ok": True,
            "v": protocol.PROTOCOL_VERSION,
            "id": self.instance_id,
            "uptime_s": float(self._clock() - self._started),
        }
        if fmt == "prometheus":
            protocol.send_json(
                conn, {**base, "text": metrics_mod.render_prometheus()}
            )
        elif fmt == "json":
            protocol.send_json(conn, {**base, "metrics": metrics_mod.snapshot()})
        else:
            raise ValueError(f"unknown metrics format {fmt!r} (json|prometheus)")

    def _refresh_level_gauges(self) -> None:
        """At-scrape refresh of the level gauges (staged bytes, jobs,
        models, connections, scheduler queue depths), so every exported
        snapshot is self-consistent with what `health` would report."""
        _M_STAGED.set(self._staged_bytes_total())
        cache_bytes = self._pass_cache_bytes_total()
        if cache_bytes is not None:
            _M_PASS_CACHE.set(cache_bytes)
        with self._jobs_lock:
            _M_JOBS.set(len(self._jobs))
        with self._models_lock:
            _M_MODELS.set(len(self._models))
        with self._conns_lock:
            _M_CONNS.set(self._active_conns)
        if self._scheduler is not None:
            self._scheduler.snapshot()  # refreshes the queue-depth gauge

    def _op_telemetry_pull(self, conn) -> None:
        """Additive wire-native telemetry export (docs/protocol.md
        "Telemetry plane ops"): everything an operator or fleet tool
        needs from this daemon in ONE cursor-free pull — the metrics
        registry as OpenMetrics text WITH per-bucket exemplars
        (``text``) and as the JSON snapshot (``metrics``), the xprof
        jit-ledger summary (``xprof``), and the config fingerprint
        (``fingerprint``; two replicas answering different fingerprints
        run different effective configs). Never shed, never journaled —
        it is the scrape path of ``tools/top.py --fleet`` and must
        answer while the daemon is melting down."""
        from spark_rapids_ml_tpu import config

        self._refresh_level_gauges()
        protocol.send_json(conn, {
            "ok": True,
            "v": protocol.PROTOCOL_VERSION,
            **self._identity(),
            "uptime_s": float(self._clock() - self._started),
            "text": metrics_mod.render_openmetrics(),
            "metrics": metrics_mod.snapshot(),
            "xprof": xprof_mod.snapshot(),
            "fingerprint": config.fingerprint(),
        })

    def _op_trace_pull(self, conn, req: Dict[str, Any]) -> None:
        """Additive wire-native trace export: journal events from the
        in-memory ring with ``seq`` greater than the request's
        ``cursor`` (0 = everything the ring still holds), plus this
        process's current ``seq`` — the caller stores it as its next
        cursor, so repeated pulls stream WITHOUT duplication
        (docs/protocol.md has the cursor contract). The cursor is
        per-daemon and per-boot: compare ``boot_id`` across pulls and
        restart from 0 when it changes. Events that aged out of the
        bounded ring between pulls are gone — the ring is a flight
        recorder, not a durable log."""
        cursor = int(_opt(req, "cursor", 0) or 0)
        events, seq = journal.tail(cursor)
        protocol.send_json(conn, {
            "ok": True,
            "v": protocol.PROTOCOL_VERSION,
            **self._identity(),
            "events": events,
            "seq": seq,
        })

    def _get_job(self, req) -> _Job:
        name = str(req.get("job"))
        job = self._lookup_job(name)  # registry, then durable restore
        if job is None:
            raise KeyError(f"no such job {name!r}")
        return job

    def _drop_job(self, name: str) -> bool:
        """Drop one job (the `drop` op's body, also run against peer
        daemons by a single-pass ``reduce_mesh``). Snapshot discard
        FIRST — unconditionally, even with no live job (drop is the
        abort op, and an orphan snapshot would resurrect the aborted job
        at its next mention), and BEFORE unregistration so a lazy
        restore racing this drop either finds the registry entry or
        finds no file; the restore path re-checks file existence after
        publishing to close the remaining load-in-flight window."""
        self._discard_job_state(name)
        with self._jobs_lock:
            job = self._jobs.pop(name, None)
        if job is not None:
            with job.lock:
                job.release()
        return job is not None

    def _op_feed(self, conn, req: Dict[str, Any]) -> None:
        import pyarrow as pa

        from spark_rapids_ml_tpu.bridge.arrow import table_column_to_matrix

        payload = _recv_payload_counted(conn, "feed")
        with pa.ipc.open_stream(payload) as reader:
            table = reader.read_all()
        input_col = _opt(req, "input_col", "features")
        x = table_column_to_matrix(table, input_col, req.get("n_cols"))
        y = None
        # (an unknown algo is refused where the job would be made)
        algorithm = JOB_ALGORITHMS.get(str(_opt(req, "algo", "pca")))
        if algorithm is not None and algorithm.needs_labels:
            label_col = _opt(req, "label_col", "label")
            if label_col not in table.column_names:
                raise KeyError(f"label column {label_col!r} not in batch")
            y = np.asarray(table.column(label_col).to_numpy(zero_copy_only=False))
        self._feed_validated(conn, req, x, y)

    def _op_feed_raw(self, conn, req: Dict[str, Any]) -> None:
        """`feed` semantics with a dependency-free payload: raw
        little-endian C-contiguous buffers (the response framing turned
        around) instead of an Arrow IPC stream — what makes a from-scratch
        client in any language ~100 lines (examples/cpp_client). Arrays:
        `x` (n, d) float32/float64 (required), `y` (n,) (linreg/logreg)."""
        arrays = _recv_arrays_aligned(conn, req)
        if "x" not in arrays:
            raise ValueError("feed_raw needs an 'x' array in the request spec")
        x = np.asarray(arrays["x"])
        if x.ndim != 2:
            raise ValueError(f"feed_raw 'x' must be 2-D, got shape {x.shape}")
        if x.dtype not in (np.float32, np.float64):
            raise ValueError(f"feed_raw 'x' must be float32/float64, got {x.dtype}")
        n_cols = req.get("n_cols")
        if n_cols is not None and int(n_cols) != x.shape[1]:
            raise ValueError(
                f"feed_raw 'x' width {x.shape[1]} != declared n_cols {n_cols}"
            )
        y = arrays.get("y")
        if y is not None:
            y = np.asarray(y).reshape(-1)
            if y.shape[0] != x.shape[0]:
                raise ValueError(
                    f"feed_raw 'y' length {y.shape[0]} != rows {x.shape[0]}"
                )
        self._feed_validated(conn, req, x, y)

    def _feed_validated(self, conn, req: Dict[str, Any], x, y) -> None:
        """Shared feed tail (Arrow and raw payloads land here): validate
        the batch BEFORE registering a job — a rejected first feed must
        not leave an orphan empty job (with its d×d device buffers)
        parked under the name forever."""
        name = str(req["job"])
        req_algo = str(_opt(req, "algo", "pca"))
        req_params = req.get("params") or {}
        # Asked of the algorithm's class, from the request alone (an
        # unknown algo is refused below, where the job would be made).
        algorithm = JOB_ALGORITHMS.get(req_algo)
        if algorithm is not None and algorithm.needs_labels:
            if y is None:
                raise ValueError(f"{req_algo} feed needs a label array")
            algorithm.check_labels(req_params, y)
        # Registry first, then the durable-state restore: a feed naming a
        # job a crashed predecessor snapshotted resurrects it here.
        job = self._lookup_job(name)
        if job is None and algorithm is not None:
            algorithm.check_first_batch(req_params, x)
        part = req.get("partition")
        for retry in (False, True):
            created = False
            if job is None:
                with self._jobs_lock:
                    job = self._jobs.get(name)
                    created = job is None
                    if created:
                        job = _new_job(req_algo, x.shape[1], self._mesh,
                                       req.get("params"), clock=self._clock)
                        self._attach_durability(name, job)
                        self._jobs[name] = job
            if job.algo != req_algo:
                raise ValueError(
                    f"job {name!r} is algo {job.algo!r}; feed requested "
                    f"{req_algo!r}"
                )
            mismatch = job.algorithm.feed_mismatch(req_params)
            if mismatch:
                raise ValueError(f"job {name!r} {mismatch}")
            try:
                job.fold(
                    x,
                    y,
                    partition=None if part is None else int(part),
                    attempt=int(_opt(req, "attempt", 0)),
                    pass_id=req.get("pass_id"),
                    feed_id=req.get("feed_id"),
                )
                break
            except ValueError:
                if created:
                    # A job whose very FIRST fold was rejected (mid-fit
                    # pass_id on a daemon that never saw the job, label
                    # validation …) must not stay parked under the name
                    # until TTL — every Spark retry of that task would
                    # create-then-fail again against the orphan's pass-0
                    # state (round-4 advisor).
                    with self._jobs_lock:
                        if self._jobs.get(name) is job:
                            with job.lock:
                                if (
                                    job.rows == 0
                                    and not job.staged
                                    and not job.committed
                                ):
                                    job.dropped = True
                                    del self._jobs[name]
                raise
            except KeyError:
                # fold met dropped=True. Usually that is a legitimately
                # finalized/aborted job — but the rejected-first-feed
                # cleanup above can RACE a concurrent valid first feed
                # (ADVICE r5): this thread fetched the job, a sibling's
                # rejected first fold then dropped-and-deleted it while
                # still empty, and our fold hit the tombstone. The
                # victim is identifiable — the drop only ever fires on
                # an EMPTY job that has also left the registry — so
                # re-resolve against the live registry and retry once
                # instead of failing a valid feed with a spurious error.
                if retry or created:
                    raise
                with job.lock:
                    empty = (
                        job.rows == 0
                        and not job.staged
                        and not job.committed
                    )
                with self._jobs_lock:
                    gone = self._jobs.get(name) is not job
                if not (empty and gone):
                    raise
                logger.info(
                    "feed into job %r raced a rejected-first-feed "
                    "cleanup; retrying against the live registry", name,
                )
                job = None
        protocol.send_json(
            conn,
            {"ok": True, "rows": job.rows, **self._identity(),
             **job.cache_ack()},
        )

    def _op_rescan(self, conn, req: Dict[str, Any]) -> None:
        """One pass from the job's cached pass (docs/protocol.md
        "rescan"): every cached batch folded again against the current
        iterate; the ack does not wait for the device. A job that cannot
        answer for exactly the rows it committed says so with
        ``no_cached_pass`` beside the error — not a transport fault, not
        a failure of the fit: the driver re-feeds that pass."""
        job = self._get_job(req)
        try:
            ack = job.rescan(req.get("pass_id"), rescan_id=req.get("rescan_id"))
        except protocol.NoCachedPass as e:
            protocol.send_json(
                conn,
                {"ok": False, "no_cached_pass": True, "error": str(e),
                 **self._identity()},
            )
            return
        protocol.send_json(
            conn,
            {"ok": True, **self._identity(), "pass_rows": ack["pass_rows"],
             "cached_rows": ack["cached_rows"],
             "cached_batches": ack["cached_batches"]},
        )

    def _op_seed(self, conn, req: Dict[str, Any]) -> None:
        """Driver-sent deterministic kmeans init: payload batch seeds the
        centers, rows are NOT folded (they arrive through the scan)."""
        import pyarrow as pa

        from spark_rapids_ml_tpu.bridge.arrow import table_column_to_matrix

        payload = _recv_payload_counted(conn, "seed")
        with pa.ipc.open_stream(payload) as reader:
            table = reader.read_all()
        name = str(req["job"])
        x = table_column_to_matrix(
            table, _opt(req, "input_col", "features"), req.get("n_cols")
        )
        params = req.get("params") or {}
        k_req = int(params.get("k", 0))
        if x.shape[0] < k_req:
            raise ValueError(f"seed batch has {x.shape[0]} rows < k={k_req}")
        job = self._lookup_job(name)
        if job is None:
            with self._jobs_lock:
                job = self._jobs.get(name)
                if job is None:
                    job = _new_job("kmeans", x.shape[1], self._mesh, params,
                                   clock=self._clock)
                    self._attach_durability(name, job)
                    self._jobs[name] = job
        job.seed_centers(x)
        protocol.send_json(
            conn, {"ok": True, "rows": job.rows, **self._identity()}
        )

    def _op_merge_state(self, conn, req: Dict[str, Any]) -> None:
        """Fold a peer daemon's exported job state into the named job —
        the cross-daemon reduce. Creates the job if absent (the request
        carries ``algo``/``n_cols``/``params`` like a first feed), so a
        driver can merge into a fresh primary even when every row was fed
        elsewhere. ``rows`` is the exporter's committed contribution."""
        arrays = _recv_arrays_aligned(conn, req)
        name = str(req["job"])
        req_algo = str(_opt(req, "algo", "pca"))
        contrib = int(_opt(req, "rows", 0))
        merge_id = req.get("merge_id")
        job = self._lookup_job(name)
        if job is None:
            n_cols = req.get("n_cols")
            if n_cols is None:
                raise ValueError("merge_state into an unknown job needs n_cols")
            # Merge into the fresh job BEFORE publishing it: a rejected
            # payload (shape/count mismatch) must not leave an orphan
            # mis-shaped job parked under the name (the same invariant
            # the feed path keeps for rejected first feeds).
            job = _new_job(req_algo, int(n_cols), self._mesh, req.get("params"),
                       clock=self._clock)
            self._attach_durability(name, job)
            rows = job.merge_remote(arrays, contrib, merge_id=merge_id)
            with self._jobs_lock:
                current = self._jobs.get(name)
                if current is None:
                    self._jobs[name] = job
            if current is None:
                # Response sent AFTER releasing _jobs_lock: a client with a
                # full TCP buffer here must stall only ITS connection, not
                # every job lookup daemon-wide (round-4 advisor).
                protocol.send_json(conn, {"ok": True, "rows": rows})
                return
            # Raced a concurrent creation: discard our unpublished copy
            # and fold into the published job instead (arrays land once).
            job = current
        if job.algo != req_algo:
            raise ValueError(
                f"job {name!r} is algo {job.algo!r}; merge_state carried "
                f"{req_algo!r}"
            )
        rows = job.merge_remote(arrays, contrib, merge_id=merge_id)
        protocol.send_json(conn, {"ok": True, "rows": rows})

    def _op_mesh_info(self, conn) -> None:
        """Additive op (docs/protocol.md "mesh_info"): the mesh
        membership snapshot — which daemons are co-resident peers on
        THIS device plane, their boot incarnations, and the fencing
        epoch. The driver reads it per pass to decide collective-vs-hub
        and stamps the epoch on ``reduce_mesh``."""
        snap = membership_mod.registry().snapshot()
        protocol.send_json(
            conn,
            {
                "ok": True,
                "v": protocol.PROTOCOL_VERSION,
                **self._identity(),
                "epoch": snap["epoch"],
                "members": snap["members"],
                "n_devices": (
                    int(self._mesh.devices.size) if self._mesh is not None else 0
                ),
            },
        )

    def _op_reduce_mesh(self, conn, req: Dict[str, Any]) -> None:
        """On-mesh collective reduce (docs/protocol.md "reduce_mesh"):
        fold co-resident peer daemons' committed pass partials into the
        named job directly on the device plane — the driver hub
        (export_state → wire → merge_state) collapses to one op whose
        data never leaves the devices. Safety order:

        1. **epoch fence**: the request's ``epoch`` must equal the live
           membership epoch — any join/leave/reboot since the driver's
           ``mesh_info`` refuses the whole reduce;
        2. **pre-reduce gather** of every peer's ``(boot_id, pass_rows,
           committed partitions)`` — the split-brain row-accounting
           checks the hub ran driver-side, now against live job state,
           all validated BEFORE anything folds (all-or-nothing);
        3. device fold in sorted-peer order (bitwise-identical to the
           hub), then optional peer-job drop (``drop_peers``, the
           single-pass algos' cleanup)."""
        name = str(req["job"])
        req_algo = str(_opt(req, "algo", "pca"))
        peers_spec = req.get("peers") or {}
        if not isinstance(peers_spec, dict) or not peers_spec:
            raise ValueError("reduce_mesh needs a non-empty peers map")
        # Permanent-loss chaos site (see export_state): a peer stopping
        # here leaves the mesh mid-reduce — the epoch fence refuses the
        # replay and the driver's death policy takes over.
        faults.checkpoint("daemon.vanish")
        # Replay dedupe FIRST — before the epoch fence and the peer
        # gather: a replay of an applied drop_peers reduce finds the
        # peer jobs gone (and possibly a changed epoch), and must get
        # its cached ack back, not a spurious failure.
        job = self._lookup_job(name)
        if job is not None:
            cached = job.seen_reduce(req.get("reduce_id"))
            if cached is not None:
                protocol.send_json(
                    conn,
                    {"ok": True, "rows": cached,
                     "reduced": len(peers_spec), **self._identity()},
                )
                return
        reg = membership_mod.registry()
        snap = reg.snapshot()
        if int(_opt(req, "epoch", -1)) != snap["epoch"]:
            raise RuntimeError(
                f"mesh membership changed (epoch {snap['epoch']} != "
                f"driver's {req.get('epoch')}): a daemon joined, left, or "
                "rebooted since mesh_info; replay the pass"
            )
        members = {m["id"]: m["boot_id"] for m in snap["members"]}
        gathered = []
        for pid in sorted(peers_spec):
            spec = peers_spec[pid] or {}
            boot = str(spec.get("boot_id"))
            if pid == self.instance_id:
                raise ValueError(
                    "reduce_mesh peers must not include the target daemon"
                )
            if members.get(pid) != boot:
                raise RuntimeError(
                    f"peer daemon {pid} is not a co-resident mesh member "
                    f"at boot {boot} (epoch {snap['epoch']}): it rebooted "
                    "or left — rows acked to the old incarnation are gone; "
                    "replay the pass"
                )
            peer = reg.get(pid, boot_id=boot)
            if peer is None:
                raise RuntimeError(f"peer daemon {pid} left the mesh")
            pjob = peer._lookup_job(name)
            if pjob is None:
                raise KeyError(f"peer daemon {pid} has no job {name!r}")
            state, pass_rows, committed, iteration = pjob.peek_pass_state()
            want_rows = int(_opt(spec, "rows", -1))
            if pass_rows != want_rows:
                raise RuntimeError(
                    f"daemon row-count mismatch at mesh reduce: tasks "
                    f"acked {want_rows} rows on peer {pid} but its job "
                    f"accounts {pass_rows} this pass; falling through "
                    "would corrupt the model — replay or refit"
                )
            want_parts = {int(p) for p in (spec.get("partitions") or [])}
            orphans = sorted(p for p in committed if p not in want_parts)
            lost = sorted(p for p in want_parts if p not in committed)
            if orphans or lost:
                parts = []
                if orphans:
                    parts.append(
                        f"partitions {orphans} committed on peer {pid} but "
                        "acked elsewhere (cross-daemon retry orphans)"
                    )
                if lost:
                    parts.append(
                        f"partitions {lost} acked on peer {pid} but not "
                        "committed"
                    )
                raise RuntimeError(
                    "partition accounting mismatch at mesh reduce: "
                    + "; ".join(parts)
                )
            gathered.append((pid, peer, pjob, state, pass_rows, iteration))
        job = self._lookup_job(name)
        if job is None:
            # Every row may have been fed to peers: create the target
            # like merge_state does, shaped from the first peer's job.
            first = gathered[0][2]
            job = _new_job(
                req_algo, first.n_cols, self._mesh, req.get("params"),
                clock=self._clock,
            )
            self._attach_durability(name, job)
            with self._jobs_lock:
                current = self._jobs.get(name)
                if current is None:
                    self._jobs[name] = job
                else:
                    job = current  # raced a concurrent creation
        if job.algo != req_algo:
            raise ValueError(
                f"job {name!r} is algo {job.algo!r}; reduce_mesh carried "
                f"{req_algo!r}"
            )
        for pid, _peer, pjob, _state, _rows, iteration in gathered:
            if pjob.algo != job.algo or pjob.n_cols != job.n_cols:
                raise ValueError(
                    f"peer {pid} job is ({pjob.algo}, n_cols="
                    f"{pjob.n_cols}); target is ({job.algo}, n_cols="
                    f"{job.n_cols})"
                )
            if iteration != job.iteration:
                raise RuntimeError(
                    f"peer {pid} is on pass {iteration}, target on "
                    f"{job.iteration}: a daemon missed a pass boundary — "
                    "replay the pass"
                )
        rows = job.merge_mesh(
            [(pid, state, n) for pid, _p, _j, state, n, _i in gathered],
            reduce_id=req.get("reduce_id"),
        )
        if _opt(req, "drop_peers", False):
            for pid, peer, _pjob, _state, _rows, _i in gathered:
                peer._drop_job(name)
        _M_MESH_REDUCES.inc(algo=job.algo)
        protocol.send_json(
            conn,
            {
                "ok": True,
                "rows": rows,
                "reduced": len(gathered),
                **self._identity(),
            },
        )

    def _op_set_iterate(self, conn, req: Dict[str, Any]) -> None:
        """Install a driver-pushed iterate. Additive recovery extension:
        when the job is unknown AND the request carries ``n_cols`` (plus
        ``algo``/``params`` like a first feed), the job is CREATED at the
        pushed iterate — the driver-held recovery ledger can re-seed a
        daemon that lost the job entirely (docs/protocol.md "Crash
        recovery"). Without ``n_cols`` an unknown job stays an error."""
        arrays = _recv_arrays_aligned(conn, req)
        # Permanent-loss chaos site (see export_state): the boundary
        # sync is where an iterative fit discovers a dead peer — the
        # frames are already drained, so the framing stays aligned.
        faults.checkpoint("daemon.vanish")
        name = str(req["job"])
        job = self._lookup_job(name)
        if job is None:
            n_cols = req.get("n_cols")
            if n_cols is None:
                raise KeyError(
                    f"no such job {name!r} (a recovery set_iterate that "
                    "should recreate it must carry n_cols/algo/params)"
                )
            # Grow-path chaos site (docs/protocol.md "Mid-fit daemon
            # join"): the creating set_iterate IS the admission
            # handshake — a joiner that crashes or stalls HERE must
            # leave the driver's membership untouched (the admit loop
            # registers nothing until this op acks).
            faults.checkpoint("daemon.join")
            job = _new_job(
                str(_opt(req, "algo", "pca")), int(n_cols), self._mesh,
                req.get("params"), clock=self._clock,
            )
            self._attach_durability(name, job)
            # Install BEFORE publishing: a rejected iterate (bad shape)
            # must not leave an orphan job parked under the name — the
            # same invariant merge_state keeps for rejected payloads.
            job.set_iterate(arrays, int(req["iteration"]))
            with self._jobs_lock:
                current = self._jobs.get(name)
                if current is None:
                    self._jobs[name] = job
            if current is None:
                protocol.send_json(conn, {"ok": True, **self._identity()})
                return
            job = current  # raced a concurrent creation: converge on it
        job.set_iterate(arrays, int(req["iteration"]))
        protocol.send_json(conn, {"ok": True, **self._identity()})

    def _enforce_model_cap_locked(self, keep: str) -> list:
        """LRU eviction past ``daemon_max_models`` (call under
        ``_models_lock``, right after registering ``keep``): a long-lived
        daemon's model registry must be bounded even with no TTL reaper.
        Re-creatable ``ensure_model`` registrations (ttl_scale 1.0) go
        first — clients simply re-register on miss; daemon-built KNN
        indexes are only reclaimed when nothing re-creatable remains
        (their owners get the explicit evicted-refit error on the next
        query, never a silent wrong answer). Returns the evicted names
        (log outside the lock)."""
        if self._max_models is None:
            return []
        evicted = []
        while len(self._models) > self._max_models:
            candidates = sorted(
                ((m.ttl_scale, m.touched, n)
                 for n, m in self._models.items() if n != keep),
            )
            if not candidates:
                break
            victim = candidates[0][2]
            del self._models[victim]
            _M_MODEL_EVICTIONS.inc(reason="lru")
            self._touch_model_state(victim)  # disk retention starts now
            evicted.append(victim)
        return evicted

    def _log_lru_evictions(self, evicted: list) -> None:
        for victim in evicted:
            logger.warning(
                "evicted served model %r (LRU, registry over the "
                "%d-model cap)", victim, self._max_models,
            )

    def _op_ensure_model(self, conn, req: Dict[str, Any]) -> None:
        """Register a fitted model for serving (idempotent). The request
        JSON carries the ``arrays`` spec; raw array frames follow — the
        same framing finalize uses in the response direction. First caller
        wins; concurrent registrations under one name are deduplicated."""
        arrays = _recv_arrays_aligned(conn, req)
        name = str(req["model"])
        algo = str(req["algo"])
        params = _opt(req, "params", {})
        # Additive fleet field: the registration's immutable version pin
        # (docs/protocol.md "Fleet & versioned serving").
        version = req.get("version")
        version = None if version is None else int(version)
        with self._models_lock:
            existing = self._models.get(name)
            if existing is None:
                served = _ServedModel(algo, arrays, params,
                                      clock=self._clock)
                served.version = version
                self._models[name] = served
                created = True
                evicted = self._enforce_model_cap_locked(keep=name)
            else:
                if existing.algo != algo:
                    raise ValueError(
                        f"model {name!r} is algo {existing.algo!r}; "
                        f"ensure_model requested {algo!r}"
                    )
                if (
                    version is not None
                    and existing.version is not None
                    and existing.version != version
                ):
                    # A version is IMMUTABLE under a name: silently
                    # accepting a re-register with different arrays
                    # would let two fleets' flips race into serving
                    # mixed versions under one key.
                    raise ValueError(
                        f"model {name!r} is registered at version "
                        f"{existing.version}; ensure_model carried "
                        f"version {version} — versions are immutable, "
                        "register the new version under its own name"
                    )
                if existing.version is None and version is not None:
                    existing.version = version  # adopt the late pin
                existing.touched = existing._clock()
                created = False
                evicted = []
        self._log_lru_evictions(evicted)
        warmed = (
            self._warmup_on_register(name, _model_width(algo, arrays))
            if created else None
        )
        ack: Dict[str, Any] = {"ok": True, "created": created}
        if warmed is not None:
            ack["warmup"] = warmed
        protocol.send_json(conn, ack)

    def _warmup_on_register(
        self, name: str, width: Optional[int]
    ) -> Optional[Dict[str, Any]]:
        """Optional eager warmup (ROADMAP 2b; config
        ``serve_warmup_on_register``): run the PR-5 bucket-ladder
        pre-compile AT registration — ensure_model payloads and
        daemon-built KNN index shards alike — so the first real request
        is a dispatch, not a jit compile. Synchronous on purpose: the
        registering caller's ack means "servable at full speed". A
        warmup failure degrades to lazy compiles (logged); it never
        fails the registration. Returns the warmup info, or None when
        not applicable (scheduler off, flag off, unknown width)."""
        if self._scheduler is None or width is None:
            return None
        from spark_rapids_ml_tpu import config

        if not bool(config.peek("serve_warmup_on_register")):
            return None
        with self._models_lock:
            served = self._models.get(name)
        if served is None:
            return None
        kind = (
            "kneighbors" if hasattr(served.model, "kneighbors")
            else "transform"
        )
        try:
            return self._warm_model(name, served, int(width), kind=kind,
                                    k=_resolve_k(served, None)
                                    if kind == "kneighbors" else None)
        except Exception as e:
            logger.warning(
                "warmup-on-register for %r failed (first requests will "
                "compile lazily): %s", name, e,
            )
            return None

    def _warm_model(
        self, name: str, served, n_cols: int, kind: str,
        k: Optional[int], dtype: str = "float32",
    ) -> Dict[str, Any]:
        """One warm pass over the reachable bucket ladder, AOT-first
        (docs/protocol.md "AOT at registration"): with ``serve_aot`` on
        and a model that publishes a ``_serve_aot_plan``, every ladder
        bucket's serving program is ``lower().compile()``d and the
        executables held on the served instance — first-request compile
        time leaves the latency path entirely, with no zero-batch device
        dispatches. The scheduler's per-instance shape ledger is
        pre-marked for the primed shapes, so the first real batch at a
        warmed bucket counts as a compile HIT. Models without a plan (or
        ``serve_aot`` off) run the PR-5 zero-batch trace warmup instead.
        Returns the warmup ack info; its additive ``aot`` field says
        which mode ran."""
        from spark_rapids_ml_tpu import config

        buckets = self._scheduler.reachable_buckets()
        if bool(config.peek("serve_aot")):
            # An AOT failure (a bucket that won't lower/compile) degrades
            # to the trace warmup below, exactly like a no-plan model —
            # the docs/protocol.md contract. Executables primed before
            # the failure stay on their wrappers (harmless hits). NOT
            # under _DEVICE_LOCK: the compiles are host-side, and a
            # registration must not stall other models' live traffic for
            # the whole ladder's compile time (aot_warm takes the lock
            # only around plan building, which may upload index data).
            try:
                info = served.aot_warm(n_cols, buckets, k, dtype)
            except Exception as e:
                logger.warning(
                    "AOT warmup for %r failed (degrading to trace "
                    "warmup): %s", name, e,
                )
                info = None
            if info is not None:
                # Pre-mark the scheduler's shape ledger: the compiles for
                # these shapes exist (they are the held executables), so
                # the first dispatched batch must read as a hit, exactly
                # like a trace-warmed shape. Done through the scheduler
                # (its lock) — _dispatch mutates the same set.
                self._scheduler.premark_shapes(
                    served,
                    [(kind, k, dtype, int(n_cols), int(b))
                     for b in info["buckets"]],
                )
                return {**info, "aot": True}
        out = self._scheduler.warmup(
            name, served, int(n_cols), kind=kind, k=k, dtype=dtype,
        )
        return {**out, "aot": False}

    @staticmethod
    def _version_fence(req: Dict[str, Any], name: str, served
                       ) -> Dict[str, Any]:
        """Fleet version pin (docs/protocol.md "Fleet & versioned
        serving"): when the request carries the additive ``version``
        field and this registration is versioned, a mismatch is refused
        (``serve_version_strict``, default on) — the replica missed a
        rollout or the router's table is stale; answering quietly would
        hand back the WRONG MODEL's numbers. Returns the ack's echo
        fields: the registration's version plus the request's
        ``fleet_epoch``, so every response names the exact (model,
        version, epoch) that produced it."""
        from spark_rapids_ml_tpu import config

        want = req.get("version")
        if (
            want is not None
            and served.version is not None
            and int(want) != served.version
        ):
            msg = (
                f"version mismatch on model {name!r}: request expects "
                f"v{int(want)}, this replica serves v{served.version} — "
                "a missed rollout or a stale routing table"
            )
            if bool(config.peek("serve_version_strict")):
                raise ValueError(msg)
            logger.warning("%s (serve_version_strict off: answering)", msg)
        echo: Dict[str, Any] = {}
        if served.version is not None:
            echo["version"] = served.version
        if req.get("fleet_epoch") is not None:
            echo["fleet_epoch"] = int(req["fleet_epoch"])
        return echo

    def _serve_dispatch(
        self, conn, req: Dict[str, Any], kind: str, name: str, served, x,
        k: Optional[int] = None,
    ):
        """Run one serving request through the micro-batching scheduler
        (when enabled and the request fits the bucket ladder) or solo.
        Returns the result, or None after answering a scheduler shed
        with the standard busy/retry_after_s response (payload already
        drained — framing stays aligned)."""
        sched = self._scheduler
        if sched is not None:
            # IVF/ANN kneighbors NEVER coalesce: the capacity-bucketed
            # candidate search shares per-list query slots across the
            # whole batch (models/knn.py "bucket (query, list) pairs ...
            # capacity C"), so co-batched — or scheduler-padded — rows
            # can EVICT a real query's candidates and change its
            # answer. Solo dispatch keeps the request's own rows the
            # only capacity holders (bitwise-exact), and the model's
            # internal query bucketer still bounds compiles. Exact-KNN
            # and every transform stay row-wise and batchable.
            ann = kind == "kneighbors" and getattr(served, "algo", "") == "ann"
            if not ann and sched.eligible(int(x.shape[0])):
                try:
                    return sched.submit(
                        name, served, kind, x, k=k,
                        deadline_s=req.get("deadline_s"),
                    )
                except scheduler_mod.SchedulerBusy as e:
                    _M_BUSY_SHEDS.inc(op=_op_label(kind))
                    protocol.send_json(
                        conn,
                        {
                            "ok": False,
                            "busy": True,
                            "retry_after_s": e.retry_after_s,
                            "error": f"busy: {e}",
                        },
                    )
                    return None
            elif x.shape[0]:  # 0-row isn't "larger than the ladder"
                sched.note_bypass(kind)
        if kind == "transform":
            return served.transform(x)
        return served.kneighbors(x, k)

    def _op_warmup(self, conn, req: Dict[str, Any]) -> None:
        """Additive op: pre-compile the scheduler's bucket ladder for a
        served model, so first-request latency is a dispatch, not a jit
        compile. ``n_cols`` names the feature width to warm (the model's
        fitted width); ``dtype`` (default float32) must match the dtype
        real traffic will carry — jit caches are dtype-keyed. With the
        scheduler disabled the op is an honest no-op (enabled: false)."""
        name = str(req["model"])
        served = self._lookup_model(name)  # registry, then durable restore
        if served is None:
            raise KeyError(f"no such model {name!r}; ensure_model first")
        if self._scheduler is None:
            protocol.send_json(
                conn,
                {"ok": True, "enabled": False, "buckets": [], "compiled": 0},
            )
            return
        n_cols = req.get("n_cols")
        if n_cols is None:
            raise ValueError("warmup needs n_cols (the model's feature width)")
        kind = _opt(
            req, "kind",
            "kneighbors" if hasattr(served.model, "kneighbors")
            else "transform",
        )
        if kind not in ("transform", "kneighbors"):
            raise ValueError(
                f"unknown warmup kind {kind!r} (transform|kneighbors)"
            )
        k = req.get("k")
        info = self._warm_model(
            name, served, int(n_cols), kind=str(kind),
            k=_resolve_k(served, k) if kind == "kneighbors" else None,
            dtype=str(_opt(req, "dtype", "float32")),
        )
        protocol.send_json(conn, {"ok": True, "enabled": True, **info})

    def _op_transform(self, conn, req: Dict[str, Any]) -> None:
        """Run a registered model over one Arrow batch; output arrays
        (role-keyed, see the model's ``_serve_outputs``) stream back as
        raw frames. The model's fitted arrays stay device-resident across
        batches and connections."""
        import pyarrow as pa

        from spark_rapids_ml_tpu.bridge.arrow import table_column_to_matrix

        payload = _recv_payload_counted(conn, "transform")
        with pa.ipc.open_stream(payload) as reader:
            table = reader.read_all()
        name = str(req["model"])
        served = self._lookup_model(name)  # registry, then durable restore
        if served is None:
            raise KeyError(f"no such model {name!r}; ensure_model first")
        x = table_column_to_matrix(
            table, _opt(req, "input_col", "features"), req.get("n_cols")
        )
        echo = self._version_fence(req, name, served)
        outs = self._serve_dispatch(conn, req, "transform", name, served, x)
        if outs is None:
            return  # shed with busy; the client retries
        _send_arrays_counted(
            conn, "transform", outs,
            {"ok": True, "rows": int(x.shape[0]), **echo},
        )

    def _op_kneighbors(self, conn, req: Dict[str, Any]) -> None:
        """Query a daemon-registered KNN/ANN index: query batch in, the
        (q, k) neighbor distances/indices back — the database-sized index
        never leaves the daemon."""
        import pyarrow as pa

        from spark_rapids_ml_tpu.bridge.arrow import table_column_to_matrix

        payload = _recv_payload_counted(conn, "kneighbors")
        with pa.ipc.open_stream(payload) as reader:
            table = reader.read_all()
        name = str(req["model"])
        served = self._lookup_model(name)  # registry, then durable restore
        if served is None:
            raise KeyError(
                f"no such model {name!r} — a daemon-built index this old "
                "was evicted (and any durable snapshot's retention "
                "window passed); refit the estimator"
            )
        q = table_column_to_matrix(
            table, _opt(req, "input_col", "features"), req.get("n_cols")
        )
        echo = self._version_fence(req, name, served)
        k = _resolve_k(served, req.get("k"))
        res = self._serve_dispatch(
            conn, req, "kneighbors", name, served, q, k=k,
        )
        if res is None:
            return  # shed with busy; the client retries
        dists, idx = res
        _send_arrays_counted(
            conn,
            "kneighbors",
            {"distances": np.asarray(dists, np.float64),
             "indices": np.asarray(idx, np.int64)},
            {"ok": True, "rows": int(q.shape[0]), **echo},
        )

    def _op_finalize(self, conn, req: Dict[str, Any]) -> None:
        # Optional raw array frames (additive to the v1 finalize: absent
        # "arrays" spec = the original JSON-only request): the sharded KNN
        # build receives the shared quantizer this way. Drained FIRST so
        # any later rejection leaves the framing aligned.
        extra = _recv_arrays_aligned(conn, req) if req.get("arrays") else {}
        job = self._get_job(req)
        params = _opt(req, "params", {})
        if isinstance(job, _RowsJob):
            # Build-and-serve: the index is registered daemon-side under
            # ``register_as``; only O(1) stats go back to the caller.
            name = str(params.get("register_as") or f"knn-{req.get('job')}")
            with self._models_lock:
                if name in self._models:
                    # First-wins like ensure_model: silently replacing a
                    # live registration would answer existing handles'
                    # queries from a different dataset's row-id space.
                    raise ValueError(
                        f"model name {name!r} is already registered; "
                        "pick a fresh register_as"
                    )
            model, info, id_map = job.build_knn_model(params, extra)
            algo = "ann" if params.get("mode") == "ivf" else "knn"
            served = _ServedModel.from_model(
                algo, model, clock=self._clock, id_map=id_map
            )
            with self._models_lock:
                if name in self._models:  # raced registration: first wins
                    raise ValueError(
                        f"model name {name!r} is already registered; "
                        "pick a fresh register_as"
                    )
                self._models[name] = served
                evicted = self._enforce_model_cap_locked(keep=name)
            self._log_lru_evictions(evicted)
            # Durable daemons write-ahead-snapshot the built index BEFORE
            # the finalize ack: an acked build is restorable across a
            # SIGKILL, and the registration reaps at the plain TTL (the
            # 8×-TTL "not re-creatable" hold retires — the snapshot is
            # the re-creation source; docs/protocol.md).
            if self._save_model_state(name, served):
                served.ttl_scale = 1.0
            # Same eager-warmup contract as ensure_model: the built index
            # shard's kneighbors ladder pre-compiles before the finalize
            # ack, so the first real query never pays the compile.
            self._warmup_on_register(name, int(info["n_cols"][0]))
            self._discard_job_state(str(req.get("job")))  # before pop (see drop)
            with self._jobs_lock:
                self._jobs.pop(str(req.get("job")), None)
            _send_arrays_counted(
                conn, "finalize", info,
                {"ok": True, "rows": job.rows, "model": name,
                 **self._identity()},
            )
            return
        drop = bool(_opt(req, "drop", True))
        arrays = job.finalize(params, drop=drop)
        # Unregister BEFORE sending: if the client disconnects mid-response
        # the name must not stay poisoned (dropped=True) in _jobs forever.
        # Snapshot discard before the pop (see the drop op's ordering).
        if drop:
            self._discard_job_state(str(req.get("job")))
            with self._jobs_lock:
                self._jobs.pop(str(req.get("job")), None)
        # pass_rows (additive): the rows behind the CURRENT pass's state —
        # a restored-at-boundary job answers 0 here, which is how a driver
        # tells "finalize over the pass I just fed" from "finalize over a
        # resurrected empty pass" (the kmeans cost would silently read 0).
        _send_arrays_counted(
            conn, "finalize", arrays,
            {"ok": True, "rows": job.rows, "pass_rows": job.pass_rows,
             **self._identity()},
        )
