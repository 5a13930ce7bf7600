"""Client side of the data-plane protocol — what a Spark task runs.

A task opens one connection, feeds its partition as one or more Arrow IPC
frames, and closes; the driver (or any one caller) finalizes. Socket-level
work only — no JAX on the executor side, mirroring how the reference keeps
executors JVM-only and the math behind the JNI boundary.

Self-healing: every op runs inside a reconnect loop. A connection-level
failure (``ConnectionError``, ``ProtocolError``, socket timeout, any
``OSError``) drops the cached socket, backs off with decorrelated jitter
(utils/retry.py — pure exponential backoff would synchronize a fleet of
executors into a thundering herd on daemon restart), reconnects, and
replays the op. Replay is exactly-once: ``feed``/``feed_raw`` carry a
client-generated ``feed_id`` and ``step`` a ``step_id`` that the daemon
dedupes, ``commit``/``seed`` are idempotent by design, and reads are
pure. A per-op deadline (``op_deadline_s``) bounds the TOTAL time spent
healing one op, separately from the per-socket-syscall ``timeout``. A
``busy`` response (daemon over its backpressure watermark) is honored by
waiting the daemon's ``retry_after_s`` hint (jittered) without burning a
reconnect attempt. ``finalize`` with ``drop=True`` is the one op replay
cannot make idempotent — see "Client retry obligations" in
docs/protocol.md.
"""

from __future__ import annotations

import random
import socket
import time
import uuid
from typing import Any, Dict, Optional, Tuple

import numpy as np

from spark_rapids_ml_tpu.serve import protocol
from spark_rapids_ml_tpu.utils import faults
from spark_rapids_ml_tpu.utils import journal
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils.logging import get_logger
from spark_rapids_ml_tpu.utils.retry import decorrelated_jitter

logger = get_logger("serve.client")

#: Ops whose acks prove rows/state landed on the answering incarnation —
#: the only acks that feed the boot fence. A ping's boot_id is excluded:
#: a restart between a task's identity ping and its first feed is
#: harmless (every row lands on the new incarnation), and counting it
#: would fail a fully consistent pass.
_STATE_ACK_OPS = frozenset((
    "feed", "feed_raw", "seed", "commit", "rescan", "step", "set_iterate",
    "merge_state", "finalize",
))

#: Client healing telemetry (process-wide registry; per-instance deltas
#: live in ``DataPlaneClient.stats``). A retry storm, a backoff pile-up,
#: or a fault-injection campaign is countable here — PR 2 proved the
#: healing works, these numbers say how often it RUNS.
_M_RECONNECTS = metrics_mod.counter(
    "srml_client_reconnects_total",
    "Connection-level failures healed by reconnecting, by op",
)
_M_REPLAYS = metrics_mod.counter(
    "srml_client_replays_total",
    "Ops replayed after possibly reaching the wire, by op",
)
_M_BACKOFF_SECONDS = metrics_mod.counter(
    "srml_client_backoff_seconds_total",
    "Seconds slept in reconnect backoff (decorrelated jitter)",
)
_M_BUSY_WAITS = metrics_mod.counter(
    "srml_client_busy_waits_total", "busy sheds honored with a wait, by op"
)
_M_BUSY_WAIT_SECONDS = metrics_mod.counter(
    "srml_client_busy_wait_seconds_total",
    "Seconds slept honoring busy retry_after_s hints",
)
_M_DEADLINE_EXPIRIES = metrics_mod.counter(
    "srml_client_deadline_expiries_total",
    "Ops abandoned because the per-op deadline expired, by op",
)
_M_FAULT_TRIPS = metrics_mod.counter(
    "srml_client_fault_trips_total",
    "Injected faults (utils/faults.py) observed by the healing loop, by op",
)


class DaemonBusy(RuntimeError):
    """Daemon shed the op under load; retry after ``retry_after_s``."""

    def __init__(self, message: str, retry_after_s: float):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class DataPlaneClient:
    def __init__(
        self,
        host: str,
        port: int,
        timeout: float = 120.0,
        token: Optional[str] = None,
        op_deadline_s: Optional[float] = None,
        max_op_attempts: int = 5,
        backoff_base_s: float = 0.05,
        backoff_max_s: float = 2.0,
        max_busy_wait_s: Optional[float] = None,
        trace_ctx: Optional[Dict[str, str]] = None,
    ):
        """``timeout`` bounds one socket syscall; ``op_deadline_s`` bounds
        one whole op including every reconnect/replay/busy-wait (None =
        attempts alone bound it); ``max_op_attempts`` counts connection
        failures per op; ``max_busy_wait_s`` caps cumulative busy-shed
        waiting per op. Its default (None) resolves to 60 s when NO
        deadline is set and to the deadline alone otherwise (a caller
        who budgeted 300 s must not be silently capped at 60); an
        EXPLICIT value is enforced alongside any deadline — a
        fleet-routed client sets it to 0 so a shed surfaces to the
        router immediately (serve/router.py).

        ``trace_ctx``: a fixed ``{"run", "span"}`` distributed-tracing
        context stamped on every request (additive wire field,
        docs/protocol.md) — how an executor-side client, whose process
        never opened the driver's journal run, still parents the
        daemon's spans into it. None (default): each op stamps the
        calling thread's CURRENT journal frame, so driver-side clients
        trace for free; with the journal off nothing is stamped and the
        wire bytes are exactly the pre-tracing ones."""
        self._addr = (host, int(port))
        self._timeout = timeout
        self._token = token
        self._sock: Optional[socket.socket] = None
        self._op_deadline = op_deadline_s
        self._max_attempts = max(1, int(max_op_attempts))
        self._backoff_base = backoff_base_s
        self._backoff_max = backoff_max_s
        # None = default policy: 60 s cap when no deadline bounds the op,
        # deadline-only otherwise. Explicit values always enforce.
        self._busy_wait_explicit = max_busy_wait_s is not None
        self._max_busy_wait = (
            60.0 if max_busy_wait_s is None else float(max_busy_wait_s)
        )
        self._trace_ctx = trace_ctx
        self._rng = random.Random()
        # Feed/step idempotency nonce: replayed ops carry the same id, so
        # the daemon can discard a duplicate whose first ack was lost.
        self._nonce = uuid.uuid4().hex[:12]
        self._seq = 0
        #: Healing counters (reconnects, replays, busy_waits) — cheap
        #: observability for chaos tests and ops dashboards.
        self.stats: Dict[str, int] = {
            "reconnects": 0, "replays": 0, "busy_waits": 0,
        }
        #: Every daemon incarnation (boot_id) whose STATE-TOUCHING acks
        #: (feed/seed/commit/step/… — see _STATE_ACK_OPS; pings are
        #: excluded) this client has seen. One entry is the normal case;
        #: two means rows/state straddled a restart — the incarnation
        #: fence the Spark estimator's pass replay keys on
        #: (docs/protocol.md "Crash recovery").
        self.seen_boot_ids: set = set()
        #: The instance id of the LAST ack received — live ground truth
        #: that outranks any cached ping: after a volatile restart the
        #: daemon answers with a new identity, and callers that keep an
        #: id cache (the executor-side feed task) must follow it.
        self.last_server_id: Optional[str] = None

    # -- connection --------------------------------------------------------

    def _conn(self, deadline: Optional[float] = None) -> socket.socket:
        if self._sock is None:
            faults.checkpoint("client.connect")
            # The connect syscall honors the op deadline too: a
            # blackholed host (SYNs dropped — the partition case the
            # healing targets) must cost the remaining budget, not the
            # full socket timeout per reconnect attempt.
            timeout = self._timeout
            if deadline is not None:
                timeout = min(timeout, max(deadline - time.monotonic(), 0.01))
            s = socket.create_connection(self._addr, timeout=timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # One client per thread by contract (class docstring); the
            # gossip thread builds a FRESH client per exchange, so this
            # state is thread-local by construction.
            self._sock = s  # srml: disable=thread-shared-state
        return self._sock

    def _reset(self) -> None:
        """Drop the cached socket: after a connection-level error it may
        be desynced mid-frame — reusing it fails confusingly."""
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            # Thread-local by the one-client-per-thread contract (see
            # _conn).
            self._sock = None  # srml: disable=thread-shared-state

    def close(self) -> None:
        # Same as _reset (one behavior, not two): a socket that errors on
        # close inside a `with` block must not mask the exception the
        # block is already unwinding with.
        self._reset()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _op_id(self) -> str:
        self._seq += 1
        return f"{self._nonce}-{self._seq}"

    def _attempt(
        self,
        req: Dict[str, Any],
        payload: Optional[bytes],
        arrays: Optional[Dict[str, np.ndarray]],
        want_arrays: bool,
        deadline: Optional[float] = None,
        sent: Optional[Dict[str, bool]] = None,
    ):
        """One request/response exchange on the cached connection; reads
        any response array frames INSIDE the attempt so a drop mid-response
        replays the whole op instead of desyncing. ``sent`` (out-param) is
        flipped once request bytes may have reached the wire — the line
        between a retry that merely reconnects and one that REPLAYS."""
        faults.checkpoint("client.op")
        sock = self._conn(deadline=deadline)
        if deadline is not None:
            # The op deadline must bound BLOCKED syscalls too, not just
            # the gaps between attempts: clamp this attempt's socket
            # timeout to the remaining budget (floor 10 ms so an
            # already-expired deadline fails fast instead of raising an
            # invalid-timeout error).
            sock.settimeout(
                min(self._timeout, max(deadline - time.monotonic(), 0.01))
            )
        req = {"v": protocol.PROTOCOL_VERSION, **req}
        if self._token is not None:
            req = {**req, "token": self._token}
        if sent is not None:
            sent["flag"] = True
        if arrays is not None:
            protocol.send_arrays(
                sock, {k: np.asarray(v) for k, v in arrays.items()}, req
            )
        else:
            protocol.send_json(sock, req)
            if payload is not None:
                protocol.send_frame(sock, payload)
        resp = protocol.recv_json(sock)
        if resp is None:
            raise ConnectionError("daemon closed the connection")
        if not resp.get("ok", False):
            if resp.get("busy"):
                raise DaemonBusy(
                    f"daemon busy: {resp.get('error')}",
                    float(resp.get("retry_after_s", 1.0)),
                )
            if resp.get("no_cached_pass"):
                raise protocol.NoCachedPass(f"daemon: {resp.get('error')}")
            raise RuntimeError(f"daemon error: {resp.get('error')}")
        boot = resp.get("boot_id")
        if boot is not None and req.get("op") in _STATE_ACK_OPS:
            self.seen_boot_ids.add(str(boot))
        sid = resp.get("id")
        if sid is not None:
            # Thread-local by the one-client-per-thread contract (see
            # _conn).
            self.last_server_id = str(sid)  # srml: disable=thread-shared-state
        outs = protocol.recv_arrays(sock, resp) if want_arrays else None
        return resp, outs

    def _op(
        self,
        req: Dict[str, Any],
        payload: Optional[bytes] = None,
        arrays: Optional[Dict[str, np.ndarray]] = None,
        want_arrays: bool = False,
    ):
        """Run one op through the self-healing loop (module docstring)."""
        # Distributed tracing (additive): stamp the op with the fixed
        # ctor context or the calling thread's current journal frame.
        # Stamped ONCE per op, outside the retry loop, so a replayed
        # request carries the same ctx as its first attempt.
        tc = self._trace_ctx or journal.trace_ctx()
        if tc:
            req = {**req, "trace_ctx": tc}
        start = time.monotonic()
        deadline = None if self._op_deadline is None else start + self._op_deadline
        attempt = 0
        busy_waited = 0.0
        delay = self._backoff_base
        while True:
            sent = {"flag": False}
            try:
                return self._attempt(req, payload, arrays, want_arrays,
                                     deadline=deadline, sent=sent)
            except protocol.FrameTooLarge:
                # Sender-side MAX_FRAME rejection: deterministic — the
                # payload will never fit, replaying cannot help. The JSON
                # header already went out though, so the connection is
                # mid-request: drop it (retry obligation #1) so the next
                # op doesn't have its header eaten as this op's payload.
                self._reset()
                raise
            except DaemonBusy as e:
                # Only the LOAD said no — but holding our connection open
                # through the wait would keep a connection-count watermark
                # pinned above its threshold forever (every shed client
                # parked, none draining). Release the slot, wait the hint
                # with jitter, reconnect on retry.
                self._reset()
                wait = e.retry_after_s * (0.5 + self._rng.random())
                now = time.monotonic()
                if deadline is not None and now + wait > deadline:
                    _M_DEADLINE_EXPIRIES.inc(op=str(req.get("op")))
                    raise
                if (
                    deadline is None or self._busy_wait_explicit
                ) and busy_waited + wait > self._max_busy_wait:
                    # The cap binds when it is the only bound (no
                    # deadline) or the caller set it EXPLICITLY — a
                    # fleet-routed client passes 0 so a shed surfaces
                    # immediately and the ROUTER retries elsewhere,
                    # deadline notwithstanding (serve/router.py). A
                    # default-cap client with a 300 s deadline keeps its
                    # full budget.
                    raise
                self.stats["busy_waits"] += 1
                busy_waited += wait
                _M_BUSY_WAITS.inc(op=str(req.get("op")))
                _M_BUSY_WAIT_SECONDS.inc(wait)
                logger.info(
                    "daemon busy (%s); retrying op %r in %.2fs",
                    self._addr, req.get("op"), wait,
                )
                time.sleep(wait)
            except (protocol.ProtocolError, OSError) as e:
                # Includes ConnectionError and socket timeouts. The cached
                # socket may be mid-frame — always drop it, even on the
                # final raise, so the NEXT op reconnects cleanly.
                self._reset()
                if isinstance(e, (faults.InjectedDrop, faults.InjectedRefusal)):
                    # Chaos accounting: the very faults test_chaos injects
                    # must be countable (the acceptance check that healing
                    # telemetry is real, not decorative).
                    _M_FAULT_TRIPS.inc(op=str(req.get("op")))
                attempt += 1
                if attempt >= self._max_attempts:
                    raise
                delay = decorrelated_jitter(
                    delay, self._backoff_base, self._backoff_max, self._rng
                )
                if deadline is not None and time.monotonic() + delay > deadline:
                    _M_DEADLINE_EXPIRIES.inc(op=str(req.get("op")))
                    raise
                self.stats["reconnects"] += 1
                _M_RECONNECTS.inc(op=str(req.get("op")))
                _M_BACKOFF_SECONDS.inc(delay)
                if sent["flag"]:
                    # Only a request that may have reached the wire is a
                    # REPLAY; a failed connect or pre-send fault is just a
                    # reconnect.
                    self.stats["replays"] += 1
                    _M_REPLAYS.inc(op=str(req.get("op")))
                logger.warning(
                    "connection failure on op %r to %s (attempt %d/%d, "
                    "reconnect in %.2fs): %s",
                    req.get("op"), self._addr, attempt, self._max_attempts,
                    delay, e,
                )
                time.sleep(delay)

    def _roundtrip(self, req: Dict[str, Any], payload: Optional[bytes] = None):
        resp, _ = self._op(req, payload=payload)
        return resp, self._sock

    # -- ops ---------------------------------------------------------------

    def ping(self) -> bool:
        """Hello: liveness + version handshake. ``ping`` is the one
        version-exempt op; the server echoes the protocol version it
        speaks, and a mismatch raises here rather than on the first real
        op (docs/protocol.md)."""
        resp, _ = self._roundtrip({"op": "ping"})
        server_v = resp.get("v")
        if server_v is not None and server_v != protocol.PROTOCOL_VERSION:
            raise protocol.ProtocolError(
                f"daemon speaks protocol v{server_v}; this client speaks "
                f"v{protocol.PROTOCOL_VERSION}"
            )
        return bool(resp["ok"])

    def health(self) -> Dict[str, Any]:
        """Daemon health snapshot (additive op): ``queue_depth`` (active
        connections), ``staged_bytes`` (uncommitted stage memory),
        ``active_jobs``, ``served_models``, ``uptime_s``, and ``busy``
        (True when the daemon is over a backpressure watermark and is
        shedding heavy ops; ``retry_after_s`` carries its hint)."""
        resp, _ = self._roundtrip({"op": "health"})
        return {k: v for k, v in resp.items() if k != "ok"}

    def gossip_push(self, view: Dict[str, Any]) -> Dict[str, Any]:
        """Anti-entropy exchange (additive op): push a FleetView wire
        dict (serve/gossip.py ``to_wire()``); the ack carries the
        daemon's own ``view`` back — push-pull in one round trip — plus
        ``merged`` (records the daemon adopted) and its identity."""
        resp, _ = self._roundtrip({"op": "gossip_push", "view": view})
        return {k: v for k, v in resp.items() if k != "ok"}

    def gossip_pull(self) -> Dict[str, Any]:
        """The daemon's gossiped FleetView wire dict (additive op):
        what a client bootstraps its routing table from given ONE seed
        address (docs/protocol.md "Fleet gossip & bootstrap")."""
        resp, _ = self._roundtrip({"op": "gossip_pull"})
        view = resp.get("view")
        return view if isinstance(view, dict) else {}

    def metrics(self, format: str = "json"):
        """Daemon metrics (additive op): the daemon process's registry
        snapshot — per-op request counts + latency histograms (cumulative
        buckets), rx/tx byte counters, busy sheds, replay hits, phase
        durations (docs/observability.md). ``format="json"`` (default)
        returns the snapshot dict; ``"prometheus"`` returns the text
        exposition (v0.0.4) string."""
        resp, _ = self._roundtrip({"op": "metrics", "format": format})
        if format == "prometheus":
            return str(resp.get("text", ""))
        return resp.get("metrics", {})

    def telemetry_pull(self) -> Dict[str, Any]:
        """One-shot wire-native telemetry export (additive op,
        docs/protocol.md "Telemetry plane ops"): ``text`` (OpenMetrics
        exposition WITH per-bucket exemplars), ``metrics`` (the JSON
        registry snapshot), ``xprof`` (jit-ledger summary),
        ``fingerprint`` (config fingerprint — differing fingerprints
        across a fleet mean differing effective configs), plus identity
        and ``uptime_s``. Cursor-free: every pull is the full current
        state."""
        resp, _ = self._roundtrip({"op": "telemetry_pull"})
        return {k: v for k, v in resp.items() if k != "ok"}

    def trace_pull(self, cursor: int = 0) -> Dict[str, Any]:
        """Journal events from the daemon's in-memory ring with ``seq``
        greater than ``cursor`` (additive op): ``{"events": […],
        "seq": N, "id": …, "boot_id": …}``. Store the returned ``seq``
        as the next call's cursor to stream without duplication; reset
        the cursor to 0 when ``boot_id`` changes (seq is per-boot). The
        ring is bounded — events older than the buffer are gone."""
        resp, _ = self._roundtrip({"op": "trace_pull", "cursor": int(cursor)})
        return {k: v for k, v in resp.items() if k != "ok"}

    def server_id(self) -> Optional[str]:
        """The daemon's self-reported instance id (from ping). Address
        strings alias (localhost vs 127.0.0.1 vs FQDN); this id is how
        callers decide whether two addresses are the same daemon. None
        when talking to a pre-id daemon."""
        resp, _ = self._roundtrip({"op": "ping"})
        sid = resp.get("id")
        return None if sid is None else str(sid)

    def server_info(self) -> Dict[str, Any]:
        """Full ping identity: ``{"v", "id", "boot_id"}``. ``id`` is the
        daemon's durable identity (stable across restarts on a
        state_dir daemon); ``boot_id`` is the incarnation, fresh every
        start — two boot_ids under one id IS a restart."""
        resp, _ = self._roundtrip({"op": "ping"})
        return {k: v for k, v in resp.items() if k != "ok"}

    @staticmethod
    def _to_ipc(data, input_col: str, label_col: str) -> bytes:
        import pyarrow as pa

        from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

        if isinstance(data, tuple):
            x, y = data
            table = pa.table(
                {
                    input_col: matrix_to_list_column(np.asarray(x)),
                    label_col: pa.array(np.asarray(y).reshape(-1)),
                }
            )
        elif isinstance(data, np.ndarray):
            table = pa.table({input_col: matrix_to_list_column(data)})
        elif isinstance(data, pa.RecordBatch):
            table = pa.Table.from_batches([data])
        else:
            table = data
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, table.schema) as writer:
            writer.write_table(table)
        return sink.getvalue().to_pybytes()

    def feed(
        self,
        job: str,
        data,
        algo: str = "pca",
        input_col: str = "features",
        label_col: str = "label",
        n_cols: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
        partition: Optional[int] = None,
        attempt: int = 0,
        pass_id: Optional[int] = None,
    ) -> int:
        """Feed one batch. ``data``: an Arrow Table/RecordBatch, or an
        (n, d) ndarray (optionally a (x, y) tuple for linreg/logreg).
        ``params`` configures job creation on the first feed (kmeans needs
        {"k": ...}). With ``partition`` set, the batch goes to that
        partition's staged state and only counts after :meth:`commit` —
        the exactly-once path for Spark tasks (retries restart the stage,
        duplicates of committed partitions are discarded). ``pass_id``
        fences iterative feeds to the job's current pass. Returns the
        job's total committed rows."""
        resp, _ = self._roundtrip(
            {
                "op": "feed",
                "job": job,
                "algo": algo,
                "input_col": input_col,
                "label_col": label_col,
                "n_cols": n_cols,
                "params": params or {},
                "partition": partition,
                "attempt": attempt,
                "pass_id": pass_id,
                # Replay dedupe: a reconnect replays this exact feed; the
                # daemon folds a given feed_id at most once per stage.
                "feed_id": self._op_id(),
            },
            payload=self._to_ipc(data, input_col, label_col),
        )
        return int(resp["rows"])

    def feed_raw(
        self,
        job: str,
        x: np.ndarray,
        y: Optional[np.ndarray] = None,
        algo: str = "pca",
        n_cols: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
        partition: Optional[int] = None,
        attempt: int = 0,
        pass_id: Optional[int] = None,
    ) -> int:
        """:meth:`feed` semantics with a dependency-free payload: raw
        little-endian buffers instead of Arrow IPC — the op that makes a
        from-scratch client (no Arrow library) ~100 lines in any
        language (docs/protocol.md; examples/cpp_client)."""
        arrays: Dict[str, np.ndarray] = {"x": np.asarray(x)}
        if y is not None:
            arrays["y"] = np.asarray(y).reshape(-1)
        resp = self._send_arrays_op(
            {
                "op": "feed_raw",
                "job": job,
                "algo": algo,
                "n_cols": n_cols,
                "params": params or {},
                "partition": partition,
                "attempt": attempt,
                "pass_id": pass_id,
                "feed_id": self._op_id(),
            },
            arrays,
        )
        return int(resp["rows"])

    def commit(
        self, job: str, partition: int, attempt: int = 0,
        pass_id: Optional[int] = None, with_meta: bool = False,
    ):
        """Commit a partition's staged feeds into the job state
        (idempotent; see :meth:`feed`). Returns total committed rows —
        or, with ``with_meta=True``, (rows, meta) where ``meta`` carries
        the ack's additive fields (``cached``, ``cached_rows``: present
        when the daemon keeps a pass cache, docs/protocol.md "rescan")."""
        resp, _ = self._roundtrip(
            {
                "op": "commit",
                "job": job,
                "partition": partition,
                "attempt": attempt,
                "pass_id": pass_id,
            }
        )
        if with_meta:
            return int(resp["rows"]), {
                k: v for k, v in resp.items() if k not in ("ok", "rows")
            }
        return int(resp["rows"])

    def rescan(self, job: str, pass_id: Optional[int] = None) -> Dict[str, Any]:
        """One pass from the job's cached pass (additive op;
        docs/protocol.md "rescan"): the daemon folds every batch it kept
        of the pass that filled its cache against the current iterate,
        and acks ``{pass_rows, cached_rows, cached_batches}`` without
        waiting for the device. Raises :class:`protocol.NoCachedPass`
        when the job cannot answer for exactly the rows it committed —
        re-feed that pass. Carries a ``rescan_id`` so a replay whose
        first ack was lost gets that ack again instead of an error."""
        resp, _ = self._roundtrip(
            {"op": "rescan", "job": job, "pass_id": pass_id,
             "rescan_id": self._op_id()}
        )
        return {k: v for k, v in resp.items() if k != "ok"}

    def seed_kmeans(
        self,
        job: str,
        data,
        k: int,
        input_col: str = "features",
        n_cols: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Deterministically seed a kmeans job's centers from a
        driver-chosen batch of ≥ k rows (rows are NOT folded — they arrive
        through the partition scan). Idempotent across retries."""
        self._roundtrip(
            {
                "op": "seed",
                "job": job,
                "input_col": input_col,
                "n_cols": n_cols,
                "params": {**(params or {}), "k": k},
            },
            payload=self._to_ipc(data, input_col, "label"),
        )

    def step(self, job: str, params: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Pass boundary for iterative jobs (kmeans/logreg): apply the
        Lloyd/Newton update over the pass's accumulated statistics and
        return convergence info ({"iteration", "moved2"|"delta", ...}).
        Carries a ``step_id`` so a replay whose first ack was lost gets
        the cached result of the step it already applied instead of
        double-advancing the iterate."""
        resp, _ = self._roundtrip(
            {"op": "step", "job": job, "params": params or {},
             "step_id": self._op_id()}
        )
        return {k: v for k, v in resp.items() if k != "ok"}

    def status(self, job: str) -> Dict[str, Any]:
        resp, _ = self._roundtrip({"op": "status", "job": job})
        return resp

    def drop(self, job: str) -> bool:
        resp, _ = self._roundtrip({"op": "drop", "job": job})
        return bool(resp["dropped"])

    def finalize(
        self, job: str, params: Dict[str, Any], drop: bool = True,
        arrays: Optional[Dict[str, np.ndarray]] = None,
        with_meta: bool = False,
    ):
        """Finalize a job; returns (result arrays, total rows) — or, with
        ``with_meta=True``, (arrays, rows, meta) where ``meta`` carries
        the response's additive fields (``pass_rows``, ``boot_id``: the
        crash-recovery reconciliation inputs, docs/protocol.md).
        ``arrays`` (optional, additive to protocol v1) sends raw array
        frames with the request — the sharded KNN build ships the shared
        quantizer this way.

        Replay-safe split (retry obligation #4): the wire request always
        carries ``drop: false`` so a reconnect replay after a lost
        response re-reads the same model instead of hitting ``no such
        job``; ``drop=True`` then issues the explicit idempotent ``drop``
        op once the arrays are safely in hand. (KNN finalizes consume the
        job either way — their response loss still needs a refit.)"""
        req = {"op": "finalize", "job": job, "params": params, "drop": False}
        resp, outs = self._op(req, arrays=arrays or None, want_arrays=True)
        if drop:
            self.drop(job)
        if with_meta:
            meta = {
                k: v for k, v in resp.items() if k not in ("ok", "arrays")
            }
            return outs, int(resp["rows"]), meta
        return outs, int(resp["rows"])

    # -- cross-daemon merge (multi-host data plane) -------------------------

    def _send_arrays_op(self, req: Dict[str, Any], arrays: Dict[str, np.ndarray]):
        """Request carrying raw array frames (ensure_model framing)."""
        resp, _ = self._op(req, arrays=arrays)
        return resp

    def export_state(self, job: str) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
        """Snapshot a job's committed O(d²) partials for a cross-daemon
        merge. Returns (state arrays keyed s0..sN in jax tree order,
        meta with rows/pass_rows/iteration/algo/n_cols). Read-only."""
        resp, arrays = self._op({"op": "export_state", "job": job},
                                want_arrays=True)
        meta = {k: v for k, v in resp.items() if k not in ("ok", "arrays")}
        return arrays, meta

    def merge_state(
        self,
        job: str,
        arrays: Dict[str, np.ndarray],
        rows: int,
        algo: str = "pca",
        n_cols: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
    ) -> int:
        """Fold a peer daemon's exported state into ``job`` (creating it
        when absent — ``algo``/``n_cols``/``params`` mirror a first feed).
        ``rows`` is the exporter's committed contribution; returns the
        job's new total."""
        resp = self._send_arrays_op(
            {
                "op": "merge_state",
                "job": job,
                "algo": algo,
                "n_cols": n_cols,
                "params": params or {},
                "rows": int(rows),
                # Replay dedupe: merges fold immediately; a reconnect
                # replay with the same id must not double-apply partials.
                "merge_id": self._op_id(),
            },
            arrays,
        )
        return int(resp["rows"])

    def mesh_info(self) -> Dict[str, Any]:
        """Mesh membership snapshot (additive op; docs/mesh.md): the
        daemons co-resident on the server's device plane — ``epoch``
        (fencing counter: bumps on every join/leave/reboot), ``members``
        (``[{"id", "boot_id"}]``) and ``n_devices``. Drivers read this
        per pass to decide the collective reduce vs the export/merge
        hub, and stamp the epoch on :meth:`reduce_mesh`."""
        resp, _ = self._roundtrip({"op": "mesh_info"})
        return {k: v for k, v in resp.items() if k != "ok"}

    def reduce_mesh(
        self,
        job: str,
        *,
        epoch: int,
        peers: Dict[str, Dict[str, Any]],
        algo: str = "pca",
        params: Optional[Dict[str, Any]] = None,
        drop_peers: bool = False,
    ) -> Dict[str, Any]:
        """On-mesh collective reduce (additive op; docs/protocol.md
        "reduce_mesh"): fold every named co-resident peer's committed
        pass partials into ``job`` on the device plane — O(d²) arrays
        never cross the wire. ``peers``: ``{peer_id: {"boot_id",
        "rows", "partitions"}}`` — the driver's task-ack accounting the
        daemon re-validates against live job state before anything
        folds (the pre-reduce (boot_id, pass_rows) handshake). The
        ``epoch`` must be the one :meth:`mesh_info` reported;
        membership changes in between refuse the reduce. A retried
        request replays safely (``reduce_id`` dedupe, like
        merge_state's ``merge_id``)."""
        resp, _ = self._op({
            "op": "reduce_mesh",
            "job": job,
            "epoch": int(epoch),
            "peers": peers,
            "algo": algo,
            "params": params or {},
            "drop_peers": bool(drop_peers),
            "reduce_id": self._op_id(),
        })
        return resp

    def sample_rows(self, job: str, n: int, seed: int = 0) -> np.ndarray:
        """Seeded uniform sample of a knn job's committed rows (additive
        op; read-only). The cross-daemon quantizer-training primitive:
        the driver samples every daemon's shard in proportion to its
        rows and hands the union to the quantizer-owning IVF build, so
        shared centroids cover the whole dataset (ADVICE r5(b))."""
        _, arrays = self._op(
            {"op": "sample_rows", "job": job, "n": int(n), "seed": int(seed)},
            want_arrays=True,
        )
        return arrays["rows"]

    def get_iterate(self, job: str) -> Tuple[Dict[str, np.ndarray], int]:
        """(iterate arrays, iteration) of an iterative job — kmeans
        {"centers"}; logreg {"w", "b"}."""
        resp, arrays = self._op({"op": "get_iterate", "job": job},
                                want_arrays=True)
        return arrays, int(resp["iteration"])

    def set_iterate(
        self, job: str, arrays: Dict[str, np.ndarray], iteration: int,
        algo: Optional[str] = None, n_cols: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Install a driver-pushed iterate on a daemon's job and open
        pass ``iteration`` (resets the pass statistics and staging).
        With ``n_cols`` (plus ``algo``/``params``, mirroring a first
        feed) the job is CREATED when the daemon does not know it — the
        recovery path that re-seeds a restarted daemon from the driver's
        ledger (docs/protocol.md "Crash recovery")."""
        req: Dict[str, Any] = {
            "op": "set_iterate", "job": job, "iteration": int(iteration),
        }
        if n_cols is None and (algo is not None or params is not None):
            # The caller asked for recreation context without the width:
            # derive it from the iterate itself (centers are (k, d);
            # coefficients are (d,) or (d, C)) rather than silently
            # sending a request the daemon can only answer with
            # "no such job".
            a = arrays.get("centers")
            if a is not None:
                n_cols = int(np.asarray(a).shape[1])
            elif arrays.get("bin_edges") is not None:
                # Forest iterate: edges are (n_cols, max_bins - 1).
                n_cols = int(np.asarray(arrays["bin_edges"]).shape[0])
            elif arrays.get("w") is not None:
                n_cols = int(np.asarray(arrays["w"]).shape[0])
        if n_cols is not None:
            req["algo"] = algo or "pca"
            req["n_cols"] = int(n_cols)
            req["params"] = params or {}
        self._send_arrays_op(req, arrays)

    # -- model serving (daemon-side transform) -----------------------------

    def ensure_model(
        self,
        name: str,
        algo: str,
        arrays: Dict[str, np.ndarray],
        params: Optional[Dict[str, Any]] = None,
        version: Optional[int] = None,
    ) -> bool:
        """Register a fitted model for serving (idempotent; first caller
        wins). ``arrays`` is the model's ``_model_data()`` payload; raw
        array frames follow the JSON header, mirroring the finalize
        response framing. ``version`` (additive) pins the registration
        to a fleet model version — immutable under the name; serving
        requests carrying a different ``version`` are refused
        (docs/protocol.md "Fleet & versioned serving"). Returns True
        when this call created it."""
        resp = self._send_arrays_op(
            {"op": "ensure_model", "model": name, "algo": algo,
             "params": params or {}, "version": version},
            arrays,
        )
        return bool(resp["created"])

    def model_status(self, name: str) -> Dict[str, Any]:
        """The registration's status: ``exists``, ``algo`` and, once a
        warmup primed it, the ``aot`` compile ledger (primed buckets,
        executables, serve-time hits/misses — docs/protocol.md "AOT at
        registration")."""
        resp, _ = self._roundtrip({"op": "model_status", "model": name})
        return {k: v for k, v in resp.items() if k != "ok"}

    def model_exists(self, name: str) -> bool:
        return bool(self.model_status(name)["exists"])

    def transform(
        self,
        name: str,
        data,
        input_col: str = "features",
        n_cols: Optional[int] = None,
        deadline_s: Optional[float] = None,
        version: Optional[int] = None,
        fleet_epoch: Optional[int] = None,
        with_meta: bool = False,
    ) -> Dict[str, np.ndarray]:
        """Run a registered model over one batch on the daemon's devices.
        ``data``: Arrow Table/RecordBatch or (n, d) ndarray. Returns the
        role-keyed output arrays (the model's ``_serve_outputs`` roles,
        e.g. {"output": ...} for PCA, {"prediction": ...} for KMeans).
        ``deadline_s`` (additive): the request's latency budget hint —
        a batching daemon sheds it with `busy` when its backlog would
        already miss it (docs/protocol.md "Serving scheduler").
        ``version``/``fleet_epoch`` (additive): the fleet routing pin —
        a versioned replica REFUSES a mismatched ``version`` instead of
        answering from the wrong model, and echoes both fields on the
        ack (docs/protocol.md "Fleet & versioned serving"). With
        ``with_meta`` the return is ``(arrays, meta)`` where ``meta``
        carries the ack's additive fields (``version``, ``fleet_epoch``)."""
        resp, arrays = self._op(
            {
                "op": "transform",
                "model": name,
                "input_col": input_col,
                "n_cols": n_cols,
                "deadline_s": deadline_s,
                "version": version,
                "fleet_epoch": fleet_epoch,
            },
            payload=self._to_ipc(data, input_col, "label"),
            want_arrays=True,
        )
        if with_meta:
            meta = {k: v for k, v in resp.items() if k not in ("ok", "arrays")}
            return arrays, meta
        return arrays

    def warmup(
        self,
        name: str,
        n_cols: int,
        k: Optional[int] = None,
        dtype: str = "float32",
        kind: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Pre-compile the serving scheduler's bucket ladder for a
        registered model (additive op): after a warmup, first-request
        latency is a dispatch, not a jit compile, and the recompile
        counters are primed for the whole ladder. ``dtype`` must match
        the dtype real query batches will carry (jit caches are
        dtype-keyed); ``kind`` defaults daemon-side to ``kneighbors``
        for KNN/ANN models and ``transform`` otherwise. On a daemon
        without batching enabled this is an honest no-op — the response
        carries ``enabled: false``."""
        resp, _ = self._roundtrip(
            {
                "op": "warmup",
                "model": name,
                "n_cols": int(n_cols),
                "k": k,
                "dtype": dtype,
                "kind": kind,
            }
        )
        return {kk: v for kk, v in resp.items() if kk != "ok"}

    def drop_model(self, name: str) -> bool:
        resp, _ = self._roundtrip({"op": "drop_model", "model": name})
        return bool(resp["dropped"])

    def finalize_knn(
        self,
        job: str,
        register_as: str,
        mode: str = "exact",
        nlist: Optional[int] = None,
        nprobe: Optional[int] = None,
        seed: int = 0,
        metric: str = "euclidean",
        row_id_base: Optional[Dict[Any, int]] = None,
        centroids: Optional[np.ndarray] = None,
        return_centroids: bool = False,
        train_rows_sample: Optional[np.ndarray] = None,
    ) -> Dict[str, np.ndarray]:
        """Build the index from a knn job's accumulated rows ON the daemon
        and register it as ``register_as`` for :meth:`kneighbors` serving.
        Returns only O(1) stats ({"n_rows", "n_cols"[, "nlist",
        "maxlen"]}) — the index itself never crosses the wire.

        Sharded (cross-daemon) builds: ``row_id_base`` maps each partition
        this daemon committed to its global row base (served ids become
        global partition-major positions); ``centroids`` ships a shared
        pretrained quantizer; ``return_centroids`` asks the build to hand
        its trained quantizer back (the driver forwards it to the peers);
        ``train_rows_sample`` ships an explicit quantizer training set
        (the driver's cross-shard ``sample_rows`` union — ADVICE r5(b)).
        """
        params: Dict[str, Any] = {
            "mode": mode, "register_as": register_as, "seed": seed,
            "metric": metric,
        }
        if nlist is not None:
            params["nlist"] = nlist
        if nprobe is not None:
            params["nprobe"] = nprobe
        if row_id_base is not None:
            params["row_id_base"] = {str(p): int(b) for p, b in row_id_base.items()}
        if return_centroids:
            params["return_centroids"] = True
        extra: Dict[str, np.ndarray] = {}
        if centroids is not None:
            extra["centroids"] = np.asarray(centroids, np.float32)
        if train_rows_sample is not None:
            extra["train_rows"] = np.asarray(train_rows_sample)
        arrays, _ = self.finalize(job, params, arrays=extra or None)
        return arrays

    def kneighbors(
        self,
        model: str,
        queries,
        k: Optional[int] = None,
        input_col: str = "features",
        n_cols: Optional[int] = None,
        deadline_s: Optional[float] = None,
        version: Optional[int] = None,
        fleet_epoch: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Query a daemon-registered index: returns (distances (q, k),
        indices (q, k)) with global partition-major row ids.
        ``deadline_s``: latency-budget hint; ``version``/``fleet_epoch``:
        the fleet routing pin — see :meth:`transform`."""
        _, arrays = self._op(
            {
                "op": "kneighbors",
                "model": model,
                "k": k,
                "input_col": input_col,
                "n_cols": n_cols,
                "deadline_s": deadline_s,
                "version": version,
                "fleet_epoch": fleet_epoch,
            },
            payload=self._to_ipc(queries, input_col, "label"),
            want_arrays=True,
        )
        return arrays["distances"], arrays["indices"]

    # -- conveniences ------------------------------------------------------

    def finalize_pca(
        self,
        job: str,
        k: int,
        mean_center: bool = True,
        solver: Optional[str] = None,
    ) -> Dict[str, np.ndarray]:
        arrays, _ = self.finalize(
            job, {"k": k, "mean_center": mean_center, "solver": solver}
        )
        return arrays

    def finalize_linreg(self, job: str, **params) -> Dict[str, np.ndarray]:
        arrays, _ = self.finalize(job, params)
        return arrays

    def finalize_kmeans(self, job: str) -> Dict[str, np.ndarray]:
        """Model after the last ``step``: {"centers", "cost", "n_iter"}.
        ``cost`` is the (unstepped) current pass's accumulated inertia —
        feed one extra pass without stepping to read the final cost."""
        arrays, _ = self.finalize(job, {})
        return arrays

    def finalize_logreg(self, job: str) -> Dict[str, np.ndarray]:
        """Model after the last ``step``: {"coefficients", "intercept", "n_iter"}."""
        arrays, _ = self.finalize(job, {})
        return arrays
