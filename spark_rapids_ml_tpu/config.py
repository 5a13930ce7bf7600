"""Runtime configuration (tier-2 flags).

The reference has a three-tier config system (SURVEY.md §5): algorithm params
(Spark ML Params — see core/params.py), runtime/cluster flags (Spark conf keys
like ``spark.rapids.sql.enabled``), and build flags. This module is the tier-2
equivalent: process-wide runtime knobs, settable programmatically or via
environment variables prefixed ``SRML_TPU_``.

Reference citations: spark conf tier at README.md:103-113 and
RapidsMLTest.scala:23-25 in /root/reference.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional


def _env(name: str, default: Any, cast: Callable[[str], Any],
         prefix: str = "SRML_TPU_") -> Any:
    raw = os.environ.get(prefix + name)
    if raw is None:
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError):
        return default


def _env_named(name: str, default: Any, cast: Callable[[str], Any]) -> Any:
    """Deployment-facing env keys carry their FULL name (no SRML_TPU_
    prefix): SRML_DAEMON_STATE_DIR, SRML_RUN_JOURNAL, SRML_SERVE_* —
    the knobs an operator sets on a daemon host, not a tuning flag."""
    return _env(name, default, cast, prefix="")


def _as_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def _as_bool_or_auto(s: str):
    return "auto" if s.strip().lower() == "auto" else _as_bool(s)


def backend_is_tpu() -> bool:
    """THE predicate for "this process computes on a TPU": every "auto"
    profile key, every Pallas gate and every interpret-mode choice asks
    it. A backend that fails to initialise raises here — answering False
    would silently select the float32/XLA profile on a TPU host."""
    import jax

    return jax.default_backend() == "tpu"


# Lazy resolution of "auto" defaults at get() time: the shipped TPU profile
# IS the measured configuration (bf16 MXU compute / f32 accumulation /
# Pallas kernels) — a fresh checkout on a real TPU reproduces the headline
# bench numbers with zero env vars, while CPU meshes (tests, dev boxes)
# resolve to the portable f32/XLA path unchanged. Explicit values (set()
# or SRML_TPU_* env) always win over "auto".
_AUTO_RESOLVERS: Dict[str, Callable[[], Any]] = {
    "use_pallas": backend_is_tpu,
    "compute_dtype": lambda: "bfloat16" if backend_is_tpu() else "float32",
}

# One visible breadcrumb per process when an "auto" key flips to the TPU
# profile (round-4 advisor): default model precision on TPU diverges from
# the float32 path CPU CI validates, and an upgrading user should see that
# happened in the logs rather than discover it in the numerics. Set
# SRML_TPU_COMPUTE_DTYPE=float32 for full-precision parity runs (bench.py
# runs exactly that parity check on the real chip every round).
_auto_announced: set = set()


def _announce_auto(key: str, value: Any) -> None:
    if key in _auto_announced:
        return
    _auto_announced.add(key)
    if (key, value) in (("compute_dtype", "bfloat16"), ("use_pallas", True)):
        from spark_rapids_ml_tpu.utils.logging import get_logger

        get_logger("config").info(
            "config %r auto-resolved to %r (TPU backend detected; the "
            "measured TPU profile). Set SRML_TPU_%s explicitly for the "
            "portable float32/XLA behavior.", key, value, key.upper(),
        )


_DEFAULTS: Dict[str, Any] = {
    # Master switch, analogous to spark.rapids.sql.enabled: when False all
    # estimators run their host (numpy) fallback path.
    "enabled": _env("ENABLED", True, _as_bool),
    # Accumulation dtype for Gram/centroid reductions. float64 gives parity
    # with the reference's double-precision cuBLAS path; float32 is the fast
    # TPU-native mode (MXU). (SURVEY.md §7 hard part (c).)
    "accum_dtype": _env("ACCUM_DTYPE", "float32", str),
    # Compute dtype for the big GEMMs; bfloat16 engages the MXU at full
    # rate. "auto" (default) = bfloat16 on a real TPU backend, float32
    # elsewhere — the measured TPU profile ships as the default. Set
    # "float32" explicitly for full-precision parity runs on TPU.
    "compute_dtype": _env("COMPUTE_DTYPE", "auto", str),
    # Default mesh axis sizes; None = use all local devices on the data axis.
    "mesh_data_axis": _env("MESH_DATA_AXIS", None, int),
    "mesh_model_axis": _env("MESH_MODEL_AXIS", 1, int),
    # On-mesh collective reduce for multi-daemon fits (docs/mesh.md): when
    # every daemon a pass fed is a co-resident mesh member (one JAX
    # runtime), per-shard partials fold on the device plane via the
    # `reduce_mesh` op instead of the driver export/merge hub. False
    # forces the hub path everywhere (the degraded mode the parity tests
    # pin against the collective path bitwise).
    "mesh_collectives": _env("MESH_COLLECTIVES", True, _as_bool),
    # Max rows per device batch when streaming host data to device.
    "stream_batch_rows": _env("STREAM_BATCH_ROWS", 1 << 20, int),
    # Use the native C++ columnar bridge if the shared library is present.
    "use_native_bridge": _env("USE_NATIVE_BRIDGE", True, _as_bool),
    # Emit profiler trace annotations (NVTX-range equivalent; SURVEY.md §5).
    "tracing": _env("TRACING", False, _as_bool),
    # Metrics registry master switch (utils/metrics.py): False turns every
    # counter/gauge/histogram record into an early return. Exposition and
    # snapshots only ever run on demand (the daemon `metrics` op).
    "metrics": _env("METRICS", True, _as_bool),
    # Run-journal output path (utils/journal.py): JSON-lines of run/phase
    # events fed by trace_span. None = off (zero overhead: no event dicts,
    # no I/O). Env key is SRML_RUN_JOURNAL — deployment-facing like
    # SRML_DAEMON_ADDRESS / SRML_FAULT_PLAN, hence no SRML_TPU_ prefix.
    "run_journal": os.environ.get("SRML_RUN_JOURNAL") or None,
    # Journal file rotation (utils/journal.py): when > 0, the journal
    # rotates logrotate-style (path → path.1 → …) before a line would
    # cross the byte cap; run_journal_keep rotated segments are
    # retained. 0 = unbounded append (REQUIRED when several processes
    # share one journal path — rotation is single-writer).
    "run_journal_max_bytes": _env_named("SRML_RUN_JOURNAL_MAX_BYTES", 0, int),
    "run_journal_keep": _env_named("SRML_RUN_JOURNAL_KEEP", 4, int),
    # Jit-ledger device timing mode (utils/xprof.py): every ledgered jit
    # call is bracketed with block_until_ready so per-call execution
    # wall-clock (and thus achieved flops/s and bytes/s) is measurable.
    # OFF by default — it serializes async dispatch, a measurement mode,
    # not a production state. Env key is SRML_DEVICE_TIMING:
    # deployment-facing (an operator flips it on a live daemon host to
    # diagnose), hence no SRML_TPU_ prefix.
    "device_timing": _env_named("SRML_DEVICE_TIMING", False, _as_bool),
    # Use Pallas kernels for hot ops (Gram, pairwise distance) on TPU.
    # "auto" (default) = on iff the backend is a real TPU (the per-kernel
    # shape/dtype gates still apply — see _pallas_backend_ok and friends).
    "use_pallas": _env("USE_PALLAS", "auto", _as_bool_or_auto),
    # Feature-sharded Gram algorithm: "allgather" (one ICI all_gather of the
    # full feature width per device) or "ring" (ppermute pipeline — one
    # block in flight, for feature dims too large to gather). "auto" =
    # allgather (ring wins when m_local*d doesn't fit alongside the data).
    "gram_algorithm": _env("GRAM_ALGORITHM", "auto", str),
    # Where the d×d eigendecomposition finalize runs: "auto" = on-device for
    # CPU meshes, host LAPACK (float64) for TPU ("device"/"host" force it).
    # The Gram reduction — the part that scales with data — always runs on
    # device; eigh on TPU is an iterative algorithm XLA executes poorly for
    # large d, while the d×d Gram is tiny to fetch.
    "finalize": _env("FINALIZE", "auto", str),
    # Eigensolver for the finalize: "full" = exact d×d eigh (host LAPACK on
    # TPU per `finalize`), "randomized" = on-device blocked subspace
    # iteration (Halko-style; MXU matmuls only, nothing but (d, k+p) panels
    # factorized — the TPU-fast path for large d with decaying spectra).
    "solver": _env("SOLVER", "full", str),
    # IVF bucketed-query shortlist multiplier: per-(list, slot) shortlist
    # width = mult·k, exact-rerank pool = 2·mult·k. The recall/speed dial
    # at bfloat16 compute (clustered 128-d measurement, recall@10 vs the
    # f32 scan's 0.99 ceiling): 2 → 0.92 at ~115k q/s/chip; 4 → 0.98 at
    # ~65k. f32 compute reaches the ceiling already at 2.
    "ann_shortlist_mult": _env("ANN_SHORTLIST_MULT", 2, int),
    # IVF bucketed-query exact rerank: re-score the 2·mult·k shortlist from
    # the raw f32 rows. Skipping it ("off") answers straight from the
    # residual-identity scores — measured 1.3–1.8× q/s for 0.005–0.017
    # recall@10 (1.8× / −0.017 at the clustered 768-d bench shape; the
    # (q, R, d) raw-row gather is the single most expensive post-scan op).
    # Keep "on" when bf16 score noise matters more than throughput.
    "ann_rerank": _env("ANN_RERANK", True, lambda v: str(v).lower() not in ("0", "false", "off")),
    # Exact-rerank shortlist width, in units of k: the rerank rescores the
    # R = ann_rerank_width*k best approximate candidates from the raw f32
    # rows ((q, R, d) gather — the dominant rerank cost). 0 = auto:
    # 2*ann_shortlist_mult on the XLA scan (sized for its approx-selection
    # noise), ann_shortlist_mult on the fused kernel (exact selection —
    # the same-run width sweep measured identical recall at half the
    # width; benchmarks/README.md).
    "ann_rerank_width": _env("ANN_RERANK_WIDTH", 0, int),
    # Fused-kernel per-(list, slot) extraction width under rerank:
    # "auto" (default) = ceil(1.2·k) — the round-5 measured frontier
    # point (177k q/s @ recall@10 0.9700 vs "wide"'s 153k @ 0.9706 at
    # the bench shape: the rerank's R = 2k selection caps what wider
    # extraction can feed it). "wide" = shortlist_mult·k, "narrow" = k
    # (183k @ 0.9577), an integer = width in rows. Rerank-off configs
    # always extract k; benchmarks/README.md round-5 frontier.
    "ann_extract": _env("ANN_EXTRACT", "auto", str),
    # Data-plane daemon backpressure watermarks (serve/daemon.py; 0 =
    # unlimited). Past either, the daemon answers heavy ops with `busy` +
    # a retry_after_s hint (graceful degradation) instead of accepting
    # work it will thrash on; pressure-relieving ops always pass.
    "daemon_max_connections": _env("DAEMON_MAX_CONNECTIONS", 0, int),
    "daemon_max_staged_bytes": _env("DAEMON_MAX_STAGED_BYTES", 0, int),
    # Pass cache (serve/daemon.py `_Job`; docs/protocol.md "rescan"): bytes
    # per device, in MiB, that ONE iterative job may keep of the batches
    # its fold placed on the device, so that the passes after the first
    # are scanned from HBM (`rescan`) and no row crosses the wire twice.
    # 0 (default) = off: no op, ack, metric or allocation differs. All or
    # nothing: the first batch that would pass the budget drops the job's
    # whole cache for the fit, which then re-feeds every pass as before.
    # The daemon reads it for the budget; the Spark estimator reads it
    # ($SRML_DAEMON_PASS_CACHE_MB / spark.srml.daemon.pass_cache_mb /
    # this key, spark/daemon_session.py) to ask for cached passes.
    "daemon_pass_cache_mb": _env_named("SRML_DAEMON_PASS_CACHE_MB", 0, int),
    # The retry hint (seconds) a shed client is told to wait; clients
    # jitter around it so a shed fleet doesn't return as one wave.
    "daemon_retry_after_s": _env("DAEMON_RETRY_AFTER_S", 1.0, float),
    # Durable daemon job state (serve/daemon.py): a directory where the
    # daemon write-ahead-snapshots iterative jobs at pass boundaries
    # (iterate + pass counter + creation params; atomic tmp+rename via
    # core/checkpoint.py) and persists its instance identity, so a
    # crashed-and-restarted daemon resurrects its jobs instead of
    # failing every in-flight fit. None = off — the zero-overhead
    # default (no snapshot writes, no restore lookups). Env key is
    # SRML_DAEMON_STATE_DIR: deployment-facing like SRML_RUN_JOURNAL /
    # SRML_DAEMON_ADDRESS, hence no SRML_TPU_ prefix.
    "daemon_state_dir": os.environ.get("SRML_DAEMON_STATE_DIR") or None,
    # Serving scheduler (serve/scheduler.py; docs/protocol.md "Serving
    # scheduler"): cross-connection micro-batching for transform/
    # kneighbors. ON by default since the fleet PR — batched results are
    # bitwise-identical to solo serving (the PR 5 matrix + the protocol
    # goldens replayed as the burn-in), so the only observable change is
    # higher QPS under concurrency. SRML_SERVE_BATCHING=0 is the
    # documented opt-out for single-caller deployments that prefer zero
    # batching-window latency. Env keys are deployment-facing
    # (SRML_SERVE_*), like SRML_DAEMON_STATE_DIR.
    "serve_batching": _env_named("SRML_SERVE_BATCHING", True, _as_bool),
    # Max milliseconds a queued request waits for co-batchable traffic
    # before its micro-batch dispatches anyway.
    "serve_batch_window_ms": _env_named(
        "SRML_SERVE_BATCH_WINDOW_MS", 2.0, float
    ),
    # Row cap per dispatched micro-batch, floored to a boundary of the
    # bucket ladder below (a batch coalesced past one would pad UP to
    # the next bucket, dispatching more device rows than the cap).
    "serve_max_batch_rows": _env_named("SRML_SERVE_MAX_BATCH_ROWS", 4096, int),
    # The bucket ladder (comma-separated ascending row counts): batches
    # are padded UP to the smallest bucket that fits, so jit
    # compilations per served model are bounded by the ladder length —
    # the padded rows are masked out of every result (bitwise-equal to
    # solo requests). Single requests larger than the top bucket bypass
    # the scheduler and dispatch solo.
    "serve_batch_buckets": _env_named(
        "SRML_SERVE_BATCH_BUCKETS", "64,256,1024,4096", str
    ),
    # Run the scheduler's bucket-ladder warmup pre-compile AT model
    # registration (ensure_model) instead of waiting for an explicit
    # client `warmup` call: first-request compile leaves the latency
    # path entirely. Only meaningful with serve_batching on; a warmup
    # failure degrades to lazy compiles, never fails the registration.
    "serve_warmup_on_register": _env_named(
        "SRML_SERVE_WARMUP_ON_REGISTER", False, _as_bool
    ),
    # True AOT at registration (docs/protocol.md "AOT at registration"):
    # when a warmup runs (warmup-on-register or the `warmup` op), models
    # that publish a `_serve_aot_plan` have their serving programs
    # `lower().compile()`d and the executables HELD on the served
    # instance — nothing executes, no zero-batch dispatches, and a
    # serving call at a primed shape runs the held executable directly
    # (zero compiles, zero jit-cache traces on the latency path). Models
    # without a plan (and shapes outside the ladder) degrade to the
    # trace-warmup/lazy-compile behavior. The warmup ack's `aot` field
    # reports which mode ran.
    "serve_aot": _env_named("SRML_SERVE_AOT", True, _as_bool),
    # Admission bound: max queued requests per served model; overflow
    # (and requests whose deadline the backlog would miss) are shed with
    # the busy/retry_after_s contract instead of queueing to death.
    "serve_queue_depth": _env_named("SRML_SERVE_QUEUE_DEPTH", 256, int),
    # Fleet serving (serve/fleet.py + serve/router.py; docs/protocol.md
    # "Fleet & versioned serving"). Env keys are deployment-facing
    # (SRML_FLEET_* / SRML_SERVE_*), like SRML_DAEMON_STATE_DIR.
    # How stale a replica's polled `health` snapshot may be before the
    # router re-polls it (also the dead-replica re-probe interval).
    "fleet_health_poll_s": _env_named("SRML_FLEET_HEALTH_POLL_S", 1.0, float),
    # Max replicas one request may try before it is declared unroutable
    # (busy/dead replicas are skipped toward the next candidate).
    # 0 = one attempt per fleet member.
    "fleet_failover_attempts": _env_named(
        "SRML_FLEET_FAILOVER_ATTEMPTS", 0, int
    ),
    # Virtual nodes per replica on the consistent-hash ring: more
    # vnodes = smoother key spread, slightly larger ring.
    "fleet_vnodes": _env_named("SRML_FLEET_VNODES", 64, int),
    # How long a rollout waits for the retired version's in-flight
    # requests to finish before dropping its registrations; a timeout
    # leaves them registered (memory) rather than yanking arrays out
    # from under a live request (correctness).
    "fleet_drain_timeout_s": _env_named(
        "SRML_FLEET_DRAIN_TIMEOUT_S", 30.0, float
    ),
    # Fleet gossip plane (serve/gossip.py; docs/protocol.md "Fleet
    # gossip & bootstrap"): daemons exchange FleetViews — replica
    # records + per-model version tables — so fleet state survives any
    # client's death. Env keys are deployment-facing (SRML_GOSSIP_* /
    # SRML_FLEET_*), like SRML_DAEMON_STATE_DIR.
    # Seconds between gossip ticks (each tick pushes this daemon's view
    # to gossip_fanout peers and merges theirs back). 0 (default) = no
    # gossip thread — the view still exists and answers gossip_pull /
    # merges gossip_push, so control planes that push synchronously
    # (ModelFleet) work without any background traffic.
    "gossip_interval_s": _env_named("SRML_GOSSIP_INTERVAL_S", 0.0, float),
    # Peers contacted per tick. Convergence is bounded by
    # gossip_interval_s × ring-diameter; fanout ≥ 2 keeps the diameter
    # O(log N).
    "gossip_fanout": _env_named("SRML_GOSSIP_FANOUT", 2, int),
    # How long retired-replica/version tombstones keep gossiping before
    # they are pruned; must exceed any plausible partition length or a
    # healed island could resurrect a retired record. 0 = keep forever.
    "gossip_tombstone_ttl_s": _env_named(
        "SRML_GOSSIP_TOMBSTONE_TTL_S", 600.0, float
    ),
    # Comma-separated seed daemon addresses ("host:port,...") a client
    # bootstraps its routing table from — ONE reachable seed suffices;
    # the pulled FleetView names the rest of the fleet. None = no
    # seeds configured (FleetClient.from_seeds requires an explicit
    # argument then). Also settable per Spark session via
    # spark.srml.fleet.seed_addresses (spark/daemon_session.py).
    "fleet_seed_addresses": _env_named(
        "SRML_FLEET_SEED_ADDRESSES", None, str
    ),
    # Versioned-serving fence (serve/daemon.py): a serving request
    # whose additive `version` field disagrees with the registration's
    # pinned version is refused (True, default) or answered with a
    # warning (False — debugging only; the answer is the WRONG model's).
    "serve_version_strict": _env_named(
        "SRML_SERVE_VERSION_STRICT", True, _as_bool
    ),
    # Serve autoscaler (serve/autoscaler.py; docs/protocol.md "Serve
    # autoscaler"): a control loop over telemetry the fleet already
    # emits (scheduler queue depth + sheds, replica busy state, routed
    # p99) that scales the replica set through the register→warm→flip→
    # drain rollout — scale-down never drops an in-flight request. Env
    # keys are deployment-facing (SRML_AUTOSCALE_*), like SRML_FLEET_*.
    # Scale UP when queued requests per live replica crosses this.
    "autoscale_high_watermark": _env_named(
        "SRML_AUTOSCALE_HIGH_WATERMARK", 8.0, float
    ),
    # Scale DOWN when queued requests per live replica falls below this
    # (the gap to the high watermark is the hysteresis band — a load
    # that sits between the two never trips an action).
    "autoscale_low_watermark": _env_named(
        "SRML_AUTOSCALE_LOW_WATERMARK", 1.0, float
    ),
    # Minimum seconds between ACTIONS: a load flapping at a watermark
    # trips at most one scale per cooldown window.
    "autoscale_cooldown_s": _env_named("SRML_AUTOSCALE_COOLDOWN_S", 30.0, float),
    # Control-loop poll interval.
    "autoscale_tick_s": _env_named("SRML_AUTOSCALE_TICK_S", 2.0, float),
    # Replica-count floor/ceiling the loop may never cross.
    "autoscale_min_replicas": _env_named("SRML_AUTOSCALE_MIN_REPLICAS", 1, int),
    "autoscale_max_replicas": _env_named("SRML_AUTOSCALE_MAX_REPLICAS", 8, int),
    # Optional latency objective: routed p99 (estimated from the
    # srml_router_request_seconds histogram) above this forces a
    # high-watermark verdict even at a quiet queue. 0 = off.
    "autoscale_p99_deadline_s": _env_named(
        "SRML_AUTOSCALE_P99_DEADLINE_S", 0.0, float
    ),
    # --- Telemetry plane (docs/observability.md, docs/protocol.md
    # "Telemetry plane ops"). Env keys are deployment-facing (SRML_*),
    # like SRML_SERVE_*. ---
    # In-memory journal-event ring the daemon arms at start
    # (utils/journal.py ring_arm): the event source for the `trace_pull`
    # wire op and the flight recorder, independent of any journal FILE.
    # 0 disables both (trace_pull answers empty, incident bundles carry
    # no spans).
    "telemetry_trace_buffer": _env_named(
        "SRML_TELEMETRY_TRACE_BUFFER", 4096, int
    ),
    # Histogram exemplar freshness window (utils/metrics.py): per bucket,
    # the worst exemplared sample of the last window is kept; an older
    # exemplar yields the slot to the next sample regardless of value.
    "telemetry_exemplar_window_s": _env_named(
        "SRML_TELEMETRY_EXEMPLAR_WINDOW_S", 60.0, float
    ),
    # Daemon telemetry-evaluation cadence: the background thread that
    # snapshots metrics, evaluates SLO burn rates (utils/slo.py), and
    # checks flight-recorder trigger conditions. 0 disables the thread
    # (SLO gauges and automatic incident capture off; telemetry_pull /
    # trace_pull still answer).
    "telemetry_eval_interval_s": _env_named(
        "SRML_TELEMETRY_EVAL_INTERVAL_S", 1.0, float
    ),
    # Declared per-op SLOs (utils/slo.py), semicolon-separated:
    # "<op>:<kind>[=<target>]@<budget>" with kind ∈ p99_ms|error|shed,
    # e.g. "transform:p99_ms=50@0.01;transform:error@0.001". Empty = no
    # objectives, nothing evaluated.
    "slo_objectives": _env_named("SRML_SLO_OBJECTIVES", "", str),
    # Multi-window burn-rate windows (SRE convention: BOTH windows must
    # burn above slo_burn_threshold to breach — the fast window catches
    # it quickly, the slow window debounces blips).
    "slo_fast_window_s": _env_named("SRML_SLO_FAST_WINDOW_S", 60.0, float),
    "slo_slow_window_s": _env_named("SRML_SLO_SLOW_WINDOW_S", 300.0, float),
    # Burn-rate breach threshold: burning budget at ≥ this multiple of
    # the sustainable rate in both windows raises srml_slo_breach (and
    # the flight-recorder trigger).
    "slo_burn_threshold": _env_named("SRML_SLO_BURN_THRESHOLD", 14.4, float),
    # Flight recorder (utils/flight.py): incident bundles land in
    # state_dir/incidents/, newest-first, capped at this many (oldest
    # deleted). 0 disables dumping entirely.
    "incident_max_bundles": _env_named("SRML_INCIDENT_MAX_BUNDLES", 16, int),
    # Debounce: minimum seconds between bundles for the SAME trigger
    # reason — a sustained storm yields one bundle per window, not one
    # per tick.
    "incident_min_interval_s": _env_named(
        "SRML_INCIDENT_MIN_INTERVAL_S", 30.0, float
    ),
    # Automatic trigger thresholds, evaluated per telemetry tick as
    # RATES (events/second over the tick window). 0 = trigger off.
    "incident_shed_rate": _env_named("SRML_INCIDENT_SHED_RATE", 0.0, float),
    "incident_deadline_rate": _env_named(
        "SRML_INCIDENT_DEADLINE_RATE", 0.0, float
    ),
    # Dump a bundle on fatal teardown (SIGTERM / atexit while a recorder
    # is armed). Off by default: test daemons exit constantly and a
    # bundle per clean exit is noise; production supervisors flip it on.
    "incident_on_fatal": _env_named("SRML_INCIDENT_ON_FATAL", False, _as_bool),
    # Served-model registry cap (0 = unbounded): past it, the least-
    # recently-used re-creatable registration is evicted (clients
    # re-register on miss); daemon-built KNN indexes are evicted only
    # when nothing re-creatable remains. The LRU twin of the TTL reaper
    # — a long-lived daemon cannot grow its model registry without
    # bound even when no TTL is configured.
    "daemon_max_models": _env("DAEMON_MAX_MODELS", 0, int),
    # Bounded fit-level pass-replay budget for the Spark estimators
    # (spark/estimator.py): how many times one pass-boundary unit (scan
    # + step / finalize) may be replayed after a daemon incarnation
    # change before the failure surfaces. 0 = off: a restart mid-fit
    # fails loudly with the split-brain error instead of healing.
    # Overridable per session via $SRML_FIT_RECOVERY_ATTEMPTS /
    # spark.srml.fit.recovery_attempts (spark/daemon_session.py).
    "fit_recovery_attempts": _env("FIT_RECOVERY_ATTEMPTS", 0, int),
    # Elastic-fit death policy (spark/estimator.py; docs/protocol.md
    # "Permanent daemon loss"): how many PEER daemons one fit may declare
    # permanently dead and amputate — quarantining the daemon, rewinding
    # survivors to the last pass boundary, and rerunning the scan with
    # the dead daemon's partitions rerouted. 0 (default) = off: a lost
    # daemon fails the fit loudly, byte-for-byte today's behavior, and
    # no classification probe ever runs. Overridable per session via
    # $SRML_FIT_DAEMON_LOSS_TOLERANCE / spark.srml.fit.daemon_loss_tolerance
    # (spark/daemon_session.py).
    "fit_daemon_loss_tolerance": _env("FIT_DAEMON_LOSS_TOLERANCE", 0, int),
    # The death deadline: a peer implicated in a failed pass is probed
    # with this as its TOTAL reconnect/healing budget, and escalates from
    # *retrying* to *declared dead* only when the whole budget is
    # exhausted — a slow or busy daemon that answers within it is never
    # amputated on a hunch. Overridable via
    # $SRML_FIT_DAEMON_DEATH_TIMEOUT_S /
    # spark.srml.fit.daemon_death_timeout_s.
    "fit_daemon_death_timeout_s": _env(
        "FIT_DAEMON_DEATH_TIMEOUT_S", 15.0, float
    ),
    # Elastic-fit GROW policy (spark/estimator.py; docs/protocol.md
    # "Mid-fit daemon join") — the inverse direction of the death policy
    # above: whether a daemon that appears MID-FIT (Spark dynamic
    # allocation granting an executor, a spot host coming up) may be
    # admitted into a running fit. "off" (default) keeps today's
    # contract byte-for-byte: an unlisted peer fails its tasks loudly
    # (centers/iterate unseeded) and no discovery probe ever runs.
    # "boundary" admits new daemons at the NEXT pass boundary only —
    # never mid-pass — by seeding them with the ledger's boundary
    # iterate, so grown fits stay bitwise-equal to a static-topology
    # fit. Env keys are deployment-facing (SRML_FIT_*), like
    # SRML_SERVE_*; also via spark.srml.fit.daemon_join_policy.
    "fit_daemon_join_policy": _env_named(
        "SRML_FIT_DAEMON_JOIN_POLICY", "off", str
    ),
    # Join budget: how many daemons one fit may admit mid-fit. A newly
    # configured daemon past the budget fails the fit loudly (the loss-
    # tolerance contract, mirrored) instead of silently staying outside
    # the topology while executors route rows at it. Also via
    # $SRML_FIT_DAEMON_JOIN_LIMIT / spark.srml.fit.daemon_join_limit.
    "fit_daemon_join_limit": _env_named(
        "SRML_FIT_DAEMON_JOIN_LIMIT", 2, int
    ),
    # Histogram tree ensembles (models/random_forest.py; docs/protocol.md
    # "The `rf` job algo"). Env keys are deployment-facing (SRML_FOREST_*),
    # like SRML_SERVE_*.
    # Row cap on the driver-side prefix sample that trains the quantile
    # bin-edge sketch (the kmeans init_sample_rows twin): the edges are
    # part of the model iterate, so every daemon bins identically.
    "forest_seed_sample_rows": _env_named(
        "SRML_FOREST_SEED_SAMPLE_ROWS", 65536, int
    ),
    # Per-device budget (MiB) for one frontier's (tree, node, feature,
    # bin, stat) histogram tensor — over it, the fit refuses at the pass
    # boundary that would allocate it (ForestCapacityError; the forest
    # twin of SRML_GRAM_DEVICE_BUDGET_MB), never a mid-pass OOM. 0 =
    # unbounded.
    "forest_hist_budget_mb": _env_named(
        "SRML_FOREST_HIST_BUDGET_MB", 256, int
    ),
    # Fused Pallas scan+selection kernel for the bucketed IVF query
    # (ops/pallas_kernels.py ivf_scan_select_pallas): the per-list residual
    # GEMM and an EXACT per-slot top-k run in one kernel, scores
    # VMEM-resident. "auto" = on when the backend is TPU and the per-list
    # tile fits VMEM (the XLA einsum+approx_min_k scan is the portable
    # fallback); "on" forces it (interpret mode off-TPU — used by tests);
    # "off" forces the XLA scan. Precision: the kernel's exact selection
    # packs ids into the low mantissa bits of the f32 score key, so with
    # ann_rerank=off the returned DISTANCES are floored to ~24-ceil(log2
    # maxlen) mantissa bits (ids exact; rerank=on recomputes true f32
    # distances). Force "off" for full-f32 rerank-off values.
    "ann_fused_scan": _env("ANN_FUSED_SCAN", "auto", str),
}

_lock = threading.Lock()
_conf: Dict[str, Any] = dict(_DEFAULTS)


def get(key: str) -> Any:
    """Get a runtime config value ("auto" keys resolve per backend)."""
    value = get_raw(key)
    if value == "auto" and key in _AUTO_RESOLVERS:
        value = _AUTO_RESOLVERS[key]()
        _announce_auto(key, value)
    return value


def get_raw(key: str) -> Any:
    """Get the stored value without "auto" resolution (option/save-restore)."""
    with _lock:
        if key not in _conf:
            raise KeyError(f"unknown config key: {key!r} (known: {sorted(_conf)})")
        return _conf[key]


def peek(key: str) -> Any:
    """LOCK-FREE read for per-record hot paths (metrics/journal gates):
    a single dict lookup, atomic under the GIL, no "auto" resolution and
    no unknown-key check. Callers must pass a key that exists and is
    never "auto" — anything else belongs on :func:`get`."""
    return _conf.get(key)


def set(key: str, value: Any) -> None:  # noqa: A003 - mirrors SparkConf.set
    """Set a runtime config value."""
    with _lock:
        if key not in _conf:
            raise KeyError(f"unknown config key: {key!r} (known: {sorted(_conf)})")
        _conf[key] = value


def reset() -> None:
    """Restore defaults (mainly for tests)."""
    with _lock:
        _conf.clear()
        _conf.update(_DEFAULTS)


def fingerprint() -> str:
    """Stable short hash of the CURRENT config (raw values, no "auto"
    resolution — the fingerprint must not touch a backend). Two
    processes answering ``telemetry_pull`` with different fingerprints
    are running different effective configs — the first thing to check
    when one replica of a fleet misbehaves. Incident bundles
    (utils/flight.py) carry it for the same reason."""
    import hashlib
    import json as _json

    with _lock:
        items = sorted(_conf.items())
    blob = _json.dumps(items, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


class option:
    """Context manager to temporarily override a config value."""

    def __init__(self, key: str, value: Any):
        self._key = key
        self._value = value
        self._saved: Optional[Any] = None

    def __enter__(self) -> "option":
        self._saved = get_raw(self._key)  # preserve "auto", don't bake it
        set(self._key, self._value)
        return self

    def __exit__(self, *exc: Any) -> None:
        set(self._key, self._saved)
