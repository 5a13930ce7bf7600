"""Headline benchmark: PCA.fit compute-path throughput, rows/sec/chip.

Measures the north-star fit workload (BASELINE.json: 100M×2048 f32, k=32 —
a dataset ≫ HBM, so the real algorithm is the STREAMING accumulate) on its
compute path:

  - per-batch fused count/colsum/Gram statistics with donated on-device
    accumulator state (the reference's dgemmCov hot loop,
    rapidsml_jni.cu:120-125, plus the device-side combiner its
    ``accumulateCov`` declared but never implemented — SURVEY.md §2.4),
    bfloat16 GEMM on the MXU with float32 accumulation. Batches are
    ingest-cast to the compute dtype at placement (the framework's
    quantize-on-ingest design: identical Gram numerics, half the transfer
    bytes) and the update runs the single-HBM-pass Pallas kernel that
    fuses the boundary row-mask and the column-sum into the GEMM
    (ops/pallas_kernels.gram_colsum_pallas);
  - one mean-centered finalize + on-device randomized top-k eigensolve +
    sign-flip (the reference's calSVD, rapidsml_jni.cu:215-269) — only the
    (d, k) result leaves the device.

The row batch is generated on device once and re-fed B times, so the number
isolates sustained device compute throughput; host→device feeding is
benchmarked separately in the bridge tests. rows/s = B·batch_rows / wall.

Baseline for ``vs_baseline``: the A100 cuML fit is GEMM-bound at 2·d²
flops/row; at ~110 TFLOP/s sustained TF32 that is ~13.1e6 rows/s. The
north-star target (BASELINE.md) is within 2× of A100 per chip, i.e.
vs_baseline >= 0.5.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

``python bench.py --serve`` (or SRML_BENCH_SERVE=1) runs the SERVING
benchmark instead: N concurrent transform clients against one in-process
daemon, scheduler off then on (serve/scheduler.py), then on again with
the TELEMETRY PLANE hot (SLO evaluation ticking, span ring armed, a live
``telemetry_pull``/``trace_pull`` scraper — docs/observability.md), and
prints one JSON line with QPS, p50/p99 latency, and mean batch occupancy
for all modes plus ``telemetry_overhead`` (the telemetry run's fractional
QPS cost, gated < 2% by tools/perfcheck.py).

``python bench.py --chaos-elastic`` (or SRML_BENCH_CHAOS_ELASTIC=1)
runs the ELASTIC-DEGRADE micro-benchmark: a 3-daemon hub-protocol
kmeans fit whose peer daemon dies permanently mid-pass (stop, NO
restart — docs/protocol.md "Permanent daemon loss"). The record carries
time-to-recover (death probe + survivor rewind + the replayed pass on
the 3→2 topology), the replayed-row count, the recovery overhead
relative to a steady pass, and a bitwise check against an uninterrupted
fit on the surviving topology; tools/perfcheck.py gates
recovery-cost regressions against the CHAOS_r* trajectory.

``python bench.py --chaos-grow`` (or SRML_BENCH_CHAOS_GROW=1) runs the
mirror-image ELASTIC-GROW micro-benchmark: a 2-daemon hub-protocol
kmeans fit that a third daemon JOINS at a pass boundary (one creating
set_iterate carrying the boundary iterate — docs/protocol.md "Mid-fit
daemon join"), runs grown for the middle passes, then shrinks back to
two at the next boundary. The record carries time-to-admit, the
rebalanced-row count, the grow overhead relative to a steady pass, and
a bitwise check against an uninterrupted static-topology fit;
tools/perfcheck.py check_chaos_grow gates it against the CHAOS_r*
trajectory (the two chaos families share the glob; mode+metric filters
separate them).

``python bench.py --chaos-partition`` (or SRML_BENCH_CHAOS_PARTITION=1)
runs the GOSSIP PARTITION-HEAL micro-benchmark: four daemons with live
gossip threads split into two islands that never hear of each other;
the losing island registers a model first, the winning island registers
AND rolls it forward (dominant epochs, the old version tombstoned), a
client bootstrapped from one losing-island seed routes traffic through
the whole split, and the heal is a single bridge gossip_push. The
record carries time-to-converge (bridge → all four FleetViews agree:
one active version, one epoch, the stale version tombstoned everywhere,
no resurrection) plus the routed/failed tallies from inside the split;
tools/perfcheck.py check_chaos_partition gates correctness absolutely
and convergence against the shared CHAOS_r* trajectory.

``python bench.py --forest`` (or SRML_BENCH_FOREST=1) runs the
TREE-ENSEMBLE benchmark: a RandomForest classifier fit (quantile
binning + fused per-depth histogram accumulate + vectorized split
scoring — the first non-GEMM workload record) plus warm-jit transform
QPS, differential against a sklearn-CPU RandomForest baseline when
installed (fit/transform speedups + an absolute accuracy gate);
tools/perfcheck.py check_forest gates it against the FOREST_r*
trajectory (SKIP-not-pass without history).

``python bench.py --serve --fleet`` (or SRML_BENCH_FLEET=1) runs the
FLEET benchmark: N replica daemons (each its own OS process — its own
Python runtime and device dispatch, the deployment shape) × M client
processes routing through serve/router.py, measured at 1 replica and at
N replicas on the same workload. The record carries per-replica-count
QPS/p50/p99 and the scaling efficiency QPS_N / (N × QPS_1) that
tools/perfcheck.py gates at ≥ 0.7 (FLEET_r* trajectory). In-process
smoke mode (SRML_BENCH_FLEET_INPROC=1) marks the record ``dryrun`` —
in-process replicas share one device lock, so its "scaling" proves
plumbing, never performance (perfcheck reads it as SKIP, not pass).
Subprocess records also embed a raw wire-fabric microphase (loopback
echo at the protocol's frame pattern, 1 vs N process pairs); when the
host's transport cannot even carry N × QPS_1 the record is marked
``wire_limited`` and perfcheck gates the FABRIC-RELATIVE efficiency
instead (see fleet_bench).
"""

import json
import os
import sys
import time

import numpy as np

A100_CUML_ROWS_PER_SEC = 13.1e6  # GEMM-bound estimate, see module docstring

# Env knobs exist for smoke-testing the bench itself on small hosts; the
# recorded benchmark always runs the defaults (the north-star shape).
D = int(os.environ.get("SRML_BENCH_D", 2048))
K = int(os.environ.get("SRML_BENCH_K", 32))
BATCH_ROWS = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 1 << 18))  # 1.1 GB bf16
# 384 × 262144 = 100.7M rows — the north-star fit size (BASELINE.json
# config #2).
N_BATCHES = int(os.environ.get("SRML_BENCH_BATCHES", 384))


def _f32_parity_check() -> None:
    """Full-precision parity on THIS backend (round-4 advisor): the shipped
    TPU defaults auto-resolve to bfloat16/Pallas, so the float32 parity the
    CPU suite validates must also be exercised where the default flips.
    Runs the PCA fit path with compute_dtype=float32 on a small shape and
    asserts against the numpy float64 oracle (PCASuite.scala:80-87's
    sign-invariant tolerance philosophy). Raises on mismatch — a failed
    parity check fails the recorded bench run."""
    import jax
    import numpy as np

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.pca import fit_pca

    n, d, k = 8192, 256, 8
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((n, d)) * rng.gamma(2.0, 1.0, d)).astype(np.float32)
    with config.option("compute_dtype", "float32"):
        sol = fit_pca(x, k=k, mean_center=True)
    pc = np.asarray(jax.device_get(sol.pc))
    xc = x.astype(np.float64) - x.mean(axis=0, dtype=np.float64)
    cov = xc.T @ xc / (n - 1)
    w, v = np.linalg.eigh(cov)
    ref = v[:, ::-1][:, :k]
    # Sign-invariant subspace agreement, column by column.
    dots = np.abs(np.sum(pc.astype(np.float64) * ref, axis=0))
    if not np.all(dots > 1 - 1e-3):  # not assert: python -O must not skip it
        raise RuntimeError(f"f32 parity failed on {jax.default_backend()}: "
                           f"|cos| = {dots}")


def main() -> None:
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops.eigh import pca_from_gram_randomized
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    _f32_parity_check()

    # Since round 4 these ARE the shipped TPU-auto defaults; pinned here so
    # the recorded number stays tied to this exact profile even if defaults
    # move.
    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")
    config.set("use_pallas", True)

    n_chips = len(jax.devices())
    mesh = make_mesh(model=1)

    # On-device data generation (no host transfer in the timed region),
    # ingest-cast to the compute dtype as the bridge does at placement.
    x = jax.random.normal(jax.random.key(0), (BATCH_ROWS, D), dtype=jnp.float32)
    x = x.astype(jnp.bfloat16)
    mask = jnp.ones((BATCH_ROWS,), jnp.float32)
    if n_chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        mask = jax.device_put(mask, NamedSharding(mesh, P("data")))

    update = gram_ops.streaming_update(
        mesh, compute_dtype="bfloat16", accum_dtype="float32"
    )

    from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

    @ledgered_jit("bench.eig_finalize")
    def finalize(count, colsum, g):
        g, mean = gram_ops.finalize_gram(count, colsum, g, mean_center=True)
        return pca_from_gram_randomized(g, K)

    from spark_rapids_ml_tpu.utils import metrics
    from spark_rapids_ml_tpu.utils.profiling import trace_span

    fed_bytes = metrics.counter(
        "srml_bench_fed_bytes_total",
        "Row bytes folded through the bench's streaming update",
    )

    def fit(n_batches):
        # The same phase names fit_pca uses (the reference's NVTX names,
        # RapidsRowMatrix.scala:62,70): the spans land in
        # srml_phase_duration_seconds, so the BENCH record below carries
        # the per-phase breakdown, not just the headline total.
        state = gram_ops.init_stats(D, accum_dtype="float32")
        with trace_span("compute cov"):
            for _ in range(n_batches):
                state = update(state, x, mask)
            # Sync before the span closes: jitted updates dispatch async,
            # and without the block the fold's device time would land in
            # the NEXT span — the finalize blamed for fold regressions.
            jax.block_until_ready(state)
            fed_bytes.inc(n_batches * BATCH_ROWS * D * 2)  # bf16 rows
        with trace_span("eig finalize"):
            pc, ev, _ = finalize(*state)
            return jax.device_get((pc, ev))  # (d, k) + (k,) — tiny

    from spark_rapids_ml_tpu.utils import xprof

    fit(2)  # warmup / compile
    # The warmup's ledger snapshot is the COMPILE story (every jit in the
    # fit compiles exactly here); the post-reset snapshot is the steady
    # state, where any compile at all is a storm tools/perfcheck.py flags.
    xla_warmup = _ledger_breakdown(xprof.snapshot())
    metrics.reset()  # the recorded snapshot covers ONLY the timed fit
    xprof.reset()

    t0 = time.perf_counter()
    pc, ev = fit(N_BATCHES)
    dt = time.perf_counter() - t0
    assert pc.shape == (D, K) and np.all(np.isfinite(pc))

    rows_per_sec_per_chip = N_BATCHES * BATCH_ROWS / dt / n_chips
    line = {
        "metric": f"pca_fit_streaming_rows_per_sec_per_chip_d{D}_k{K}",
        "value": round(rows_per_sec_per_chip, 1),
        "unit": "rows/s/chip",
        "vs_baseline": round(rows_per_sec_per_chip / A100_CUML_ROWS_PER_SEC, 4),
        "metrics": _metrics_breakdown(metrics.snapshot()),
        "xla": {
            "warmup": xla_warmup,
            "steady": _ledger_breakdown(xprof.snapshot()),
            "device_timing": bool(config.get("device_timing")),
        },
    }
    if os.environ.get("SRML_BENCH_INGEST", "") in ("1", "true"):
        line.update(_ingest_inclusive(update))
    print(json.dumps(line))


def _metrics_breakdown(snap: dict) -> dict:
    """Registry snapshot → the compact breakdown each BENCH record
    embeds: per-phase span durations + bytes moved. Perf trajectory
    records then say WHERE a regression landed (fold vs finalize), not
    just that the headline moved."""
    phases = {}
    for s in snap.get("srml_phase_duration_seconds", {}).get("samples", []):
        phases[s["labels"].get("phase", "?")] = {
            "count": s["count"],
            "sum_s": round(float(s["sum"]), 4),
        }
    fed = snap.get("srml_bench_fed_bytes_total", {}).get("samples", [])
    return {
        "phases": phases,
        "fed_bytes": int(fed[0]["value"]) if fed else 0,
    }


def _ledger_breakdown(snap: dict) -> dict:
    """Jit-ledger snapshot (utils/xprof.py) → the per-fn device-cost
    attribution each BENCH record embeds: compile s vs execute s, model
    flops/bytes (XLA cost analysis), achieved flops/s and bytes/s in
    SRML_DEVICE_TIMING runs. This is the breakdown tools/perfcheck.py
    gates on — a regression record says WHICH jit slowed or started
    compile-storming, not just that the headline moved."""
    out = {}
    for fn, a in snap.items():
        out[fn] = {
            "calls": a["calls"],
            "compiles": a["compiles"],
            "compile_s": round(a["compile_s"], 4),
            "cache_misses": a["cache_misses"],
            "execute_s": round(a["execute_s"], 4),
            "flops": sum(
                r["flops"] * r["calls"]
                for r in a["signatures"] if r["flops"] is not None
            ),
            "bytes": sum(
                r["bytes_accessed"] * r["calls"]
                for r in a["signatures"] if r["bytes_accessed"] is not None
            ),
            "flops_per_s": a["flops_per_s"],
            "bytes_per_s": a["bytes_per_s"],
        }
    return out


def _ingest_inclusive(update):
    """Optional ingest-inclusive measurement (SRML_BENCH_INGEST=1): real
    host Arrow batches through bridge/arrow + device_put, double-buffered
    against the device fold — the end-to-end feed the compute-only
    headline deliberately excludes (r2 review weak #5).
    """
    import time

    import jax
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import (
        matrix_to_list_column,
        table_column_to_matrix,
    )
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    rows = int(os.environ.get("SRML_BENCH_INGEST_ROWS", 1 << 16))
    n_b = int(os.environ.get("SRML_BENCH_INGEST_BATCHES", 8))
    rng = np.random.default_rng(0)
    host = rng.standard_normal((rows, D), dtype=np.float32)
    tables = [
        pa.table({"features": matrix_to_list_column(host)}) for _ in range(2)
    ]

    import ml_dtypes

    def put(i):
        mat = table_column_to_matrix(tables[i % 2], "features")
        # Quantize-on-ingest: cast to bfloat16 ON THE HOST so the wire
        # carries 2 bytes/element (the design the headline documents);
        # a device-side cast would transfer f32 and double the bytes.
        return jax.device_put(mat.astype(ml_dtypes.bfloat16))

    state = gram_ops.init_stats(D, accum_dtype="float32")
    # Timer starts BEFORE the first put: all n_b conversions/transfers are
    # inside the window (an outside-t0 warm put would credit n_b batches
    # while timing n_b − 1).
    t0 = time.perf_counter()
    nxt = put(0)
    for i in range(n_b):
        cur = nxt
        if i + 1 < n_b:
            nxt = put(i + 1)  # overlap next transfer with this fold
        state = update(state, cur, rows)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return {
        "ingest_rows_per_sec": round(n_b * rows / dt, 1),
        "ingest_batch_rows": rows,
    }


def multichip_bench() -> None:
    """Pod-scale FIT benchmark (``--multichip``; replaces the MULTICHIP_r*
    dryruns with a measured record): a real PCA streaming fit and a real
    k-means Lloyd fit on a 1-device mesh and an N-device data mesh, same
    total work, with per-phase timing (fold / step / finalize) plus a raw
    (d, d) all-reduce microphase — the collective the on-mesh reduction
    rides (docs/mesh.md). Prints ONE JSON line.

    Scaling efficiency: on real multi-chip hardware the N-device ideal is
    N× the 1-device throughput; on a SIMULATED mesh (CPU host platform
    split into N virtual devices — same silicon) the ideal is the
    1-device throughput itself, so the number reads as "fraction of
    single-device throughput kept after sharding + collectives". The
    record carries ``simulated`` so tools/perfcheck.py gates like against
    like; the ≥0.8 floor is the acceptance bar either way."""
    n_want = int(os.environ.get("SRML_BENCH_MULTICHIP_DEVICES", 8))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # Before the jax import: a CPU host splits into n_want virtual
        # devices (ignored by real TPU backends — their device count is
        # physical).
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_want}"
        ).strip()

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops.eigh import pca_from_gram_randomized
    from spark_rapids_ml_tpu.parallel import mapreduce as mpr
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from spark_rapids_ml_tpu.utils import metrics, xprof
    from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

    d = int(os.environ.get("SRML_BENCH_MULTICHIP_D", 512))
    k = int(os.environ.get("SRML_BENCH_MULTICHIP_K", 16))
    batch_rows = int(os.environ.get("SRML_BENCH_MULTICHIP_BATCH_ROWS", 1 << 16))
    n_batches = int(os.environ.get("SRML_BENCH_MULTICHIP_BATCHES", 24))
    km_k = int(os.environ.get("SRML_BENCH_MULTICHIP_KMEANS_K", 16))
    km_passes = int(os.environ.get("SRML_BENCH_MULTICHIP_KMEANS_PASSES", 3))

    devs = jax.devices()
    n_dev = min(len(devs), n_want)
    simulated = devs[0].platform == "cpu"

    from spark_rapids_ml_tpu.models.kmeans import (
        _stream_step_fn,
        apply_lloyd_update,
        stream_zero_state,
    )

    cd = str(jnp.dtype(config.get("compute_dtype")))
    ad = str(jnp.dtype(config.get("accum_dtype")))

    def run_fits(n: int) -> dict:
        """Both fits on an n-device data mesh; phase seconds + rows/s."""
        mesh = make_mesh(data=n, model=1, devices=devs[:n])
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.random.normal(
            jax.random.key(0), (batch_rows, d), dtype=jnp.float32
        ).astype(jnp.dtype(cd))
        x = jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS, None)))
        update = gram_ops.streaming_update(
            mesh, compute_dtype=cd, accum_dtype=ad
        )

        @ledgered_jit(f"bench.multichip_finalize_{n}dev")
        def finalize(count, colsum, g):
            gg, _ = gram_ops.finalize_gram(count, colsum, g, mean_center=True)
            return pca_from_gram_randomized(gg, k)

        km_update = _stream_step_fn(mesh, km_k, cd, ad)
        centers0 = jax.device_put(
            jax.random.normal(jax.random.key(1), (km_k, d), dtype=jnp.dtype(ad))
        )
        mask = jax.device_put(
            jnp.ones((batch_rows,), jnp.dtype(cd)),
            NamedSharding(mesh, P(DATA_AXIS)),
        )

        # The raw-collective microphase: one all-reduce of the (d, d)
        # accumulator over the data axis — the exact reduction shape the
        # fused fold rides, isolated so the record names collective cost
        # separately from GEMM cost.
        allred = ledgered_jit(
            f"bench.multichip_allreduce_{n}dev",
            mpr.map_fn(
                lambda g: mpr.reduce_sum(g, DATA_AXIS),
                mesh,
                in_specs=P(),
                out_specs=P(),
                check_vma=False,
            ),
        )

        def pca_fit(batches: int):
            state = gram_ops.init_stats(d, accum_dtype=ad)
            for _ in range(batches):
                state = update(state, x, mask)
            jax.block_until_ready(state)
            return state

        def km_fit(passes: int):
            centers = centers0
            for _ in range(passes):
                st = stream_zero_state(km_k, d, jnp.dtype(ad))
                for _ in range(max(n_batches // 2, 1)):
                    st = km_update(st, centers, x, mask)
                centers, moved2 = apply_lloyd_update(st[0], st[1], centers)
            jax.block_until_ready(centers)
            return centers

        # Warmup: compile everything outside the timed region — TWO steps
        # per loop (like main()'s fit(2)): the second iteration's input is
        # the first's mesh-committed output, a distinct jit signature.
        # Then reset the jit ledger so this mesh's steady breakdown shows
        # compiles only if a shape leaked into the timed loops (the storm
        # gate tools/perfcheck.py applies to every record).
        state = pca_fit(2)
        jax.block_until_ready(finalize(*state))
        km_fit(2)
        gseed = jnp.zeros((d, d), jnp.dtype(ad))
        jax.block_until_ready(allred(allred(gseed)))
        warmup_xla = _ledger_breakdown(xprof.snapshot())
        xprof.reset()

        phases: dict = {}

        def timed(name, fn, *a):
            t0 = time.perf_counter()
            out = fn(*a)
            jax.block_until_ready(out)
            phases[name] = round(time.perf_counter() - t0, 4)
            return out

        state = timed("pca_fold", pca_fit, n_batches)
        timed("pca_finalize", finalize, *state)
        timed("kmeans_fold", km_fit, km_passes)

        reps = 16
        t0 = time.perf_counter()
        g = gseed
        for _ in range(reps):
            g = allred(g)
        jax.block_until_ready(g)
        phases["allreduce_dxd"] = round((time.perf_counter() - t0) / reps, 6)

        pca_rows = n_batches * batch_rows
        km_rows = km_passes * max(n_batches // 2, 1) * batch_rows
        steady_xla = _ledger_breakdown(xprof.snapshot())
        # Clear the ledger on the way out: the NEXT mesh's warmup
        # snapshot must not absorb this mesh's timed-loop entries (the
        # fn names are shared between meshes).
        xprof.reset()
        return {
            "phases": phases,
            "pca_rows_per_sec": round(
                pca_rows / (phases["pca_fold"] + phases["pca_finalize"]), 1
            ),
            "kmeans_rows_per_sec": round(km_rows / phases["kmeans_fold"], 1),
            "xla_warmup": warmup_xla,
            "xla_steady": steady_xla,
        }

    xprof.reset()  # per-mesh warmup/steady splits live in run_fits
    one = run_fits(1)
    many = run_fits(n_dev)
    # One record-level steady view for the storm gate: the two meshes
    # register distinct bench.* entries but SHARE the model-update ledger
    # names, so each mesh's steady is keyed under its device count.
    steady = {
        **{f"1dev:{fn}": a for fn, a in one.pop("xla_steady").items()},
        **{f"{n_dev}dev:{fn}": a for fn, a in many.pop("xla_steady").items()},
    }
    warmup = {
        **{f"1dev:{fn}": a for fn, a in one.pop("xla_warmup").items()},
        **{f"{n_dev}dev:{fn}": a for fn, a in many.pop("xla_warmup").items()},
    }

    def eff(key: str) -> float:
        ideal = one[key] * (1.0 if simulated else n_dev)
        return round(many[key] / ideal, 4) if ideal else 0.0

    pca_eff, km_eff = eff("pca_rows_per_sec"), eff("kmeans_rows_per_sec")
    line = {
        "metric": f"multichip_fit_rows_per_sec_d{d}_k{k}",
        "value": many["pca_rows_per_sec"],
        "unit": "rows/s",
        "n_devices": n_dev,
        "simulated": simulated,
        "dryrun": False,
        "scaling_efficiency": min(pca_eff, km_eff),
        "pca_efficiency": pca_eff,
        "kmeans_efficiency": km_eff,
        "one_device": one,
        "n_device": many,
        "xla": {
            "warmup": warmup,
            "steady": steady,
            "device_timing": bool(config.get("device_timing")),
        },
        "metrics": _metrics_breakdown(metrics.snapshot()),
    }
    print(json.dumps(line))


def serve_bench() -> None:
    """Serving-plane benchmark: N concurrent transform clients against
    one daemon, micro-batching scheduler off vs on (the PR-5 acceptance
    number: batching must raise QPS on the same workload), then on WITH
    the telemetry plane hot — SLO burn-rate evaluation ticking fast, the
    journal span ring armed, and a concurrent wire scraper draining
    ``telemetry_pull`` + cursored ``trace_pull`` the way ``tools/top
    --fleet --telemetry`` does. Emits ONE JSON line with every mode's
    QPS + latency quantiles, the scheduler run's mean batch occupancy,
    ``telemetry_overhead`` (fractional QPS cost of the telemetry run vs
    the plain scheduler-on run; tools/perfcheck.py gates it < 2%), and
    the standard per-phase metrics breakdown."""
    import contextlib
    import threading

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.pca import PCA
    from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon
    from spark_rapids_ml_tpu.utils import metrics

    d = int(os.environ.get("SRML_BENCH_SERVE_D", 256))
    k = int(os.environ.get("SRML_BENCH_SERVE_K", 16))
    clients = int(os.environ.get("SRML_BENCH_SERVE_CLIENTS", 8))
    reqs = int(os.environ.get("SRML_BENCH_SERVE_REQS", 40))
    rows = int(os.environ.get("SRML_BENCH_SERVE_ROWS", 64))
    rng = np.random.default_rng(0)
    data = rng.standard_normal((4096, d)).astype(np.float32)
    model = PCA().setK(k).fit({"features": data})
    arrays = model._model_data()
    queries = rng.standard_normal((clients, rows, d)).astype(np.float32)

    def run(batching: bool, telemetry: bool = False) -> dict:
        metrics.reset()
        lat: list = []
        lat_lock = threading.Lock()
        errors: list = []
        opts = [("serve_batching", batching)]
        if telemetry:
            # The telemetry plane at its most expensive supported
            # setting: an SLO objective to evaluate every 50 ms, plus
            # the wire scraper below. The span ring is armed in every
            # mode (the production default) — the delta measured here
            # is evaluation + scraping.
            opts += [
                ("slo_objectives", "transform:p99_ms=250@0.01"),
                ("telemetry_eval_interval_s", 0.05),
            ]
        with contextlib.ExitStack() as stack:
            for key, val in opts:
                stack.enter_context(config.option(key, val))
            with DataPlaneDaemon() as daemon:
                host, port = daemon.address
                with DataPlaneClient(host, port) as c0:
                    c0.ensure_model("bench-serve", "pca", arrays)
                    if batching:
                        c0.warmup("bench-serve", n_cols=d, dtype="float32")
                    else:  # same warm jit caches for the off mode
                        c0.transform("bench-serve", queries[0])
                scrape_stop = threading.Event()
                pulls = [0]

                def scraper() -> None:
                    # What tools/top --fleet --telemetry does to every
                    # replica, at an aggressive cadence: full telemetry
                    # export + cursored trace drain, on its own
                    # connection, competing with the serving traffic.
                    cursor = 0
                    with DataPlaneClient(host, port) as sc:
                        while not scrape_stop.wait(0.05):
                            sc.telemetry_pull()
                            cursor = int(
                                sc.trace_pull(cursor).get("seq") or cursor
                            )
                            pulls[0] += 1

                scrape_thread = None
                if telemetry:
                    scrape_thread = threading.Thread(
                        target=scraper, name="bench-telemetry-scraper",
                        daemon=True,
                    )
                    scrape_thread.start()
                barrier = threading.Barrier(clients)

                def worker(i: int) -> None:
                    # A failed worker must fail the BENCH record: silently
                    # dropping its requests would still divide by the full
                    # clients*reqs and print a wrong QPS.
                    mine = []
                    try:
                        with DataPlaneClient(host, port) as c:
                            barrier.wait()
                            for _ in range(reqs):
                                t0 = time.perf_counter()
                                c.transform("bench-serve", queries[i])
                                mine.append(time.perf_counter() - t0)
                    except BaseException as e:
                        barrier.abort()  # peers fail fast, never hang
                        with lat_lock:
                            errors.append(e)
                        raise
                    with lat_lock:
                        lat.extend(mine)

                threads = [
                    threading.Thread(target=worker, args=(i,))
                    for i in range(clients)
                ]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                if scrape_thread is not None:
                    scrape_stop.set()
                    scrape_thread.join(timeout=10)
        if errors:
            raise RuntimeError(
                f"{len(errors)}/{clients} serve-bench workers failed "
                f"(batching={batching})"
            ) from errors[0]
        lat.sort()
        occ = metrics.snapshot().get("srml_scheduler_batch_rows", {})
        samples = occ.get("samples", [])
        total = sum(s["sum"] for s in samples)
        count = sum(s["count"] for s in samples)
        out = {
            "qps": round(clients * reqs / wall, 1),
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3, 3),
        }
        if count:
            out["mean_batch_occupancy"] = round(total / count, 2)
        if telemetry:
            out["scrapes"] = pulls[0]
        return out

    off = run(False)
    metrics.reset()
    on = run(True)
    on_breakdown = _metrics_breakdown(metrics.snapshot())
    metrics.reset()
    tel = run(True, telemetry=True)
    overhead = (
        round(max(0.0, 1.0 - tel["qps"] / on["qps"]), 4)
        if on["qps"] else None
    )
    print(json.dumps({
        "metric": f"serve_transform_qps_d{d}_k{k}_c{clients}_b{rows}",
        # Headline value = the production configuration's QPS
        # (scheduler on, telemetry plane at its defaults): what the
        # perfcheck throughput gate tracks against the trajectory.
        "value": on["qps"],
        "unit": "transforms/s",
        "clients": clients,
        "batch_rows": rows,
        "scheduler_off": off,
        "scheduler_on": on,
        "telemetry_on": tel,
        "telemetry_overhead": overhead,
        "speedup": round(on["qps"] / off["qps"], 3) if off["qps"] else None,
        "metrics": on_breakdown,
    }))


def chaos_elastic_bench() -> None:
    """``--chaos-elastic``: the recovery-cost micro-record for the
    elastic fit (docs/protocol.md "Permanent daemon loss").

    Three in-process daemons drive a hub-protocol kmeans fit (the same
    feed/commit → export/merge → step → set_iterate sequence the Spark
    estimator runs); the peer holding a third of the partitions is
    STOPPED mid-pass and never restarted. The bench then performs the
    estimator's degrade unit — liveness probe to deadline exhaustion,
    survivor rewind to the last boundary iterate, the full pass replayed
    with the dead daemon's partitions rerouted — and times it. Integer-
    valued data makes every fold exact, so the record self-verifies: the
    degraded fit's centers must be bitwise-equal to an uninterrupted fit
    on the surviving 2-daemon topology. One JSON line; perfcheck gates
    ``recovery_overhead``/``value`` against the CHAOS_r* trajectory."""
    from spark_rapids_ml_tpu.serve.client import DataPlaneClient
    from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon

    d = int(os.environ.get("SRML_BENCH_ELASTIC_D", 64))
    k = int(os.environ.get("SRML_BENCH_ELASTIC_K", 8))
    part_rows = int(os.environ.get("SRML_BENCH_ELASTIC_PART_ROWS", 32768))
    passes = max(int(os.environ.get("SRML_BENCH_ELASTIC_PASSES", 3)), 2)
    death_timeout = float(
        os.environ.get("SRML_BENCH_ELASTIC_DEATH_TIMEOUT_S", 1.0)
    )
    n_parts = 6
    rng = np.random.default_rng(7)
    centers0 = rng.integers(-12, 13, size=(k, d)) * 4
    n = n_parts * part_rows
    x = (
        centers0[rng.integers(0, k, size=(n,))]
        + rng.integers(-1, 2, size=(n, d))
    ).astype(np.float64)
    parts = [np.ascontiguousarray(p) for p in np.array_split(x, n_parts)]
    seed_batch = x[: 32 * k]
    params = {"k": k, "seed": 11}

    def client(daemon):
        return DataPlaneClient(
            *daemon.address, timeout=60.0, max_op_attempts=2,
            backoff_base_s=0.02, backoff_max_s=0.2,
        )

    def feed_pass(job, routing, it):
        for pid, c in routing.items():
            c.feed(job, parts[pid], algo="kmeans", partition=pid,
                   pass_id=it, params=params)
            c.commit(job, partition=pid, pass_id=it)

    def reduce_step_sync(job, primary, peers):
        for pc in peers:
            arrays, meta = pc.export_state(job)
            primary.merge_state(
                job, arrays, rows=int(meta["pass_rows"]), algo="kmeans",
                n_cols=d, params=params,
            )
        info = primary.step(job)
        arrays, it_n = primary.get_iterate(job)
        for pc in peers:
            pc.set_iterate(job, arrays, it_n)
        return info, (arrays, it_n)

    record: dict = {
        "metric": f"chaos_elastic_replay_rows_per_s_d{d}_k{k}",
        "unit": "rows/s",
        "mode": "chaos_elastic",
        "n_daemons": 3,
        "n_survivors": 2,
        "rows": n,
        "passes": passes,
        "death_timeout_s": death_timeout,
    }
    da = DataPlaneDaemon(ttl=3600.0).start()
    db = DataPlaneDaemon(ttl=3600.0).start()
    dc_ = DataPlaneDaemon(ttl=3600.0).start()
    ca, cb, cc = client(da), client(db), client(dc_)
    try:
        # Oracle: the surviving topology (a holds the victim's
        # partitions), uninterrupted — also the steady-pass clock.
        job = "elastic-oracle"
        steady = []
        for c in (ca, cc):
            c.seed_kmeans(job, seed_batch, k=k, params=params)
        routing2 = {pid: (cc if pid >= 4 else ca) for pid in range(n_parts)}
        for it in range(passes):
            t0 = time.perf_counter()
            feed_pass(job, routing2, it)
            reduce_step_sync(job, ca, [cc])
            steady.append(time.perf_counter() - t0)
        oracle, _ = ca.finalize(job, {}, drop=False)
        ca.drop(job)
        steady_pass_s = min(steady)

        # Degraded run: 3 daemons; the victim dies mid-pass-1 for good.
        job = "elastic-degrade"
        for c in (ca, cb, cc):
            c.seed_kmeans(job, seed_batch, k=k, params=params)
        routing3 = {
            pid: (cc if pid >= 4 else cb if pid >= 2 else ca)
            for pid in range(n_parts)
        }
        feed_pass(job, routing3, 0)
        _, ledger = reduce_step_sync(job, ca, [cb, cc])
        # Pass 1 opens normally, then the victim vanishes under it.
        for pid in (0, 1):
            ca.feed(job, parts[pid], algo="kmeans", partition=pid,
                    pass_id=1, params=params)
            ca.commit(job, partition=pid, pass_id=1)
        db.stop()  # the permanent death — nothing ever restarts it
        failed = False
        try:
            cb.feed(job, parts[2], algo="kmeans", partition=2, pass_id=1,
                    params=params)
        except Exception:
            failed = True
        assert failed, "the dead daemon accepted a feed?"
        # The degrade unit, timed end to end: classify → rewind → replay.
        t0 = time.perf_counter()
        probe_t0 = time.perf_counter()
        dead = False
        try:
            with DataPlaneClient(
                *db.address, timeout=60.0, op_deadline_s=death_timeout,
                max_op_attempts=8, backoff_base_s=0.02, backoff_max_s=0.2,
            ) as probe:
                probe.ping()
        except Exception:
            dead = True
        probe_s = time.perf_counter() - probe_t0
        assert dead, "the liveness probe answered for a stopped daemon"
        arrays, it_n = ledger
        ca.set_iterate(job, arrays, it_n)
        cc.set_iterate(job, arrays, it_n)
        routing_shrunk = {
            pid: (cc if pid >= 4 else ca) for pid in range(n_parts)
        }
        feed_pass(job, routing_shrunk, 1)
        _, ledger = reduce_step_sync(job, ca, [cc])
        time_to_recover = time.perf_counter() - t0
        for it in range(2, passes):
            feed_pass(job, routing_shrunk, it)
            reduce_step_sync(job, ca, [cc])
        degraded, _ = ca.finalize(job, {}, drop=False)
        ca.drop(job)
        cc.drop(job)

        record.update({
            "value": round(n / time_to_recover, 1),
            "time_to_recover_s": round(time_to_recover, 4),
            "probe_s": round(probe_s, 4),
            "replayed_rows": n,
            "steady_pass_s": round(steady_pass_s, 4),
            "recovery_overhead": round(time_to_recover / steady_pass_s, 3),
            "bitwise_equal_oracle": bool(
                np.array_equal(degraded["centers"], oracle["centers"])
            ),
        })
    finally:
        for c in (ca, cb, cc):
            c.close()
        for daemon in (da, db, dc_):
            daemon.stop()
    print(json.dumps(record))


def chaos_grow_bench() -> None:
    """``--chaos-grow``: the scale-UP micro-record for the elastic fit
    (docs/protocol.md "Mid-fit daemon join") — the mirror image of
    ``--chaos-elastic``'s 3→2 degrade.

    Two in-process daemons drive a hub-protocol kmeans fit; at the first
    pass boundary a THIRD daemon appears and is admitted the way the
    estimator's grow path admits it — one creating ``set_iterate``
    carrying the boundary iterate plus the algo/n_cols/params creation
    fields (the same PR 4 ledger replay uses) — and a third of the
    partitions rebalance onto it for the middle passes. At the next
    boundary the fleet shrinks back to two (the joiner's partials are
    merged at the boundary, then it simply stops being routed to and is
    stopped), so one record exercises grow AND shrink. Integer-valued
    data makes every fold exact, so the record self-verifies: the grown
    2→3→2 fit's centers must be bitwise-equal to an uninterrupted fit on
    the static 2-daemon topology. Reported: ``time_to_admit_s`` (the
    admission handshake alone), ``rebalanced_rows`` (rows moved onto the
    joiner), ``grow_overhead`` (admit + first grown pass / steady pass).
    One JSON line; perfcheck's ``check_chaos_grow`` gates correctness
    absolutely and the cost numbers against the CHAOS_r* trajectory."""
    from spark_rapids_ml_tpu.serve.client import DataPlaneClient
    from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon

    d = int(os.environ.get("SRML_BENCH_GROW_D", 64))
    k = int(os.environ.get("SRML_BENCH_GROW_K", 8))
    part_rows = int(os.environ.get("SRML_BENCH_GROW_PART_ROWS", 32768))
    passes = max(int(os.environ.get("SRML_BENCH_GROW_PASSES", 3)), 3)
    n_parts = 6
    rng = np.random.default_rng(7)
    centers0 = rng.integers(-12, 13, size=(k, d)) * 4
    n = n_parts * part_rows
    x = (
        centers0[rng.integers(0, k, size=(n,))]
        + rng.integers(-1, 2, size=(n, d))
    ).astype(np.float64)
    parts = [np.ascontiguousarray(p) for p in np.array_split(x, n_parts)]
    seed_batch = x[: 32 * k]
    params = {"k": k, "seed": 11}

    def client(daemon):
        return DataPlaneClient(
            *daemon.address, timeout=60.0, max_op_attempts=2,
            backoff_base_s=0.02, backoff_max_s=0.2,
        )

    def feed_pass(job, routing, it):
        for pid, c in routing.items():
            c.feed(job, parts[pid], algo="kmeans", partition=pid,
                   pass_id=it, params=params)
            c.commit(job, partition=pid, pass_id=it)

    def reduce_step_sync(job, primary, peers):
        for pc in peers:
            arrays, meta = pc.export_state(job)
            primary.merge_state(
                job, arrays, rows=int(meta["pass_rows"]), algo="kmeans",
                n_cols=d, params=params,
            )
        info = primary.step(job)
        arrays, it_n = primary.get_iterate(job)
        for pc in peers:
            pc.set_iterate(job, arrays, it_n)
        return info, (arrays, it_n)

    record: dict = {
        "metric": f"chaos_grow_admit_rows_per_s_d{d}_k{k}",
        "unit": "rows/s",
        "mode": "chaos_grow",
        "n_daemons": 2,
        "n_grown": 3,
        "rows": n,
        "passes": passes,
    }
    da = DataPlaneDaemon(ttl=3600.0).start()
    dc_ = DataPlaneDaemon(ttl=3600.0).start()
    ca, cc = client(da), client(dc_)
    db = None
    cb = None
    try:
        # Oracle: the static 2-daemon topology, uninterrupted — also
        # the steady-pass clock the grow overhead is measured against.
        job = "grow-oracle"
        steady = []
        for c in (ca, cc):
            c.seed_kmeans(job, seed_batch, k=k, params=params)
        routing2 = {pid: (cc if pid >= 3 else ca) for pid in range(n_parts)}
        for it in range(passes):
            t0 = time.perf_counter()
            feed_pass(job, routing2, it)
            reduce_step_sync(job, ca, [cc])
            steady.append(time.perf_counter() - t0)
        oracle, _ = ca.finalize(job, {}, drop=False)
        ca.drop(job)
        steady_pass_s = min(steady)

        # Grown run: pass 0 on two daemons, then the joiner appears at
        # the boundary and takes partitions 2-3 for the middle passes.
        job = "grow-elastic"
        for c in (ca, cc):
            c.seed_kmeans(job, seed_batch, k=k, params=params)
        feed_pass(job, routing2, 0)
        _, ledger = reduce_step_sync(job, ca, [cc])

        t0 = time.perf_counter()
        db = DataPlaneDaemon(ttl=3600.0).start()
        cb = client(db)
        # The admission handshake: ONE creating set_iterate seeds the
        # joiner with the boundary iterate (same creation fields the
        # quarantine-replay ledger carries) — no seed_kmeans, no feed.
        admit_t0 = time.perf_counter()
        arrays, it_n = ledger
        cb.set_iterate(job, arrays, it_n, algo="kmeans", n_cols=d,
                       params=params)
        time_to_admit = time.perf_counter() - admit_t0
        routing3 = {
            pid: (cc if pid >= 4 else cb if pid >= 2 else ca)
            for pid in range(n_parts)
        }
        rebalanced_rows = sum(
            len(parts[pid]) for pid, c in routing3.items() if c is cb
        )
        feed_pass(job, routing3, 1)
        _, ledger = reduce_step_sync(job, ca, [cb, cc])
        time_to_grow = time.perf_counter() - t0

        # Grown middle passes, then shrink at the boundary: the
        # joiner's partials were merged by the reduce above, so the
        # last pass simply routes around it — no rewind, no replay.
        for it in range(2, passes - 1):
            feed_pass(job, routing3, it)
            reduce_step_sync(job, ca, [cb, cc])
        cb.close()
        cb = None
        db.stop()
        db = None
        feed_pass(job, routing2, passes - 1)
        reduce_step_sync(job, ca, [cc])
        grown, _ = ca.finalize(job, {}, drop=False)
        ca.drop(job)
        cc.drop(job)

        record.update({
            "value": round(rebalanced_rows / time_to_grow, 1),
            "time_to_admit_s": round(time_to_admit, 4),
            "time_to_grow_s": round(time_to_grow, 4),
            "rebalanced_rows": rebalanced_rows,
            "steady_pass_s": round(steady_pass_s, 4),
            "grow_overhead": round(time_to_grow / steady_pass_s, 3),
            "bitwise_equal_oracle": bool(
                np.array_equal(grown["centers"], oracle["centers"])
            ),
        })
    finally:
        for c in (ca, cb, cc):
            if c is not None:
                c.close()
        for daemon in (da, db, dc_):
            if daemon is not None:
                daemon.stop()
    print(json.dumps(record))


def chaos_partition_bench() -> None:
    """``--chaos-partition``: the gossip partition-heal micro-record
    for the fleet control plane (docs/protocol.md "Fleet gossip &
    bootstrap") — the serving-plane sibling of the elastic chaos pair.

    Four daemons with LIVE gossip threads form two islands that never
    hear of each other (each island's controller only ever pushes to
    its own pair, and gossip peers are drawn from each daemon's own
    view, so the split needs no firewall). Island B registers the model
    first; island A registers it and rolls it to v2 AFTER — so A's
    records dominate under the ``(epoch, boot_id)`` merge rule and v1
    carries a tombstone. A routed client bootstrapped from ONE island-B
    seed serves traffic throughout the split; every response must
    succeed and be bitwise-stable (a partition degrades freshness,
    never correctness). The heal is ONE bridge ``gossip_push`` from an
    island-A view into an island-B daemon; anti-entropy carries it the
    rest of the way. Reported: ``time_to_converge_s`` (bridge push →
    all four views agree: active v2, one record epoch, v1 tombstoned
    everywhere, four live replicas — the record self-verifies that the
    losing island's v1 never resurrects), plus the routed/failed/
    mismatched traffic tallies from inside the split. One JSON line;
    perfcheck's ``check_chaos_partition`` gates correctness absolutely
    and convergence time against the CHAOS_r* trajectory."""
    import threading

    from spark_rapids_ml_tpu.serve.client import DataPlaneClient
    from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon
    from spark_rapids_ml_tpu.serve.fleet import ModelFleet
    from spark_rapids_ml_tpu.serve.router import FleetClient

    d = int(os.environ.get("SRML_BENCH_PARTITION_D", 64))
    k = int(os.environ.get("SRML_BENCH_PARTITION_K", 8))
    rows = int(os.environ.get("SRML_BENCH_PARTITION_ROWS", 64))
    interval = float(os.environ.get("SRML_BENCH_PARTITION_INTERVAL_S", 0.05))
    fanout = int(os.environ.get("SRML_BENCH_PARTITION_FANOUT", 2))
    split_s = float(os.environ.get("SRML_BENCH_PARTITION_SPLIT_S", 0.5))
    deadline = float(os.environ.get("SRML_BENCH_PARTITION_DEADLINE_S", 30.0))
    model = "bench-partition"

    rng = np.random.default_rng(0)
    # Fabricated projections (the fleet_bench idiom — a (d, k) payload
    # needs no fit); v2 is a different shape so a flip is observable.
    arrays_v1 = {
        "pc": rng.standard_normal((d, k)).astype(np.float64),
        "mean": np.zeros((d,), np.float64),
    }
    arrays_v2 = {
        "pc": rng.standard_normal((d, k - 2)).astype(np.float64),
        "mean": np.zeros((d,), np.float64),
    }
    q = rng.standard_normal((rows, d)).astype(np.float64)

    record: dict = {
        "metric": "chaos_partition_converge_d4",
        "unit": "s",
        "mode": "chaos_partition",
        "n_daemons": 4,
        "gossip_interval_s": interval,
        "gossip_fanout": fanout,
    }
    daemons = [
        DataPlaneDaemon(
            ttl=3600.0, gossip_interval_s=interval, gossip_fanout=fanout,
        ).start()
        for _ in range(4)
    ]
    island_a, island_b = daemons[:2], daemons[2:]
    stop = threading.Event()
    routed = [0]
    failed = [0]
    mismatched = [0]

    def traffic() -> None:
        # A fresh operator box: ONE island-B seed, no endpoint roster.
        ref = None
        seed = "%s:%d" % island_b[0].address
        with FleetClient.from_seeds([seed]) as fc:
            while not stop.is_set():
                try:
                    got = np.asarray(
                        fc.transform(model, q, route_key="bench")["output"]
                    )
                except Exception:
                    failed[0] += 1
                    continue
                if ref is None:
                    ref = got
                elif not np.array_equal(got, ref):
                    mismatched[0] += 1
                routed[0] += 1

    try:
        # Island B first: its v1 records carry the OLDER epochs.
        with ModelFleet([d_.address for d_ in island_b]) as fb:
            fb.register(model, "pca", arrays_v1, version=1)
        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        # Island A second, and it rolls forward — both controllers live
        # in this process so they share one Lamport clock and A's
        # register + rollout strictly dominate B's stale v1 records.
        with ModelFleet([d_.address for d_ in island_a]) as fa:
            fa.register(model, "pca", arrays_v1, version=1)
            fa.rollout(model, "pca", arrays_v2, version=2, warm=False)
        time.sleep(split_s)  # let traffic route inside the split
        stop.set()
        t.join(timeout=60)

        def converged() -> bool:
            epochs = set()
            for dm in daemons:
                rec = dm.fleet_view.model(model)
                if rec is None or rec.get("active_version") != 2:
                    return False
                if rec.get("intent") is not None:
                    return False
                if "1" not in (rec.get("tombstones") or {}):
                    return False
                if len(dm.fleet_view.replicas(liveness="up")) != 4:
                    return False
                epochs.add(int(rec["epoch"]))
            return len(epochs) == 1

        # The heal: ONE bridge push A→B; the gossip threads do the rest.
        t0 = time.perf_counter()
        with DataPlaneClient(*island_b[0].address, timeout=10.0) as bridge:
            bridge.gossip_push(island_a[0].fleet_view.to_wire())
        while not converged():
            if time.perf_counter() - t0 > deadline:
                break
            time.sleep(interval / 4)
        time_to_converge = time.perf_counter() - t0

        record.update({
            "value": round(time_to_converge, 4),
            "time_to_converge_s": round(time_to_converge, 4),
            "converged": converged(),
            "routed_during_partition": routed[0],
            "failed_during_partition": failed[0],
            "mismatched_during_partition": mismatched[0],
            "tombstones_clean": all(
                "1" in (dm.fleet_view.model(model) or {}).get(
                    "tombstones", {}
                )
                for dm in daemons
            ),
            "split_s": split_s,
        })
    finally:
        stop.set()
        for dm in daemons:
            dm.stop()
    print(json.dumps(record))


def forest_bench() -> None:
    """``--forest``: histogram tree-ensemble throughput (the first
    non-GEMM workload record — FOREST_r*).

    Fits a RandomForest classifier (models/random_forest.py: quantile
    binning + fused per-depth histogram accumulate + vectorized split
    scoring, all level-synchronous on device) on a clustered synthetic
    classification set and measures

      * ``value``: fit SCAN throughput, rows/s — rows x depth-passes
        over the fit wall clock (each pass re-scans the dataset, the
        honest analogue of the streaming-fit rows/s headline);
      * ``transform_rows_per_s``: bucketed ``predict_matrix`` QPS over
        repeated batches (warm jit — serving-path throughput);
      * a held-out ``accuracy`` self-check, differential against a
        sklearn-CPU RandomForest baseline when sklearn is installed
        (``baseline.impl: "sklearn"``; ``accuracy_ok`` = ours within
        0.05 of the baseline — an ABSOLUTE correctness gate for
        tools/perfcheck.py check_forest, not history-relative).

    One JSON line; ``tools/perfcheck.py`` gates fit/transform
    throughput against the FOREST_r* trajectory (SKIP-not-pass without
    history) and the accuracy gate absolutely."""
    import jax

    from spark_rapids_ml_tpu.models.random_forest import (
        RandomForestClassificationModel,
        fit_random_forest_classifier,
    )

    n = int(os.environ.get("SRML_BENCH_FOREST_ROWS", 200_000))
    d = int(os.environ.get("SRML_BENCH_FOREST_COLS", 32))
    trees = int(os.environ.get("SRML_BENCH_FOREST_TREES", 8))
    depth = int(os.environ.get("SRML_BENCH_FOREST_DEPTH", 6))
    bins = int(os.environ.get("SRML_BENCH_FOREST_BINS", 32))
    classes = int(os.environ.get("SRML_BENCH_FOREST_CLASSES", 4))
    n_test = max(n // 10, 1024)
    rng = np.random.default_rng(11)
    centers = rng.normal(size=(classes, d)) * 6.0
    y_all = rng.integers(0, classes, size=n + n_test)
    x_all = (
        centers[y_all] + rng.normal(size=(n + n_test, d))
    ).astype(np.float32)
    x, y = x_all[:n], y_all[:n]
    x_test, y_test = x_all[n:], y_all[n:]

    def fit_ours():
        t0 = time.perf_counter()
        sol = fit_random_forest_classifier(
            x, y, n_classes=classes, num_trees=trees, max_depth=depth,
            max_bins=bins, seed=5,
        )
        return sol, time.perf_counter() - t0

    # Warmup fit compiles the per-depth programs; the timed fit
    # measures steady dispatch (the compile-storm split every BENCH
    # record keeps).
    fit_ours()
    sol, fit_s = fit_ours()
    model = RandomForestClassificationModel(arrays=sol.arrays)
    acc = float(np.mean(model.predict(x_test) == y_test))

    batch = x_test[:4096] if n_test >= 4096 else x_test
    model.predict(batch)  # warm the predict ladder
    reps = max(int(2_000_000 // max(batch.shape[0], 1)), 5)
    t0 = time.perf_counter()
    for _ in range(reps):
        model.predict(batch)
    transform_s = time.perf_counter() - t0
    transform_rps = reps * batch.shape[0] / transform_s

    baseline: dict = {"impl": None}
    speedup_fit = speedup_transform = None
    accuracy_ok = True
    try:
        from sklearn.ensemble import RandomForestClassifier as SkRF

        t0 = time.perf_counter()
        sk = SkRF(
            n_estimators=trees, max_depth=depth, random_state=5, n_jobs=-1
        ).fit(x, y)
        sk_fit_s = time.perf_counter() - t0
        sk.predict(batch)
        t0 = time.perf_counter()
        for _ in range(max(reps // 4, 2)):
            sk.predict(batch)
        sk_tr_s = time.perf_counter() - t0
        sk_rps = max(reps // 4, 2) * batch.shape[0] / sk_tr_s
        sk_acc = float(sk.score(x_test, y_test))
        baseline = {
            "impl": "sklearn",
            "fit_s": round(sk_fit_s, 4),
            "transform_rows_per_s": round(sk_rps, 1),
            "accuracy": round(sk_acc, 4),
        }
        speedup_fit = round(sk_fit_s / fit_s, 3)
        speedup_transform = round(transform_rps / sk_rps, 3)
        accuracy_ok = acc >= sk_acc - 0.05
    except ImportError:
        # No sklearn on this image: the accuracy gate falls back to an
        # absolute floor on the easy synthetic shape.
        accuracy_ok = acc >= 0.9

    record = {
        "metric": (
            f"forest_fit_rows_per_s_n{n}_d{d}_t{trees}"
            f"_depth{depth}_b{bins}"
        ),
        "unit": "rows/s",
        "mode": "forest",
        "value": round(n * sol.n_passes / fit_s, 1),
        "rows": n,
        "n_cols": d,
        "trees": trees,
        "max_depth": depth,
        "max_bins": bins,
        "n_classes": classes,
        "passes": sol.n_passes,
        "fit_s": round(fit_s, 4),
        "transform_rows_per_s": round(transform_rps, 1),
        "accuracy": round(acc, 4),
        "accuracy_ok": bool(accuracy_ok),
        "baseline": baseline,
        "speedup_fit": speedup_fit,
        "speedup_transform": speedup_transform,
        "backend": jax.default_backend(),
    }
    print(json.dumps(record))


def kernels_bench() -> None:
    """``--kernels``: fused-vs-unfused microbench, ONE JSON record line
    per kernel — the per-kernel receipt behind the fusion PR's headline.

    Each record carries the fused path's throughput (``value``), the
    unfused XLA two-step's (``unfused_rows_per_s``), and their ratio
    (``speedup``); ``tools/perfcheck.py check_kernels`` gates the fused
    path as NEVER-SLOWER-THAN-UNFUSED on the same backend. Off-TPU the
    fused kernels run the Pallas interpreter, which measures nothing
    about the TPU kernel — those records are marked ``interpret`` and
    perfcheck reads them as SKIP, never pass (the shapes also shrink to
    smoke size there). Kernels covered: the single-pass streaming
    count/colsum/Gram (``gram_colsum_pallas`` vs the XLA mask two-step)
    and the streaming distance+top-k (``dist_topk_pallas`` vs
    ``sq_euclidean`` → ``lax.top_k``)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops import pallas_kernels as pk
    from spark_rapids_ml_tpu.ops.distances import sq_euclidean
    from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

    backend = jax.default_backend()
    interpret = backend != "tpu"
    tpu = not interpret
    n = int(os.environ.get("SRML_BENCH_KERNELS_ROWS",
                           1 << 17 if tpu else 1 << 12))
    d = int(os.environ.get("SRML_BENCH_KERNELS_COLS", 1024 if tpu else 256))
    q = int(os.environ.get("SRML_BENCH_KERNELS_QUERIES", 1024 if tpu else 64))
    k = int(os.environ.get("SRML_BENCH_KERNELS_K", 16 if tpu else 8))
    reps = int(os.environ.get("SRML_BENCH_KERNELS_REPS", 8 if tpu else 2))
    cd = jnp.bfloat16 if tpu else jnp.float32
    cd_name = jnp.dtype(cd).name

    x = jax.random.normal(jax.random.key(0), (n, d), jnp.float32).astype(cd)
    queries = jax.random.normal(
        jax.random.key(1), (q, d), jnp.float32
    ).astype(cd)
    ids = jnp.arange(n, dtype=jnp.int32)
    mask = jnp.ones((n,), jnp.float32)

    def timed(fn, *args) -> float:
        jax.block_until_ready(fn(*args))  # compile + warm outside the clock
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    @ledgered_jit("bench.kernels_gram_fused")
    def gram_fused(xb):
        return pk.gram_colsum_pallas(xb, n, interpret=interpret)

    @ledgered_jit("bench.kernels_gram_unfused")
    def gram_unfused(xb):
        return gram_ops.local_stats(
            xb, compute_dtype=cd_name, accum_dtype="float32",
            use_pallas=False,
        )

    @ledgered_jit("bench.kernels_topk_fused")
    def topk_fused(qs, xb):
        return pk.dist_topk_pallas(qs, xb, ids, mask, k, interpret=interpret)

    @ledgered_jit("bench.kernels_topk_unfused")
    def topk_unfused(qs, xb):
        d2 = sq_euclidean(qs, xb, accum_dtype=jnp.float32)
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx

    for kernel, fused_s, unfused_s, rows, shape in (
        (
            "gram_colsum",
            timed(gram_fused, x),
            timed(gram_unfused, x),
            n,
            f"n{n}_d{d}_{cd_name}",
        ),
        (
            "dist_topk",
            timed(topk_fused, queries, x),
            timed(topk_unfused, queries, x),
            n,  # db rows scanned per query batch
            f"n{n}_d{d}_q{q}_k{k}_{cd_name}",
        ),
    ):
        fused_rps = rows / fused_s
        unfused_rps = rows / unfused_s
        print(json.dumps({
            "metric": f"kernel_{kernel}_{shape}",
            "mode": "kernels",
            "kernel": kernel,
            "value": round(fused_rps, 1),
            "unit": "rows/s",
            "unfused_rows_per_s": round(unfused_rps, 1),
            "speedup": round(fused_rps / unfused_rps, 4),
            "fused_s": round(fused_s, 6),
            "unfused_s": round(unfused_s, 6),
            "backend": backend,
            "interpret": interpret,
        }))


def _fleet_daemon_worker() -> None:
    """``--fleet-daemon`` subcommand: one replica daemon as its own OS
    process (the deployment unit). Prints ``READY <port>``; serves until
    stdin closes — the parent's handle drop is the shutdown signal, so
    an aborted bench never leaks the process (tests/daemon_worker.py's
    contract).

    ``SRML_BENCH_FLEET_CPUS`` (a comma-separated core list) pins this
    replica's CPU affinity BEFORE the jax import sizes its threadpools:
    on a real fleet each replica owns its own host's silicon, so a
    shared-box measurement must give each replica a fixed disjoint core
    slice — otherwise one daemon's XLA threadpool absorbs the whole
    machine and "adding replicas" just re-partitions the same cores,
    measuring nothing."""
    cpus = os.environ.get("SRML_BENCH_FLEET_CPUS")
    if cpus and hasattr(os, "sched_setaffinity"):
        os.sched_setaffinity(0, {int(c) for c in cpus.split(",")})

    import jax

    # Pinned to the CPU: N replica PROCESSES cannot share one chip (a
    # chip belongs to one process). The READY line carries the backend
    # this worker actually got, so the record states it from observation.
    jax.config.update("jax_platforms", "cpu")

    from spark_rapids_ml_tpu.serve import DataPlaneDaemon
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    daemon = DataPlaneDaemon(host="127.0.0.1", port=0, ttl=600.0).start()
    print(f"READY {daemon.address[1]} {jax.default_backend()}", flush=True)
    sys.stdin.read()
    daemon.stop()


def _fleet_client_worker() -> None:
    """``--fleet-client`` subcommand: one load-generating client process
    running ``SRML_BENCH_FLEET_THREADS`` request loops (each its own
    FleetClient — the router is single-threaded by contract; threads
    overlap the wire wait, which is most of a small request's latency).
    Each loop routes ``SRML_BENCH_FLEET_REQS`` transforms with fresh
    route keys (uniform spread), then the worker prints ONE JSON line of
    per-request latencies. Prints ``READY`` after warmup and waits for
    ``GO`` on stdin so the parent can open every worker's timed window
    together."""
    import threading

    # Same affinity contract as the daemon worker: load generators are
    # pinned OFF the replica cores (and identically in the 1-replica and
    # N-replica phases), so adding replicas changes replica resources
    # and nothing else.
    cpus = os.environ.get("SRML_BENCH_FLEET_CPUS")
    if cpus and hasattr(os, "sched_setaffinity"):
        os.sched_setaffinity(0, {int(c) for c in cpus.split(",")})

    import jax

    jax.config.update("jax_platforms", "cpu")

    from spark_rapids_ml_tpu.serve.fleet import ModelFleet

    endpoints = os.environ["SRML_BENCH_FLEET_ENDPOINTS"].split(",")
    model = os.environ.get("SRML_BENCH_FLEET_MODEL", "bench-fleet")
    reqs = int(os.environ.get("SRML_BENCH_FLEET_REQS", 50))
    rows = int(os.environ.get("SRML_BENCH_FLEET_ROWS", 64))
    d = int(os.environ.get("SRML_BENCH_FLEET_D", 256))
    threads_n = int(os.environ.get("SRML_BENCH_FLEET_THREADS", 2))
    seed = int(os.environ.get("SRML_BENCH_FLEET_SEED", 0))
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((rows, d)).astype(np.float32)

    fleet = ModelFleet([(e.rsplit(":", 1)[0], int(e.rsplit(":", 1)[1]))
                        for e in endpoints])
    # The table needs the model's active version; the parent registered
    # v1 on every replica — mirror that registration table-side only
    # (arrays are only needed for in-band repair, which the bench skips).
    fleet.table.install(model, 1, "pca", {}, {})
    fleet.table.activate(model, 1)
    # Round-robin STICKY keys, one per replica: hashing a fresh nonce
    # per request is uniform on average but binomially imbalanced at any
    # instant (some replica queues while another idles); a throughput
    # client cycles a key per ring member instead — still pure
    # client-side routing, now perfectly balanced. Failover semantics
    # are unchanged.
    ring = fleet.table.ring
    keys: list = []
    probe = 0
    want = set(ring.members)
    while want:
        k = f"rr-{probe}"
        probe += 1
        owner = ring.primary(k)
        if owner in want:
            want.discard(owner)
            keys.append(k)
    clients = [fleet.client() for _ in range(threads_n)]
    for c in clients:
        c.transform(model, q)  # warm each loop's route + sockets
    print("READY", flush=True)
    for line in sys.stdin:
        if line.strip() == "GO":
            break
    lat: list = []
    lock = threading.Lock()

    def loop(client, offset: int) -> None:
        mine = []
        for n in range(reqs):
            t0 = time.perf_counter()
            client.transform(model, q,
                             route_key=keys[(offset + n) % len(keys)])
            mine.append(time.perf_counter() - t0)
        with lock:
            lat.extend(mine)

    ts = [threading.Thread(target=loop, args=(c, i))
          for i, c in enumerate(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for c in clients:
        c.close()
    fleet.close()
    print(json.dumps({"latencies": lat}), flush=True)


_ECHO_SERVER = """
import socket, sys
req_bytes, resp_bytes = int(sys.argv[1]), int(sys.argv[2])
hdr = b"h" * 128
resp = b"r" * resp_bytes
srv = socket.socket(); srv.bind(("127.0.0.1", 0)); srv.listen(4)
print(srv.getsockname()[1], flush=True)
conn, _ = srv.accept()
want = 256 + req_bytes  # header frame + payload frame, like a transform
with conn:
    while True:
        got = 0
        while got < want:
            data = conn.recv(1 << 20)
            if not data:
                raise SystemExit(0)
            got += len(data)
        conn.sendall(hdr)
        conn.sendall(resp)
"""

_ECHO_CLIENT = """
import socket, sys, time
port, req_bytes, resp_bytes, secs = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), float(sys.argv[4])
)
hdr = b"h" * 256
payload = b"a" * req_bytes
want = 128 + resp_bytes
n = 0
with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    stop = time.monotonic() + secs
    while time.monotonic() < stop:
        s.sendall(hdr)
        s.sendall(payload)
        got = 0
        while got < want:
            data = s.recv(1 << 20)
            if not data:
                raise SystemExit(1)
            got += len(data)
        n += 1
print(n)
"""


def _wire_fabric_scaling(n: int, req_bytes: int, resp_bytes: int,
                         secs: float = 2.0) -> dict:
    """Raw loopback request/response scaling, 1 vs n PROCESS pairs with
    the serving protocol's frame pattern (header+payload up,
    header+arrays down, real sizes) — the fleet twin of --multichip's
    raw allreduce microphase. On a real kernel this is ~linear and huge;
    on a sandboxed/virtualized network stack it is the hard ceiling
    every replica shares, and the fleet record must say so rather than
    let the environment read as a fleet-layer regression."""
    import subprocess

    def run(pairs: int) -> float:
        servers = [
            subprocess.Popen(
                [sys.executable, "-c", _ECHO_SERVER, str(req_bytes),
                 str(resp_bytes)],
                stdout=subprocess.PIPE, text=True,
            )
            for _ in range(pairs)
        ]
        ports = [int(s.stdout.readline()) for s in servers]
        clients = [
            subprocess.Popen(
                [sys.executable, "-c", _ECHO_CLIENT, str(p), str(req_bytes),
                 str(resp_bytes), str(secs)],
                stdout=subprocess.PIPE, text=True,
            )
            for p in ports
        ]
        total = sum(int(c.communicate()[0]) for c in clients)
        for s in servers:
            s.kill()
        return total / secs

    one = run(1)
    many = run(n)
    return {
        "pairs": n, "req_bytes": req_bytes, "resp_bytes": resp_bytes,
        "reqs_per_s_1": round(one, 1), "reqs_per_s_n": round(many, 1),
        "efficiency": round(many / (n * one), 4) if one else 0.0,
    }


def fleet_bench() -> None:
    """Fleet-serving benchmark (module docstring): QPS at 1 replica vs
    N replicas, same M-client workload, scaling efficiency recorded and
    gated (tools/perfcheck.py ``check_serve_fleet``).

    Single-box honesty: every replica of a single-box measurement
    shares the host's loopback stack, so the record also measures the
    RAW WIRE FABRIC's own process-scaling (an echo microphase at the
    request payload size — the fleet twin of --multichip's raw
    allreduce microphase). A fabric that itself scales below the floor
    marks the record ``wire_limited``: the absolute efficiency gate
    SKIPs (never a pass — the environment, not the fleet, is the
    ceiling) and the FABRIC-RELATIVE efficiency (QPS scaling divided by
    wire scaling) is gated instead, isolating what the fleet LAYER
    costs on top of whatever transport it rides. Replica daemons are
    additionally core-pinned (disjoint slices, clients on the
    remainder) so on hosts where affinity binds, one replica cannot
    absorb the whole box's compute."""
    import subprocess
    import threading

    from spark_rapids_ml_tpu.serve.fleet import ModelFleet

    d = int(os.environ.get("SRML_BENCH_FLEET_D", 256))
    k = int(os.environ.get("SRML_BENCH_FLEET_K", 16))
    n_replicas = int(os.environ.get("SRML_BENCH_FLEET_REPLICAS", 4))
    clients = int(os.environ.get("SRML_BENCH_FLEET_CLIENTS", 8))
    threads_per = int(os.environ.get("SRML_BENCH_FLEET_THREADS", 2))
    reqs = int(os.environ.get("SRML_BENCH_FLEET_REQS", 50))
    rows = int(os.environ.get("SRML_BENCH_FLEET_ROWS", 64))
    inproc = os.environ.get("SRML_BENCH_FLEET_INPROC", "") in ("1", "true")
    # Cores pinned per replica daemon (0 = no pinning): each replica
    # models a host that owns a FIXED silicon slice — without disjoint
    # affinity one daemon's XLA threadpool spans the whole box and the
    # 1-replica baseline already uses all the compute the N-replica run
    # would (see _fleet_daemon_worker).
    cpus_per = int(os.environ.get("SRML_BENCH_FLEET_CPUS_PER_REPLICA", 2))
    # Total concurrent request loops (and the request count the run must
    # account for, to the request): in-process smoke mode runs plain
    # threads, so threads_per applies to the subprocess mode only.
    loops = clients * (1 if inproc else threads_per)

    rng = np.random.default_rng(0)
    # Fabricated projection — the serving plane only needs a model
    # artifact, and a (d, k) payload needs no fit.
    arrays = {
        "pc": rng.standard_normal((d, k)).astype(np.float64),
        "mean": np.zeros((d,), np.float64),
    }

    #: Backends the replica daemons report (READY line / this process).
    backends: set = set()

    def spawn_daemons(n: int):
        if inproc:
            import jax

            from spark_rapids_ml_tpu.serve import DataPlaneDaemon

            daemons = [DataPlaneDaemon().start() for _ in range(n)]
            backends.add(jax.default_backend())
            return daemons, [d_.address for d_ in daemons]
        procs = []
        addrs = []
        for i in range(n):
            env = dict(os.environ)
            if cpus_per > 0 and hasattr(os, "sched_setaffinity"):
                cores = sorted(os.sched_getaffinity(0))
                slice_ = [
                    str(cores[c % len(cores)])
                    for c in range(i * cpus_per, (i + 1) * cpus_per)
                ]
                env["SRML_BENCH_FLEET_CPUS"] = ",".join(slice_)
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--fleet-daemon"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
            )
            procs.append(p)
        for p in procs:
            line = p.stdout.readline()
            assert line.startswith("READY"), f"daemon worker said {line!r}"
            addrs.append(("127.0.0.1", int(line.split()[1])))
            backends.add(line.split()[2])
        return procs, addrs

    def stop_daemons(handles):
        for h in handles:
            if inproc:
                h.stop()
            else:
                h.stdin.close()
        if not inproc:
            for h in handles:
                h.wait(timeout=30)

    def run(n: int) -> dict:
        handles, addrs = spawn_daemons(n)
        try:
            with ModelFleet(addrs) as fleet:
                fleet.register("bench-fleet", "pca", arrays, version=1)
            endpoints = ",".join(f"{h}:{p}" for h, p in addrs)
            lat: list = []
            if inproc:
                from spark_rapids_ml_tpu.serve.fleet import (
                    ModelFleet as _Fleet,
                )

                fleet = _Fleet(addrs)
                fleet.table.install("bench-fleet", 1, "pca", {}, {})
                fleet.table.activate("bench-fleet", 1)
                q = rng.standard_normal((rows, d)).astype(np.float32)
                fcs = [fleet.client() for _ in range(clients)]
                for fc in fcs:
                    fc.transform("bench-fleet", q)
                lock = threading.Lock()
                barrier = threading.Barrier(clients + 1)

                def worker(fc):
                    mine = []
                    barrier.wait()
                    for _ in range(reqs):
                        t0 = time.perf_counter()
                        fc.transform("bench-fleet", q)
                        mine.append(time.perf_counter() - t0)
                    with lock:
                        lat.extend(mine)

                threads = [threading.Thread(target=worker, args=(fc,))
                           for fc in fcs]
                for t in threads:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                for fc in fcs:
                    fc.close()
                fleet.close()
            else:
                env = {
                    **os.environ,
                    "SRML_BENCH_FLEET_ENDPOINTS": endpoints,
                    "SRML_BENCH_FLEET_REQS": str(reqs),
                    "SRML_BENCH_FLEET_ROWS": str(rows),
                    "SRML_BENCH_FLEET_D": str(d),
                    "SRML_BENCH_FLEET_THREADS": str(threads_per),
                }
                if cpus_per > 0 and hasattr(os, "sched_setaffinity"):
                    # Clients live on the cores NO replica phase will
                    # pin (the top n_replicas*cpus_per are reserved),
                    # so client resources are identical at 1 and N
                    # replicas and never contend with replica cores.
                    cores = sorted(os.sched_getaffinity(0))
                    reserved = min(n_replicas * cpus_per, len(cores) - 1)
                    client_cores = cores[reserved:] or cores
                    env["SRML_BENCH_FLEET_CPUS"] = ",".join(
                        str(c) for c in client_cores
                    )
                workers = []
                for i in range(clients):
                    workers.append(subprocess.Popen(
                        [sys.executable, os.path.abspath(__file__),
                         "--fleet-client"],
                        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                        text=True,
                        env={**env, "SRML_BENCH_FLEET_SEED": str(i)},
                        cwd=os.path.dirname(os.path.abspath(__file__)),
                    ))
                for w in workers:
                    line = w.stdout.readline()
                    assert line.strip() == "READY", f"client said {line!r}"
                t0 = time.perf_counter()
                for w in workers:
                    w.stdin.write("GO\n")
                    w.stdin.flush()
                outs = [w.stdout.readline() for w in workers]
                wall = time.perf_counter() - t0
                for w, out in zip(workers, outs):
                    w.stdin.close()
                    w.wait(timeout=30)
                    lat.extend(json.loads(out)["latencies"])
            assert len(lat) == loops * reqs, (
                f"lost requests: {len(lat)} != {loops * reqs}"
            )
            lat.sort()
            return {
                "qps": round(loops * reqs / wall, 1),
                "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                "p99_ms": round(
                    lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3, 3
                ),
            }
        finally:
            stop_daemons(handles)

    trials = int(os.environ.get("SRML_BENCH_FLEET_TRIALS", 2))

    def best(n: int) -> dict:
        # Best-of-N trials: on a shared box the scheduler-noise floor is
        # large, and a throughput record should report what the stack
        # sustains, not what a noisy neighbor left of it.
        return max((run(n) for _ in range(max(trials, 1))),
                   key=lambda r: r["qps"])

    one = best(1)
    many = best(n_replicas)
    eff = round(many["qps"] / (n_replicas * one["qps"]), 4) if one["qps"] else 0.0
    record = {
        "metric": f"serve_fleet_transform_qps_d{d}_k{k}_c{clients}_b{rows}",
        "value": many["qps"],
        "unit": "transforms/s",
        "n_replicas": n_replicas,
        "clients": clients,
        "threads_per_client": 1 if inproc else threads_per,
        "cpus_per_replica": 0 if inproc else cpus_per,
        "batch_rows": rows,
        "dryrun": inproc,
        "backend": ",".join(sorted(backends)),
        "scaling_efficiency": eff,
        "replicas": {"1": one, str(n_replicas): many},
    }
    if not inproc:
        # The wire-fabric microphase (docstring): what the host's raw
        # loopback can carry at this workload's frame pattern, 1 vs N
        # process pairs. The FEASIBLE ideal on this host is
        # min(N x QPS_1, fabric capacity at N pairs) — a record whose
        # fabric cannot even carry N x QPS_1 is `wire_limited`: the
        # absolute efficiency gate is unmeasurable (the environment,
        # not the fleet, is the ceiling) and perfcheck gates the
        # fabric-relative efficiency QPS_N / feasible instead.
        wire = _wire_fabric_scaling(
            n_replicas, rows * d * 4, rows * k * 8
        )
        record["wire"] = wire
        ideal = n_replicas * one["qps"]
        feasible = min(ideal, wire["reqs_per_s_n"]) or 1.0
        record["wire_limited"] = wire["reqs_per_s_n"] < ideal
        record["fabric_relative_efficiency"] = round(
            many["qps"] / feasible, 4
        )
    print(json.dumps(record))


def _dispatch_mode() -> None:
    if "--fleet" in sys.argv or os.environ.get(
        "SRML_BENCH_FLEET", ""
    ) in ("1", "true"):
        fleet_bench()
    elif "--chaos-elastic" in sys.argv or os.environ.get(
        "SRML_BENCH_CHAOS_ELASTIC", ""
    ) in ("1", "true"):
        chaos_elastic_bench()
    elif "--chaos-grow" in sys.argv or os.environ.get(
        "SRML_BENCH_CHAOS_GROW", ""
    ) in ("1", "true"):
        chaos_grow_bench()
    elif "--chaos-partition" in sys.argv or os.environ.get(
        "SRML_BENCH_CHAOS_PARTITION", ""
    ) in ("1", "true"):
        chaos_partition_bench()
    elif "--serve" in sys.argv or os.environ.get("SRML_BENCH_SERVE", "") in (
        "1", "true"
    ):
        serve_bench()
    elif "--multichip" in sys.argv or os.environ.get(
        "SRML_BENCH_MULTICHIP", ""
    ) in ("1", "true"):
        multichip_bench()
    elif "--forest" in sys.argv or os.environ.get(
        "SRML_BENCH_FOREST", ""
    ) in ("1", "true"):
        forest_bench()
    elif "--kernels" in sys.argv or os.environ.get(
        "SRML_BENCH_KERNELS", ""
    ) in ("1", "true"):
        kernels_bench()
    else:
        main()


if __name__ == "__main__":
    if "--fleet-daemon" in sys.argv:
        _fleet_daemon_worker()
    elif "--fleet-client" in sys.argv:
        _fleet_client_worker()
    else:
        from spark_rapids_ml_tpu.utils.compile_cache import (
            ensure_compile_cache,
        )

        ensure_compile_cache()
        _dispatch_mode()
