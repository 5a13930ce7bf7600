"""PCA.transform p50 latency — the second BASELINE.json headline metric.

The reference's transform re-uploads the PC matrix host→device on every
batch (rapidsml_jni.cu:85 — flagged in SURVEY.md §3.2 as the optimization
target); here the PC matrix is device-resident across batches and the
per-batch work is one (batch, d) × (d, k) MXU GEMM.

Baseline: an A100 cuML batch transform at 65536×2048 × 2048×32 is ~8.6
GFLOP ≈ 0.08 ms of GEMM plus per-batch PC upload (~0.25 ms for 0.5 MB
over PCIe effective ~2 GB/s with launch overhead) ≈ 0.35 ms. vs_baseline =
baseline_p50 / our_p50 (higher is better, >1 beats the A100 path).

Measurement notes (so the number stays comparable across rounds): the
measured path is this framework's quantize-on-ingest design — bf16 inputs,
f32 accumulation — against the reference's f32 path; the dtype is in the
metric name. The p50 is the per-batch *device* latency via slope_dt, which
subtracts the fixed per-measurement host round-trip; the A100 baseline's
per-batch PC upload is kept in the baseline because eliminating it
(device-resident PC) is a real architectural difference.
"""

import os
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/bench_*.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

BASELINE_P50_MS = 0.35

D = int(os.environ.get("SRML_BENCH_D", 2048))
K = int(os.environ.get("SRML_BENCH_K", 32))
BATCH = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 65536))
CALLS = int(os.environ.get("SRML_BENCH_CALLS", 200))


def main() -> None:
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from benchmarks import emit

    rng = np.random.default_rng(0)
    # Ingest-cast to bfloat16 (the framework's quantize-on-ingest design):
    # the batch GEMM is HBM-bound at these shapes, so halving the bytes
    # halves the latency; accumulation stays float32.
    pc = jnp.asarray(rng.normal(size=(D, K)), dtype=jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(BATCH, D)), dtype=jnp.bfloat16)

    @jax.jit
    def transform(pc, x):
        return jax.lax.dot_general(
            x, pc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @jax.jit
    def transform_bf16_out(pc, x):
        # bf16 output writes (f32 accumulation unchanged): halves the
        # store bytes. At this shape the op is LOAD-bound (k ≪ d: the
        # (batch, d) bf16 read is ~268 MB vs an 8.4 MB f32 store), so
        # the roofline gain is ~1.5% — measured to close VERDICT r3 #7.
        return jax.lax.dot_general(
            x, pc, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(jnp.bfloat16)

    # Per-batch device latency via the two-point slope: chained batches in
    # one sync window, so the fixed host round-trip cancels out of the p50.
    from benchmarks import slope_dt

    def make_run(fn):
        def run(n):
            out = None
            for _ in range(n):
                out = fn(pc, x)
            jax.block_until_ready(out)
            return out
        return run

    run, run_bf16 = make_run(transform), make_run(transform_bf16_out)
    for r in (run, run_bf16):  # warm / compile both sizes, outside samples
        r(CALLS)
        r(2 * CALLS)
    # Interleave the two arms (same-run A/B: chip drift discipline).
    lat, lat_bf16 = [], []
    for _ in range(9):
        lat.append(slope_dt(run, CALLS, 2 * CALLS, warm=False) * 1e3)
        lat_bf16.append(slope_dt(run_bf16, CALLS, 2 * CALLS, warm=False) * 1e3)
    p50 = float(np.percentile(lat, 50))
    p50_bf16 = float(np.percentile(lat_bf16, 50))
    # HBM roofline at this shape (v5e 819 GB/s): read x (batch·d·2B) +
    # pc, write out (batch·k·4B or ·2B).
    bytes_f32 = BATCH * D * 2 + D * K * 2 + BATCH * K * 4
    bytes_bf16 = BATCH * D * 2 + D * K * 2 + BATCH * K * 2
    daemon_extras = _daemon_serving_p50(rng)
    emit(
        f"pca_transform_p50_ms_batch{BATCH}_d{D}_k{K}_bf16",
        p50,
        "ms",
        BASELINE_P50_MS / p50,
        bf16_out_p50_ms=round(p50_bf16, 4),
        roofline_ms=round(bytes_f32 / 819e9 * 1e3, 4),
        roofline_bf16_out_ms=round(bytes_bf16 / 819e9 * 1e3, 4),
        hbm_efficiency=round(bytes_f32 / 819e9 * 1e3 / p50, 4),
        **daemon_extras,
    )


def _daemon_serving_p50(rng) -> dict:
    """End-to-end daemon ``transform`` round-trip p50 (Arrow IPC over
    loopback TCP + host→device + GEMM + device→host) — the path Spark
    executors actually take (VERDICT r2 #1 asked for this number next to
    the device-only p50).

    Measured at a smaller batch than the device-only metric
    (``SRML_BENCH_DAEMON_ROWS``).
    """
    import time

    from spark_rapids_ml_tpu.models.pca import PCAModel
    from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon

    d_rows = int(os.environ.get("SRML_BENCH_DAEMON_ROWS", 4096))
    model = PCAModel(
        pc=rng.normal(size=(D, K)), mean=np.zeros(D),
        explained_variance=np.ones(K) / K,
    )
    xb = rng.normal(size=(d_rows, D)).astype(np.float32)
    with DataPlaneDaemon() as daemon:
        with DataPlaneClient(*daemon.address) as c:
            c.ensure_model("bench-pca", "pca", model._model_data())
            c.transform("bench-pca", xb)  # warm: compile + device residency
            lats = []
            for _ in range(9):
                t0 = time.perf_counter()
                c.transform("bench-pca", xb)
                lats.append((time.perf_counter() - t0) * 1e3)
    p50 = float(np.percentile(lats, 50))
    return {"daemon_p50_ms": round(p50, 3), "daemon_batch_rows": d_rows}


if __name__ == "__main__":
    main()
