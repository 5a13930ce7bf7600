"""End-to-end ingest: Arrow batches host→device, overlapped with compute.

SURVEY.md §7 hard-part (a) — the JVM↔TPU-host data plane. The headline
bench (bench.py) isolates compute by design; this one measures the full
feed path a Spark-fed fit actually exercises:

    pyarrow list column → bridge.arrow.table_column_to_matrix (zero-copy /
    native threaded cast) → jax.device_put (row-sharded) → streaming Gram
    fold (donated accumulator)

reporting sustained rows/s for (1) the bridge alone (host-side), (2) the
full ingest+compute pipeline, and comparing against (3) the compute-only
rate on device-resident data. The pipeline overlaps naturally: device_put
and the fold dispatch async while the host converts the next batch; a
>30% gap between (2) and min(1, 3) would indicate a serialization stall.

Baseline: an A100's effective H2D is ~20 GB/s (PCIe4 x16 measured); at
d=512 f32 that is ~9.8M rows/s. vs_baseline compares the full-pipeline
rate against that.
"""

import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 512))
BATCH_ROWS = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 1 << 17))  # 128k
N_BATCHES = int(os.environ.get("SRML_BENCH_BATCHES", 8))

A100_H2D_ROWS_PER_SEC = 20e9 / (D * 4)


def main() -> None:
    from benchmarks import emit
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import pyarrow as pa

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.bridge.arrow import (
        matrix_to_list_column,
        table_column_to_matrix,
    )
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")

    mesh = make_mesh(model=1)
    x_sh = row_sharding(mesh)
    m_sh = row_sharding(mesh, ndim=1)

    # Host-side Arrow batches (f32, fixed_size_list — what a configured
    # Spark Arrow exporter ships). Built once; the bench loops over them.
    rng = np.random.default_rng(0)
    host = rng.standard_normal((BATCH_ROWS, D), dtype=np.float32)
    tables = [
        pa.table({"features": matrix_to_list_column(host)}) for _ in range(2)
    ]  # two distinct buffers so no cache effects collapse the loop
    mask = np.ones((BATCH_ROWS,), np.float32)

    update = gram_ops.streaming_update(mesh)
    state = gram_ops.init_stats(D)

    # Warm: compile the fold once.
    xs = jax.device_put(host, x_sh)
    ms = jax.device_put(mask, m_sh)
    state = update(state, xs, ms)
    jax.block_until_ready(state)

    # (1) bridge-only host rate (arrow -> contiguous matrix).
    t0 = time.perf_counter()
    for i in range(N_BATCHES):
        mat = table_column_to_matrix(tables[i % 2], "features")
    bridge_dt = (time.perf_counter() - t0) / N_BATCHES
    assert mat.shape == (BATCH_ROWS, D)

    # (3) compute-only rate on device-resident data (same fold).
    t0 = time.perf_counter()
    for _ in range(N_BATCHES):
        state = update(state, xs, ms)
    jax.block_until_ready(state)
    compute_dt = (time.perf_counter() - t0) / N_BATCHES

    # (2) full pipeline: convert + device_put + fold, loop overlapped
    # (no per-batch sync — dispatch runs ahead while the host converts).
    t0 = time.perf_counter()
    for i in range(N_BATCHES):
        mat = table_column_to_matrix(tables[i % 2], "features")
        xb = jax.device_put(mat, x_sh)
        state = update(state, xb, ms)
    jax.block_until_ready(state)
    pipe_dt = (time.perf_counter() - t0) / N_BATCHES

    pipeline_rate = BATCH_ROWS / pipe_dt
    emit(
        f"ingest_pipeline_rows_per_sec_d{D}",
        pipeline_rate,
        "rows/s",
        pipeline_rate / A100_H2D_ROWS_PER_SEC,
        bridge_rows_per_sec=round(BATCH_ROWS / bridge_dt, 1),
        compute_rows_per_sec=round(BATCH_ROWS / compute_dt, 1),
    )


if __name__ == "__main__":
    main()
