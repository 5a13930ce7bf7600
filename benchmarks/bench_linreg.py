"""LinearRegression normal-equations throughput — BASELINE.json config #4
(Gram-matrix psum; the Criteo-scale shape class, d ≈ 1k dense).

Times the moment-accumulation hot loop (`_normal_eq_stats_fn`: fused
XᵀX / Xᵀy / Σx / Σy / Σy² with psum) on device-resident data — the same
partition-Gram pattern as PCA (SURVEY.md §7.6: "literally the PCA
reduction with an extra Xᵀy psum"). The d×d solve is a fixed cost
amortized over the dataset and excluded (measured in tests).

Baseline: Gram is 2·d² flops/row; A100 at ~110 TFLOP/s → 110e12/(2·1024²)
≈ 52.5e6 rows/s. vs_baseline >= 0.5 matches the north-star "within 2×".

Batches are device-resident bfloat16 (same convention as bench.py's
streaming PCA headline: a production ingest path device_puts the compute
dtype, and an f32-resident batch re-reads 2× the bytes every pass —
measured 20.9 → 14.8 ms/batch at 1M×1024). The fused one-HBM-pass Pallas
stats kernel is on (config use_pallas, linreg_stats_pallas); set
SRML_BENCH_AB_PALLAS=1 to emit a same-run XLA-path arm first.
"""

import os
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/bench_*.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 1024))
ROWS = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 1 << 19))  # 524288×1024 = 2.1 GB
REPS = int(os.environ.get("SRML_BENCH_REPS", 16))

A100_ROWS_PER_SEC = 110e12 / (2 * D * D)


def main() -> None:
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from benchmarks import emit
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.linear_regression import _normal_eq_stats_fn
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")
    config.set("use_pallas", True)

    n_chips = len(jax.devices())
    mesh = make_mesh(model=1)
    x = jax.random.normal(jax.random.key(0), (ROWS, D), dtype=jnp.bfloat16)
    y = jax.random.normal(jax.random.key(1), (ROWS,), dtype=jnp.float32)
    if n_chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        y = jax.device_put(y, NamedSharding(mesh, P("data")))
    mask = jnp.ones((ROWS,), dtype=jnp.float32)

    from benchmarks import slope_dt

    def measure(use_pallas: bool) -> float:
        stats = _normal_eq_stats_fn(mesh, "bfloat16", "float32", use_pallas)

        def run(n):
            out = None
            for _ in range(n):
                out = stats(x, y, mask)
            jax.block_until_ready(out)  # one sync; calls queue on device
            assert np.isfinite(float(out[5]))
            return out

        run(REPS); run(2 * REPS)
        dts = [slope_dt(run, REPS, 2 * REPS, warm=False) for _ in range(5)]
        return float(np.median(dts))

    if os.environ.get("SRML_BENCH_AB_PALLAS"):
        dt0 = measure(False)
        emit(
            f"linreg_ab_xla_rows_per_sec_per_chip_d{D}",
            ROWS / dt0 / n_chips, "rows/s/chip",
            (ROWS / dt0 / n_chips) / A100_ROWS_PER_SEC,
        )
    dt = measure(True)
    emit(
        f"linreg_normal_eq_rows_per_sec_per_chip_d{D}",
        ROWS / dt / n_chips,
        "rows/s/chip",
        (ROWS / dt / n_chips) / A100_ROWS_PER_SEC,
    )


if __name__ == "__main__":
    main()
