"""KMeans Lloyd-iteration throughput — BASELINE.json config #3
(KMeans k=100 on 50M×256: pairwise-distance kernel + centroid allreduce).

Times the fused assign+update step (`models.kmeans._lloyd_fn`: distance
GEMM → argmin → one-hot update GEMM → psum) on device-resident data for a
fixed iteration count, reporting row-iterations/s/chip.

Baseline: the step is two k×d GEMMs ≈ 4·k·d flops/row·iter; an A100 at
~110 TFLOP/s sustained is ~1.07e9 row-iters/s. vs_baseline >= 0.5 matches
the north-star "within 2×".
"""

import os
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/bench_*.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 256))
K = int(os.environ.get("SRML_BENCH_K", 100))
ROWS = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 1 << 21))  # 2M × 256 f32 = 2.1 GB
ITERS = int(os.environ.get("SRML_BENCH_ITERS", 20))

A100_ROW_ITERS_PER_SEC = 110e12 / (4 * K * D)


def main() -> None:
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from benchmarks import emit
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.kmeans import _lloyd_fn
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")

    n_chips = len(jax.devices())
    mesh = make_mesh(model=1)
    x = jax.random.normal(jax.random.key(0), (ROWS, D), dtype=jnp.float32)
    if n_chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    mask = jnp.ones((ROWS,), dtype=jnp.float32)
    centers0 = jax.random.normal(jax.random.key(1), (K, D), dtype=jnp.float32)

    # tol=0 → exactly n iterations: a throughput measurement, not a
    # convergence race. Two iteration counts + slope_dt cancel the fixed
    # sync/dispatch overhead out of the reported rate.
    from benchmarks import slope_dt

    config.set("use_pallas", True)
    fns = {
        n: _lloyd_fn(
            mesh, K, n, 0.0, "bfloat16", "float32", use_pallas=True
        )
        for n in (ITERS, 2 * ITERS)
    }

    def run(n):
        centers, cost, n_iter = fns[n](x, mask, centers0)
        jax.block_until_ready(centers)
        assert int(n_iter) == n
        return centers

    # Median of 7 two-point slopes: a single slope can invert or halve (a
    # lone sample has produced physically impossible >HBM-bound rates).
    run(ITERS)
    run(2 * ITERS)
    lats = [slope_dt(run, ITERS, 2 * ITERS, warm=False) for _ in range(7)]
    dt_per_iter = float(np.median(lats))
    emit(
        f"kmeans_row_iters_per_sec_per_chip_d{D}_k{K}",
        ROWS / dt_per_iter / n_chips,
        "row_iters/s/chip",
        (ROWS / dt_per_iter / n_chips) / A100_ROW_ITERS_PER_SEC,
    )


if __name__ == "__main__":
    main()
