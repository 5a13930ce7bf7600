"""LogisticRegression Newton-step throughput — BASELINE.json config #4
(normal-equations-class Gram psum, IRLS flavor).

Times the binomial Newton fit (`_newton_fn`: per-iteration predict +
weighted Gram Hessian + psum + d×d solve) for a fixed iteration count on
device-resident data, reporting row-iterations/s/chip.

Baseline: each Newton iteration is Hessian-Gram-bound at ~2·d² flops/row;
A100 at ~110 TFLOP/s → 110e12/(2·1024²) ≈ 52.5e6 row-iters/s.
vs_baseline >= 0.5 matches the north-star "within 2×".
"""

import os
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/bench_*.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 1024))
ROWS = int(os.environ.get("SRML_BENCH_BATCH_ROWS", 1 << 19))
ITERS = int(os.environ.get("SRML_BENCH_ITERS", 8))

A100_ROW_ITERS_PER_SEC = 110e12 / (2 * D * D)


def main() -> None:
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from benchmarks import emit
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.logistic_regression import _newton_fn
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")
    config.set("use_pallas", True)  # fused single-HBM-pass Newton step

    n_chips = len(jax.devices())
    mesh = make_mesh(model=1)
    key = jax.random.key(0)
    x = jax.random.normal(key, (ROWS, D), dtype=jnp.float32)
    w_true = jax.random.normal(jax.random.key(1), (D,), dtype=jnp.float32) / np.sqrt(D)
    y = (jax.nn.sigmoid(x @ w_true) > 0.5).astype(jnp.float64)
    if n_chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        y = jax.device_put(y, NamedSharding(mesh, P("data")))
    mask = jnp.ones((ROWS,), dtype=jnp.float32)

    # tol=0 → exactly n Newton steps: throughput, not convergence. Two
    # iteration counts + slope_dt cancel the fixed sync overhead.
    from benchmarks import slope_dt

    fns = {
        n: _newton_fn(mesh, 1e-4, True, n, 0.0, "float32")
        for n in (ITERS, 2 * ITERS)
    }

    def run(n):
        w, b, n_iter, loss = fns[n](x, y, mask)
        jax.block_until_ready(w)
        assert int(n_iter) == n and np.isfinite(float(loss))
        return w

    dt_per_iter = slope_dt(run, ITERS, 2 * ITERS)

    # -- multinomial MM-Newton pass (streamed-protocol kernel) -------------
    # Per pass: gradient GEMM + C per-class weighted Grams ≈ 2·C·n·d²
    # flops; the same A100 sustained-GEMM convention gives the baseline.
    from spark_rapids_ml_tpu.models.logistic_regression import (
        _stream_multinomial_step_fn,
        _stream_softmax_stats_fn,
        stream_softmax_zero_state,
    )

    C = int(os.environ.get("SRML_BENCH_CLASSES", 32))
    rows_mm = int(os.environ.get("SRML_BENCH_MM_ROWS", ROWS // 4))
    x_mm = jax.random.normal(jax.random.key(2), (rows_mm, D), dtype=jnp.float32)
    y_mm = jax.random.randint(jax.random.key(3), (rows_mm,), 0, C).astype(
        jnp.float32
    )
    mask_mm = jnp.ones((rows_mm,), jnp.float32)
    if n_chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        x_mm = jax.device_put(x_mm, NamedSharding(mesh, P("data", None)))
        y_mm = jax.device_put(y_mm, NamedSharding(mesh, P("data")))
        mask_mm = jax.device_put(mask_mm, NamedSharding(mesh, P("data")))
    mm_step = _stream_multinomial_step_fn(1e-4, True, "float32")

    def mm_timer(update):
        def run_mm(n):
            W = jnp.zeros((D, C), jnp.float32)
            b = jnp.zeros((C,), jnp.float32)
            for _ in range(n):
                state = stream_softmax_zero_state(D, C, jnp.float32)
                gw, gb, hw, hwb, hbb, _, nn = update(
                    state, W, b, x_mm, y_mm, mask_mm
                )
                W, b, _ = mm_step(gw, gb, hw, hwb, hbb, nn, W, b)
            jax.block_until_ready(W)
            return W

        mm_iters = max(2, ITERS // 2)
        return slope_dt(run_mm, mm_iters, 2 * mm_iters)

    # Same-run A/B: the shared-tile Pallas curvature kernel (use_pallas
    # snapshot True — the shipped TPU profile) vs the XLA per-class loop.
    from spark_rapids_ml_tpu.models.logistic_regression import (
        _stream_softmax_stats_cached,
    )

    dt_mm = mm_timer(_stream_softmax_stats_fn(mesh, C, "float32"))
    dt_mm_xla = mm_timer(
        _stream_softmax_stats_cached(mesh, C, "float32", "bfloat16", False)
    )
    a100_mm = 110e12 / (2 * C * D * D)
    emit(
        f"logreg_newton_row_iters_per_sec_per_chip_d{D}",
        ROWS / dt_per_iter / n_chips,
        "row_iters/s/chip",
        (ROWS / dt_per_iter / n_chips) / A100_ROW_ITERS_PER_SEC,
    )
    # Its own line (VERDICT r3 #8): the multinomial MM-Newton pass is a
    # peer workload, not a footnote on the binary number.
    emit(
        f"logreg_multinomial_row_iters_per_sec_per_chip_d{D}_C{C}",
        rows_mm / dt_mm / n_chips,
        "row_iters/s/chip",
        (rows_mm / dt_mm / n_chips) / a100_mm,
        classes=C,
        ab_xla_row_iters_per_sec=round(rows_mm / dt_mm_xla / n_chips, 1),
        kernel_speedup=round(dt_mm_xla / dt_mm, 4),
    )


if __name__ == "__main__":
    main()
