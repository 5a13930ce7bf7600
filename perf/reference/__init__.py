"""Plain references: float32 `jax.numpy` at `highest` matmul precision,
float64 on the host where sums are combined. They import nothing from
`spark_rapids_ml_tpu`; the harness compares the system's results with
theirs, never its timings."""
