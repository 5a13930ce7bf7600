"""Per-config benchmark suite for the BASELINE.json workloads.

``bench.py`` at the repo root is the recorded headline (PCA.fit streaming
throughput); the scripts here cover the remaining BASELINE.json configs —
PCA transform latency, KMeans, LinearRegression/LogisticRegression normal
equations, and IVF-Flat approximate KNN. Each prints ONE JSON line
``{"metric", "value", "unit", "vs_baseline"}``; shapes are scaled to a
single chip's HBM (the multi-chip story is sharding-tested in tests/ and
dry-run-compiled via __graft_entry__.dryrun_multichip) and every script has
``SRML_BENCH_*`` env knobs for smoke-testing on small hosts.

``vs_baseline`` denominators are analytic A100 estimates (GEMM-bound at
~110 TFLOP/s sustained TF32, the same convention as bench.py's module
docstring) — the reference repo publishes no numbers (BASELINE.md).
"""

import json


def emit(metric: str, value: float, unit: str, vs_baseline: float, **extras) -> None:
    """Print the one-JSON-line bench contract; ``extras`` appends further
    keys (recall, component rates, flags) to the same line."""
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 4),
                "unit": unit,
                "vs_baseline": round(vs_baseline, 4),
                **extras,
            }
        )
    )


def slope_dt(run, n1: int, n2: int, warm: bool = True) -> float:
    """Seconds per work-unit via a two-point fit: time run(n1) and run(n2),
    return (t2-t1)/(n2-n1).

    Removes fixed per-measurement overhead (dispatch, the final
    host↔device sync) from the reported rate. ``run(n)`` must execute n
    units and block until the device is done. Each size is timed
    twice and the min taken, so a single noisy sample can't invert the
    slope; pass warm=False when the caller has already compiled/warmed both
    sizes (e.g. repeated sampling in a loop).
    """
    import time

    if warm:
        run(n1)  # warm / compile both sizes
        run(n2)

    def timed(n):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            run(n)
            best = min(best, time.perf_counter() - t0)
        return best

    t1, t2 = timed(n1), timed(n2)
    if t2 <= t1:  # still inverted after min-of-2: fall back to the average
        return t2 / n2
    return (t2 - t1) / (n2 - n1)
