"""Within-run IVF query stage profile (dev-chip drift-proof).

Cross-run comparisons on the shared dev chip are invalid (documented
within-session speed decay: an unchanged control fell 127→109k q/s in an
hour), so this profiler interleaves ALL stages' measurements in one
process — cycle 1 measures probe/bucket/scan/full back-to-back, then
cycle 2, ... — and reports per-stage medians. Stage cuts are the
``_debug_stage`` hooks in models/knn.py: each cut keeps everything up to
that point live (data-dependent outputs, no DCE) and drops the rest.

Run: python benchmarks/profile_ivf_stages.py   (same env knobs as
bench_knn). Prints one JSON line with per-stage ms and deltas.
"""

import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 768))
N_BASE = int(os.environ.get("SRML_BENCH_BASE_ROWS", 1 << 20))
N_QUERY = int(os.environ.get("SRML_BENCH_QUERIES", 4096))
K = int(os.environ.get("SRML_BENCH_K", 10))
NLIST = int(os.environ.get("SRML_BENCH_NLIST", 1024))
NPROBE = int(os.environ.get("SRML_BENCH_NPROBE", 32))
NCLUST = int(os.environ.get("SRML_BENCH_CLUSTERS", 4096))
REPS = int(os.environ.get("SRML_BENCH_REPS", 8))
CYCLES = int(os.environ.get("SRML_BENCH_CYCLES", 5))


def main() -> None:
    from benchmarks import slope_dt
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.knn import (
        _ivf_query_fn,
        _residual_index_data,
        build_ivf_flat_device,
    )

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")
    config.set("use_pallas", True)

    cc = jax.random.normal(jax.random.key(7), (NCLUST, D), jnp.float32)
    assign = jax.random.randint(jax.random.key(8), (N_BASE,), 0, NCLUST)
    base = cc[assign] + 0.35 * jax.random.normal(
        jax.random.key(9), (N_BASE, D), jnp.float32
    )
    qassign = jax.random.randint(jax.random.key(10), (N_QUERY,), 0, NCLUST)
    queries = cc[qassign] + 0.35 * jax.random.normal(
        jax.random.key(11), (N_QUERY, D), jnp.float32
    )
    index = build_ivf_flat_device(base, nlist=NLIST, seed=0)
    del base
    dev = [
        jnp.asarray(index.centroids, dtype=jnp.float32),
        jnp.asarray(index.lists, dtype=jnp.float32),
        jnp.asarray(index.list_ids),
        jnp.asarray(index.list_mask),
    ]
    norms, lists_lo = _residual_index_data(dev[1], dev[0], jnp.bfloat16)

    stages = [
        ("dispatch", dict(rerank=False, _debug_stage="dispatch")),
        ("probe", dict(rerank=False, _debug_stage="probe")),
        ("bucket", dict(rerank=False, _debug_stage="bucket")),
        ("scan_nosel", dict(rerank=False, _debug_stage="scan_nosel")),
        ("scan", dict(rerank=False, _debug_stage="scan")),
        ("full_norerank", dict(rerank=False)),
        ("rerank_norescore", dict(rerank=True, _debug_stage="rerank_norescore")),
        ("full_rerank", dict(rerank=True)),
    ]
    fns = {
        name: _ivf_query_fn(K, NPROBE, "bfloat16", "float32", **kw)
        for name, kw in stages
    }

    def make_run(fn):
        def run(n):
            out = None
            for _ in range(n):
                _, out = fn(*dev, queries, resid_norms=norms, lists_lo=lists_lo)
            jax.block_until_ready(out)
            return out
        return run

    runs = {name: make_run(fn) for name, fn in fns.items()}
    for r in runs.values():  # compile + warm both sizes, outside samples
        r(REPS)
        r(3 * REPS)
    samples = {name: [] for name, _ in stages}
    for _ in range(CYCLES):  # interleave so drift hits all stages alike
        for name, _ in stages:
            samples[name].append(
                slope_dt(runs[name], REPS, 3 * REPS, warm=False) * 1e3
            )
    med = {name: float(np.median(v)) for name, v in samples.items()}
    out = {
        "metric": "ivf_stage_profile_ms_per_call",
        **{f"{n}_ms": round(v, 3) for n, v in med.items()},
        "probe_minus_dispatch_ms": round(med["probe"] - med["dispatch"], 3),
        "bucket_minus_probe_ms": round(med["bucket"] - med["probe"], 3),
        "scan_nosel_minus_bucket_ms": round(med["scan_nosel"] - med["bucket"], 3),
        "scan_minus_bucket_ms": round(med["scan"] - med["bucket"], 3),
        "sel_in_scan_ms": round(med["scan"] - med["scan_nosel"], 3),
        "select_minus_scan_ms": round(med["full_norerank"] - med["scan"], 3),
        "rerank_extra_ms": round(med["full_rerank"] - med["full_norerank"], 3),
        "rescore_in_graph_ms": round(
            med["full_rerank"] - med["rerank_norescore"], 3
        ),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
