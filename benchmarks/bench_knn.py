"""IVF-Flat approximate-KNN query throughput — BASELINE.json config #5
(10M×768 SBERT-class embeddings; scaled to one chip's HBM here).

Data is CLUSTERED (a 4096-component gaussian mixture, within-cluster
spread 0.35) — the embedding-like regime IVF exists for; isotropic random
data has no inverted-list structure and makes recall meaningless. The
index build uses the capacity-balanced quantizer (balanced-Lloyd
refinement + next-nearest spill, models/knn.py) which bounds the padded
layout's maxlen AND is what keeps recall high on clustered data.

Recall@10 is measured against exact chunked brute-force ground truth and
reported in the SAME JSON line; the query path runs with
``ann_rerank=off`` (residual-identity scores answer directly — measured
~1.8× q/s for ~0.015 recall on this workload, still ≥ 0.95).

Baseline: an A100 IVF-Flat at this recall point sustains ~2e5 q/s
(RAFT-class, bandwidth-limited — rough published ballpark; the reference
repo itself publishes nothing, BASELINE.md).
"""

import os
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/bench_*.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 768))
N_BASE = int(os.environ.get("SRML_BENCH_BASE_ROWS", 1 << 20))  # 1M×768 = 3.2 GB
N_QUERY = int(os.environ.get("SRML_BENCH_QUERIES", 4096))
K = int(os.environ.get("SRML_BENCH_K", 10))
NLIST = int(os.environ.get("SRML_BENCH_NLIST", 1024))
# nprobe 20 / slack 1.4: the round-3 measured frontier point — with the
# fused kernel's EXACT per-slot selection, probe count (not selection
# loss) sets recall, and the same-run sweep showed recall@10 *rising* as
# nprobe fell (smaller final-merge pool -> less PartialReduce loss) while
# q/s plateaued below nprobe 20 (other stages dominate). 32/1.5 was the
# approx-selection round-2 point; both sweeps are in benchmarks/README.md.
NPROBE = int(os.environ.get("SRML_BENCH_NPROBE", 20))
NCLUST = int(os.environ.get("SRML_BENCH_CLUSTERS", 4096))
SLACK = float(os.environ.get("SRML_BENCH_SLACK", 1.4))

A100_QUERIES_PER_SEC = 2e5


def main() -> None:
    from benchmarks import emit
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.knn import (
        _ivf_query_fn,
        _residual_index_data,
        build_ivf_flat_device,
        sq_euclidean,
    )

    config.set("compute_dtype", "bfloat16")
    config.set("accum_dtype", "float32")
    config.set("use_pallas", True)  # fused Lloyd step for the coarse quantizer
    config.set("ann_rerank", False)  # see module docstring

    n_chips = len(jax.devices())
    # Clustered base + queries generated on device (the host CPU is far too
    # slow for 1M×768 draws).
    cc = jax.random.normal(jax.random.key(7), (NCLUST, D), jnp.float32)
    assign = jax.random.randint(jax.random.key(8), (N_BASE,), 0, NCLUST)
    base = cc[assign] + 0.35 * jax.random.normal(
        jax.random.key(9), (N_BASE, D), jnp.float32
    )
    qassign = jax.random.randint(jax.random.key(10), (N_QUERY,), 0, NCLUST)
    queries = cc[qassign] + 0.35 * jax.random.normal(
        jax.random.key(11), (N_QUERY, D), jnp.float32
    )

    # Exact ground truth: chunked brute force (f32 accumulation).
    @jax.jit
    def gt_chunk(qc, bchunk, lo):
        d2 = sq_euclidean(qc, bchunk, accum_dtype=jnp.float32)
        neg, pos = jax.lax.top_k(-d2, K)
        return -neg, pos + lo

    bs = -(-N_BASE // 8)  # ceil: the last chunk may be short, no tail drop
    best_d = np.full((N_QUERY, K), np.inf, np.float32)
    best_i = np.full((N_QUERY, K), -1, np.int64)
    for lo in range(0, N_BASE, bs):
        bchunk = jax.lax.slice_in_dim(base, lo, min(lo + bs, N_BASE))
        dd, ii = gt_chunk(queries, bchunk, lo)
        cat_d = np.concatenate([best_d, np.asarray(dd)], axis=1)
        cat_i = np.concatenate([best_i, np.asarray(ii)], axis=1)
        sel = np.argsort(cat_d, axis=1)[:, :K]
        best_d = np.take_along_axis(cat_d, sel, axis=1)
        best_i = np.take_along_axis(cat_i, sel, axis=1)
    gt = best_i

    index = build_ivf_flat_device(base, nlist=NLIST, seed=0)
    del base  # free 3 GB of HBM — the index alone serves the queries
    dev = [
        jnp.asarray(index.centroids, dtype=jnp.float32),
        jnp.asarray(index.lists, dtype=jnp.float32),
        jnp.asarray(index.list_ids),
        jnp.asarray(index.list_mask),
    ]
    from benchmarks import slope_dt

    # Residual norms + the bf16 residual scan copy are index data:
    # precompute once like a serving deployment would (the model path
    # caches them on device via _ensure_dev_index).
    norms, lists_lo = _residual_index_data(dev[1], dev[0], jnp.bfloat16)
    reps = int(os.environ.get("SRML_BENCH_REPS", 8))

    def measure(rerank: bool, slack: float = SLACK, nprobe: int = NPROBE,
                rerank_width: int = 0, extract: str = "auto"):
        """(q/s, recall@10) at one operating point — BOTH points are
        emitted every run (r2 review: the default config ships
        rerank=on, the headline ran rerank=off; report both always)."""
        query = _ivf_query_fn(
            K, nprobe, "bfloat16", "float32", rerank=rerank, slack=slack,
            fused=str(config.get("ann_fused_scan")),
            rerank_width=rerank_width, extract=extract,
        )
        ids0 = np.asarray(
            query(*dev, queries, resid_norms=norms, lists_lo=lists_lo)[1]
        )
        recall = float(
            np.mean([len(set(ids0[i]) & set(gt[i])) / K for i in range(N_QUERY)])
        )

        # Host-driven rep loop, one jitted call per batch: successive
        # independent batches PIPELINE across the query's probe/scan/
        # select stages on device, which is exactly how a serving host
        # issues them (a lax.scan rep loop serializes the stages and
        # measured ~35% lower — an under-estimate of serving throughput,
        # recorded in benchmarks/README.md). Per-call dispatch overhead
        # pushes the other way; the slope over reps removes its fixed
        # component.
        def run(n):
            ids = None
            for _ in range(n):
                _, ids = query(
                    *dev, queries, resid_norms=norms, lists_lo=lists_lo
                )
            jax.block_until_ready(ids)  # one sync; calls queue on device
            return ids

        # MEDIAN of 5 slopes: single slopes on the shared dev chip have
        # produced 2× outliers in both directions (same discipline as
        # bench_kmeans; the r2 review flagged single-sample spreads).
        run(reps)
        run(3 * reps)
        lats = [slope_dt(run, reps, 3 * reps, warm=False) for _ in range(5)]
        dt = float(np.median(lats))
        return N_QUERY / dt / n_chips, recall

    ab = os.environ.get("SRML_BENCH_AB_FUSED")
    if ab:
        # Same-run interleaved A/B arms (within-session chip drift
        # forbids cross-run comparison — benchmarks/README.md): one extra
        # JSON line per arm, then the normal headline (auto = fused).
        # SRML_BENCH_AB_FUSED=1 → the fused-off/on pair; or a
        # semicolon-separated list of arm specs, e.g.
        # "fused=off;fused=on;fused=on,slack=1.25,nprobe=28".
        specs = (
            ["fused=off", "fused=on"]
            if ab == "1"
            else [a for a in ab.split(";") if a]
        )
        for spec in specs:
            kv = dict(p.split("=") for p in spec.split(","))
            config.set("ann_fused_scan", kv.get("fused", "auto"))
            qps, rec = measure(
                rerank=kv.get("rerank", "off") == "on",
                slack=float(kv.get("slack", SLACK)),
                nprobe=int(kv.get("nprobe", NPROBE)),
                rerank_width=int(kv.get("rw", 0)),
                extract=kv.get("extract", "auto"),
            )
            emit(
                "ivfflat_ab_" + spec.replace("=", "").replace(",", "_"),
                qps, "queries/s/chip",
                qps / A100_QUERIES_PER_SEC, recall_at_10=round(rec, 4),
            )
        config.set("ann_fused_scan", "auto")

    qps_off, recall_off = measure(rerank=False)
    qps_on, recall_on = measure(rerank=True)
    # Third point: rerank with NARROW kernel extraction (config
    # ann_extract) — the round-4 speed/recall dial between the two.
    qps_nar, recall_nar = measure(rerank=True, extract="narrow")
    emit(
        f"ivfflat_queries_per_sec_per_chip_n{N_BASE}_d{D}"
        f"_k{K}_nprobe{NPROBE}_clustered",
        qps_off,
        "queries/s/chip",
        qps_off / A100_QUERIES_PER_SEC,
        recall_at_10=round(recall_off, 4),
        rerank_on_qps=round(qps_on, 1),
        rerank_on_recall=round(recall_on, 4),
        rerank_on_vs_baseline=round(qps_on / A100_QUERIES_PER_SEC, 4),
        rerank_narrow_qps=round(qps_nar, 1),
        rerank_narrow_recall=round(recall_nar, 4),
    )


if __name__ == "__main__":
    main()
