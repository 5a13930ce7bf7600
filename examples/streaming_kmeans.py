"""Out-of-HBM KMeans: one host scan per Lloyd iteration.

The batch source is any callable returning a fresh iterator per call —
here a generator over synthetic shards; in production, an Arrow/Parquet
reader. Centers checkpoint each iteration; rerunning after an
interruption resumes at the saved iteration.
"""

import os
import sys

if __package__ in (None, ""):  # runnable without installation
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_ml_tpu.models.kmeans import fit_kmeans_stream
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

ensure_compile_cache()  # one rule for where compiled programs are kept

rng = np.random.default_rng(0)
true_centers = rng.normal(size=(16, 128)) * 8


def batches():
    for i in range(20):  # 20 batches x 50k rows = 1M rows per scan
        yield (true_centers[rng.integers(0, 16, 50_000)]
               + rng.normal(size=(50_000, 128))).astype(np.float32)


sol = fit_kmeans_stream(
    batches, k=16, n_cols=128, max_iter=10, seed=0,
    checkpoint_path="/tmp/kmeans.ckpt",
)
print(f"{sol.n_iter} iterations over {sol.n_rows} rows; cost {sol.cost:.3e}")
