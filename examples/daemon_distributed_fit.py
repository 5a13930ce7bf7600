"""Executor-fed distributed fit through the TPU-host data-plane daemon.

Emulates N Spark tasks (threads here; real tasks connect over the
network) streaming Arrow partitions with the EXACTLY-ONCE commit
protocol: feeds stage per (partition, attempt) and only ``commit`` folds
them in, so task retries and speculative duplicates cannot double-count
(the semantics the Spark wrappers rely on — spark/estimator.py drives
this protocol automatically for `SparkPCA().fit(df)` etc.). The driver
finalizes and receives only the model. Iterative algorithms use the same
wire protocol with one scan per iteration and a step() call at each pass
boundary.
"""

import os
import sys

if __package__ in (None, ""):  # runnable without installation
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import threading

import numpy as np

from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

ensure_compile_cache()  # one rule for where compiled programs are kept

rng = np.random.default_rng(0)
data = (rng.normal(size=(200_000, 128)) * np.logspace(0, -1.5, 128)).astype(np.float32)
parts = np.array_split(data, 8)

with DataPlaneDaemon(ttl=600.0) as daemon:  # idle jobs evicted after 10 min
    host, port = daemon.address

    def task(pid, part):
        with DataPlaneClient(host, port) as c:
            for sub in np.array_split(part, 2):  # several batches per task
                c.feed("demo", sub, algo="pca", partition=pid)
            c.commit("demo", partition=pid)  # the only point rows count

    threads = [
        threading.Thread(target=task, args=(i, p)) for i, p in enumerate(parts)
    ]
    [t.start() for t in threads]
    [t.join() for t in threads]

    # A retried duplicate of partition 0 (Spark speculation): harmless —
    # its feeds stage separately and its commit is discarded as duplicate.
    with DataPlaneClient(host, port) as c:
        c.feed("demo", parts[0], algo="pca", partition=0, attempt=1)
        c.commit("demo", partition=0, attempt=1)

    with DataPlaneClient(host, port) as c:
        assert c.status("demo")["rows"] == data.shape[0]  # no double count
        result = c.finalize_pca("demo", k=8)
print("pc:", result["pc"].shape, "ev:", result["explained_variance"][:4])
