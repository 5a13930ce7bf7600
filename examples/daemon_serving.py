"""Serving from the data-plane daemon: TPU-resident transform + KNN.

Round-3 surface (docs/protocol.md, "Model-serving ops"): a fitted model
registers ONCE on the TPU-host daemon and then scores batches with its
arrays device-resident — the accelerator-resident columnar UDF of the
reference (RapidsPCA.scala:128-161) without its per-batch matrix
re-upload (rapidsml_jni.cu:85). KNN goes further: the executors stream
raw rows, the daemon builds the index ON ITS DEVICES, and queries are
served remotely — neither the dataset nor the dataset-sized index ever
exists on the driver.

Run: python examples/daemon_serving.py
"""

import os
import sys

if __package__ in (None, ""):  # direct script run
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache


def main() -> None:
    ensure_compile_cache()  # one rule for where compiled programs are kept
    rng = np.random.default_rng(0)
    x = rng.normal(size=(20_000, 64)).astype(np.float32)

    with DataPlaneDaemon() as daemon:  # on the TPU host; ttl/token in prod
        host, port = daemon.address

        # --- serve a fitted model's transform from the TPU -------------
        model = PCA().setK(8).fit({"features": x})
        with DataPlaneClient(host, port) as c:
            c.ensure_model("pca-serve", "pca", model._model_data())
            # ... each executor task then scores its batches remotely:
            out = c.transform("pca-serve", x[:4096])
            print("served projection:", out["output"].shape)  # (4096, 8)

        # --- daemon-built KNN index (never driver-resident) -------------
        with DataPlaneClient(host, port) as c:
            for pid, part in enumerate(np.array_split(x, 4)):
                c.feed("knn-fit", part, algo="knn", partition=pid)
                c.commit("knn-fit", partition=pid)
            stats = c.finalize_knn(
                "knn-fit", register_as="knn-index", mode="ivf",
                nlist=64, nprobe=16,
            )
            print("index built on daemon:", {k: v.tolist() for k, v in stats.items()})
            dists, ids = c.kneighbors("knn-index", x[:8], k=5)
            print("self-nearest:", ids[:, 0].tolist())
            c.drop_model("knn-index")
            c.drop_model("pca-serve")


if __name__ == "__main__":
    main()
