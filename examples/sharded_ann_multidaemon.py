"""Pod-scale ANN: one IVF index SHARDED across daemons (round 5).

BASELINE config #5 (10M×768 on v5e-64) does not fit one host: a v5e-64
pod is 16 host VMs × 4 chips, one data-plane daemon per host. The
Spark-fed path (`SparkApproximateNearestNeighbors.fit`) does everything
below automatically whenever executors feed more than one daemon; this
example drives the same protocol by hand so the moving parts are visible
(docs/protocol.md "Sharded index across daemons", docs/ann-capacity.md):

1. each daemon accumulates the partitions ITS executors fed (row data
   never crosses hosts);
2. the first daemon's `finalize` trains the coarse quantizer and hands
   back the (nlist, d) centroids — O(nlist·d) on the wire;
3. every other daemon finalizes against those FROZEN centroids, so all
   shards bucket into the same list space;
4. `row_id_base` translates each shard's local row positions to global
   partition-major ids — every shard answers in one id space;
5. queries fan out to every shard and merge top-k host-side
   (`models/knn.merge_topk` — exact for the union, the daemon-level twin
   of the device-mesh all_gather merge).

Run: python examples/sharded_ann_multidaemon.py
"""

import os
import sys

if __package__ in (None, ""):  # direct script run
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_ml_tpu.models.knn import merge_topk
from spark_rapids_ml_tpu.serve import DataPlaneClient, DataPlaneDaemon
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache


def main() -> None:
    ensure_compile_cache()  # one rule for where compiled programs are kept
    rng = np.random.default_rng(0)
    kc, d, k, nlist = 16, 64, 5, 32
    centers = rng.normal(size=(kc, d)) * 8
    x = np.concatenate(
        [c + rng.normal(size=(400, d)) for c in centers]
    ).astype(np.float32)
    x = x[rng.permutation(len(x))]
    queries = x[:32]

    # Two daemons — in production, one per TPU host VM.
    with DataPlaneDaemon() as da, DataPlaneDaemon() as db:
        ca = DataPlaneClient(*da.address)
        cb = DataPlaneClient(*db.address)

        # 1. executors feed their host-local daemon (partitions 0-1 → A,
        #    2-3 → B); global id base = cumulative partition row counts.
        parts = np.array_split(x, 4)
        base = {
            str(i): int(sum(len(p) for p in parts[:i])) for i in range(4)
        }
        for pid, client in ((0, ca), (1, ca), (2, cb), (3, cb)):
            client.feed("ann-fit", parts[pid], algo="knn", partition=pid)
            client.commit("ann-fit", partition=pid)

        # 2. first shard trains the quantizer and returns it…
        info_a = ca.finalize_knn(
            "ann-fit", register_as="ann-idx", mode="ivf", nlist=nlist,
            nprobe=8, row_id_base={p: base[p] for p in ("0", "1")},
            return_centroids=True,
        )
        # 3. …which the peer build buckets against, frozen.
        info_b = cb.finalize_knn(
            "ann-fit", register_as="ann-idx", mode="ivf", nlist=nlist,
            nprobe=8, row_id_base={p: base[p] for p in ("2", "3")},
            centroids=info_a["centroids"],
        )
        shard_rows = [int(info_a["n_rows"][0]), int(info_b["n_rows"][0])]
        print("shards:", shard_rows, "rows — index never left the daemons")

        # 4+5. fan out the query batch, merge top-k by distance.
        per = [
            c.kneighbors("ann-idx", queries, k=min(k, n))
            for c, n in ((ca, shard_rows[0]), (cb, shard_rows[1]))
        ]
        dists, ids = merge_topk(
            [d_ for d_, _ in per], [i_ for _, i_ in per], k
        )
        print("top-1 self-hits:", int((ids[:, 0] == np.arange(32)).sum()),
              "/ 32")
        assert (ids[:, 0] == np.arange(32)).all()

        ca.drop_model("ann-idx"), cb.drop_model("ann-idx")
        ca.close(), cb.close()


if __name__ == "__main__":
    main()
