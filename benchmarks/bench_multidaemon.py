"""Multi-daemon pass-boundary overhead: export → merge → step → iterate sync.

The multi-host data plane's design bet (the reference's partition-Gram
property, RapidsRowMatrix.scala:122-139) is that ONLY O(d²)/O(k·d)
sufficient statistics cross hosts — never rows — so the per-pass boundary
cost is independent of dataset size. This bench puts a number on that
claim: two daemons in two OS PROCESSES (separate runtimes, TCP between
everything, like tests/test_spark_multidaemon.py's flagship), a KMeans job
(k=100, d=2048) and a PCA job (d=2048) fed on both, then the full pass
boundary timed: peer export_state → primary merge_state → primary step →
get_iterate → peer set_iterate. Bytes-on-wire are computed from the actual
exported array sizes. Row-independence is demonstrated directly: the
boundary is timed at two dataset scales (1× and 8× rows) in the same run.

Prints ONE JSON line. Runs on host CPU (the boundary is host/TCP work;
device math is not in the loop being measured).
"""

import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

D = int(os.environ.get("SRML_BENCH_D", 2048))
K = int(os.environ.get("SRML_BENCH_K", 100))
ROWS = int(os.environ.get("SRML_BENCH_ROWS", 4096))
PASSES = int(os.environ.get("SRML_BENCH_PASSES", 5))

# Workers are pinned to the CPU: two daemon PROCESSES cannot share one
# chip. The READY line carries the backend each worker actually got.
_WORKER = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()
d = DataPlaneDaemon(host="127.0.0.1", port=0, ttl=600.0).start()
print(f"READY {d.address[1]} {jax.default_backend()}", flush=True)
sys.stdin.read()
d.stop()
"""


def main() -> None:
    from spark_rapids_ml_tpu.serve.client import DataPlaneClient

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    workers = []
    backends = set()
    try:
        for _ in range(2):
            env = {k: v for k, v in os.environ.items()
                   if not k.startswith("SRML_")}
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in (repo, env.get("PYTHONPATH")) if p
            )
            proc = subprocess.Popen(
                [sys.executable, "-c", _WORKER],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                cwd=repo, env=env, text=True,
            )
            _, port, backend = proc.stdout.readline().split()
            backends.add(backend)
            workers.append((proc, int(port)))
        (pa, port_a), (pb, port_b) = workers
        ca = DataPlaneClient("127.0.0.1", port_a)
        cb = DataPlaneClient("127.0.0.1", port_b)

        rng = np.random.default_rng(0)
        seed_x = rng.normal(size=(max(K, 256), D)).astype(np.float32)

        def feed_pass(job, xs, pass_id):
            for pid, (c, x) in enumerate(xs):
                c.feed(job, x, algo="kmeans", partition=pid, pass_id=pass_id,
                       params={"k": K, "seed": 0})
                c.commit(job, partition=pid, pass_id=pass_id)

        def boundary(job):
            """One timed pass boundary; returns (seconds, wire bytes).

            The untimed exports first force both daemons' PENDING feed
            folds to completion (jax dispatch is async; export_state's
            device_get waits on them) — the boundary number must measure
            the boundary, not the tail of the scan's compute."""
            cb.export_state(job)
            ca.export_state(job)
            t0 = time.perf_counter()
            arrays, meta = cb.export_state(job)
            ca.merge_state(job, arrays, rows=int(meta["pass_rows"]),
                           algo="kmeans", n_cols=D,
                           params={"k": K, "seed": 0})
            ca.step(job)
            it_arrays, iteration = ca.get_iterate(job)
            cb.set_iterate(job, it_arrays, iteration)
            dt = time.perf_counter() - t0
            wire = sum(a.nbytes for a in arrays.values()) + sum(
                a.nbytes for a in it_arrays.values()
            )
            return dt, wire

        def run_kmeans(job, rows):
            xa = rng.normal(size=(rows, D)).astype(np.float32)
            xb = rng.normal(size=(rows, D)).astype(np.float32)
            ca.seed_kmeans(job, seed_x, k=K, params={"seed": 0})
            cb.seed_kmeans(job, seed_x, k=K, params={"seed": 0})
            times, wire = [], 0
            it = 0
            for p in range(PASSES):
                feed_pass(job, [(ca, xa), (cb, xb)], it)
                dt, wire = boundary(job)
                it += 1  # step advanced the primary; peers synced to it
                times.append(dt)
            ca.drop(job), cb.drop(job)
            return float(np.median(times[1:])), wire  # drop compile pass

        km_ms_1x, km_wire = run_kmeans("km1", ROWS)
        km_ms_8x, _ = run_kmeans("km8", 8 * ROWS)

        # PCA: single-pass — the boundary is export+merge only.
        xpa = rng.normal(size=(ROWS, D)).astype(np.float32)
        times = []
        for p in range(3):
            job = f"pca{p}"
            ca.feed(job, xpa, algo="pca", partition=0)
            ca.commit(job, partition=0)
            cb.feed(job, xpa, algo="pca", partition=1)
            cb.commit(job, partition=1)
            cb.export_state(job)  # force pending folds (see boundary())
            t0 = time.perf_counter()
            arrays, meta = cb.export_state(job)
            ca.merge_state(job, arrays, rows=int(meta["pass_rows"]),
                           algo="pca", n_cols=D)
            times.append(time.perf_counter() - t0)
            pca_wire = sum(a.nbytes for a in arrays.values())
            ca.drop(job), cb.drop(job)
        pca_ms = float(np.median(times[1:]) * 1e3)

        ca.close(), cb.close()
        # Bound statement: rows/s-equivalent the boundary costs — at the
        # headline fit rate (21.8M rows/s/chip), X ms of boundary "buys"
        # X·21800 rows of scan; a pass over millions of rows dwarfs it.
        print(json.dumps({
            "metric": f"multidaemon_pass_boundary_ms_d{D}_k{K}",
            "value": round(km_ms_1x * 1e3, 2),
            "unit": "ms/pass",
            "vs_baseline": 0.0,
            "backend": ",".join(sorted(backends)),
            "kmeans_wire_mb_per_pass": round(km_wire / 2**20, 3),
            "kmeans_boundary_ms_8x_rows": round(km_ms_8x * 1e3, 2),
            "rows_independent": bool(km_ms_8x < 3 * km_ms_1x),
            "pca_export_merge_ms": round(pca_ms, 2),
            "pca_wire_mb": round(pca_wire / 2**20, 3),
            "boundary_equiv_rows_at_headline_rate": int(
                km_ms_1x * 21.8e6
            ),
        }))
    finally:
        for proc, _ in workers:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()


if __name__ == "__main__":
    main()
