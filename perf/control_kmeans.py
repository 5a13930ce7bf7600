#!/usr/bin/env python3
"""The control of the KMeans cell's `correct`, on the chip, at the cell's
own size (`perf/control.py` is for the `fold_resident` cells).

    python3 perf/control_kmeans.py --workload <a lloyd_cached cell> --seeds 1,2,3

For each seed: the cell's rows made on the device as a run makes them, the
run's own starting centres, the plain reference (`perf/reference/kmeans.py`)
over them, and the control of `perf/reference/control_kmeans.py` — the same
reference from rows rounded to float8_e4m3fn — compared with the reference by
`perf/harness/agree_kmeans.py` under the configuration's tolerances: the
comparison a run makes of the program's fits. Prints one JSON line a seed:
each number compared beside its limit, and whether the control came out
correct (it must not). Needs a TPU; measures no time. Not run by the
benchmark's own runs."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args(argv)

    import numpy as np

    from perf.harness import agree_kmeans, device, kmeans_data, layout
    from perf.reference import control_kmeans
    from perf.reference import kmeans as ref_kmeans

    _, cell, cfg, traffic, p = layout.resolve(ROOT, args.workload)
    if traffic["generator"] != "lloyd_cached":
        raise SystemExit("perf/control_kmeans.py is for the lloyd_cached cells")
    device.require_device("tpu", cell["chips"])
    d, k = cfg["n_cols"], cfg["k"]
    rows, n_batches = p["batch_rows"], p["cached_batches"]
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        planted = kmeans_data.spec(seed, d, k)
        batches = [kmeans_data.device_rows(planted, seed, i, rows)
                   for i in range(n_batches)]
        start = kmeans_data.start_centres(seed, np.asarray(batches[0]), k)
        ref = ref_kmeans.fit(batches, start, cfg["max_iter"], cfg["tol"])
        got = control_kmeans.fit(ref_kmeans, batches, start, cfg["max_iter"], cfg["tol"])
        # the control folds every row in every pass: only its numbers are off
        model = {"centers": got["centers"], "cost": got["cost"], "pass0": got["pass0"],
                 "pass_counts": [float(n_batches * rows)]}
        problems = agree_kmeans.check_fit(model, ref, cfg["tolerances"], n_batches * rows)
        compared = agree_kmeans.compared([{"model": model}], cfg["tolerances"],
                                         n_batches * rows)
        all_failed &= bool(problems)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "float8_e4m3fn", "correct": not problems,
                          "compared": compared, "pass0_parts": model["_pass0_parts"]}),
              flush=True)
        del batches  # before the next seed's: two sets of rows do not fit a chip
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
