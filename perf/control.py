#!/usr/bin/env python3
"""The control of `correct`, on the chip, at a cell's own size.

    python3 perf/control.py --workload <a fold_resident cell> --seeds 1,2,3

For each seed: the cell's ring made on the device as a run makes it, the
plain reference over it, and the control of `perf/reference/control.py`
(the reference in float8_e4m3fn) compared with the reference by
`perf/harness/agree.py` under the configuration's tolerances — the
comparison a run makes of the program's models. Prints one JSON line a seed:
each number compared, beside its limit, and whether the control came out
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

    import jax

    from perf.harness import agree, data, device, layout
    from perf.reference import control
    from perf.reference import pca as ref_pca

    _, cell, cfg, traffic, p = layout.resolve(ROOT, args.workload)
    if traffic["generator"] != "fold_resident":
        raise SystemExit("perf/control.py is for the fold_resident cells")
    device.require_device("tpu", cell["chips"])
    # rows over the chips as a run lays them; a mesh of the benchmark's own
    rows_over_chips = jax.sharding.NamedSharding(
        jax.sharding.Mesh(jax.devices(), ("data",)),
        jax.sharding.PartitionSpec("data", None))
    d, k = cfg["n_cols"], cfg["k"]
    rows, ring_n, folds = p["global_batch_rows"], p["ring_batches"], p["folds_per_fit"]
    weights = [folds // ring_n] * ring_n
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        spec = data.pca_spec(seed, d, k)
        ring = [data.device_rows(spec, seed, i, rows, rows_over_chips)
                for i in range(ring_n)]
        jax.block_until_ready(ring)
        ref = ref_pca.fit(ring, weights, k)
        model = control.fit(ring, weights, k)
        model["rows"] = int(model["rows"])
        problems = agree.check_pca_fit(model, ref, cfg["tolerances"], d, k)
        compared = agree.compared_pca([{"model": model}], cfg["tolerances"], folds * rows)
        all_failed &= bool(problems)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "float8_e4m3fn", "correct": not problems,
                          "compared": compared}), flush=True)
        del ring  # before the next seed's: two rings do not fit a chip
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
