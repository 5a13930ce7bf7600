#!/usr/bin/env python3
"""The control of the logistic cell's `correct`, on the chip, at the cell's
own size (`perf/control.py` is for the `fold_resident` cells,
`perf/control_kmeans.py` for `lloyd_cached`).

    python3 perf/control_logreg.py --workload <a newton_cached cell> --seeds 1,2,3

For each seed: the cell's rows and labels made on the device as a run makes
them, the run's own start iterate, the plain reference
(`perf/reference/logreg.py`) over them, and the controls of
`perf/reference/control_logreg.py` — the same reference from rows rounded to
float8_e4m3fn and to bfloat16 (`--controls`, both by default) — compared
with the reference by `perf/harness/agree_logreg.py` under the
configuration's tolerances: the comparison a run makes of the program's
fits. Prints one JSON line a seed and control: each number compared beside
its limit, and whether the control came out correct (it must not). Needs a TPU; measures no time. Not run by the
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
    parser.add_argument("--controls", default="float8_e4m3fn,bfloat16")
    args = parser.parse_args(argv)

    from perf.harness import agree_logreg, device, layout, logreg_data
    from perf.reference import control_logreg
    from perf.reference import logreg as ref_logreg

    _, cell, cfg, traffic, p = layout.resolve(ROOT, args.workload)
    if traffic["generator"] != "newton_cached":
        raise SystemExit("perf/control_logreg.py is for the newton_cached cells")
    device.require_device("tpu", cell["chips"])
    rows, n_batches = p["batch_rows"], p["cached_batches"]
    fit_args = (cfg["max_iter"], cfg["tol"], cfg["reg"], cfg["fit_intercept"])
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        planted = logreg_data.spec(seed, cfg["n_cols"])
        start = logreg_data.start_iterate(seed, planted)
        batches = [logreg_data.device_rows(planted, seed, i, rows)
                   for i in range(n_batches)]
        ref = ref_logreg.fit(batches, start, *fit_args)
        for precision in args.controls.split(","):
            got = control_logreg.fit(ref_logreg, batches, start, *fit_args,
                                     precision=precision)
            # the control folds every row in every pass: only its numbers are off
            model = {"w": got["w"], "b": got["b"], "loss": got["loss"],
                     "pass0": got["pass0"], "pass_rows": [float(n_batches * rows)]}
            problems = agree_logreg.check_fit(model, ref, cfg["tolerances"],
                                              n_batches * rows)
            compared = agree_logreg.compared([{"model": model}], cfg["tolerances"],
                                             n_batches * rows)
            all_failed &= bool(problems)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": precision, "correct": not problems,
                              "compared": compared,
                              "pass0_parts": model["_pass0_parts"]}), flush=True)
        del batches  # before the next seed's: two sets of rows do not fit a chip
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
