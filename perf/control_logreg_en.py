#!/usr/bin/env python3
"""The control of the elastic-net logistic cell's `correct`, on the chip, at
the cell's own size.

    python3 perf/control_logreg_en.py --workload <a prox_newton_cached cell> --seeds 1,2,3

For each seed: the cell's rows and labels made on the device as a run makes
them, the run's own start iterate, and the controls of
`perf/reference/control_logreg_en.py` — the reference's whole fit from rows
rounded to float8_e4m3fn and to bfloat16 (`--controls`, both by default) —
compared with the plain reference (`perf/reference/logreg_en.py`: its first
pass at the start, its first boundary from the start, its last boundary
from the iterate the control's last pass started at) by `perf/harness/agree_logreg_en.py` under the
configuration's tolerances: the comparison a run makes of the program's
fits. Prints one JSON line a seed and control: each number compared beside
its limit, and whether the control came out correct (it must not). Needs a
TPU; measures no time. Not run by the benchmark's own runs."""

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

    from perf.harness import agree_logreg_en, device, layout, logreg_en_data
    from perf.reference import control_logreg_en
    from perf.reference import logreg_en as ref_logreg_en

    _, cell, cfg, traffic, p = layout.resolve(ROOT, args.workload)
    if traffic["generator"] != "prox_newton_cached":
        raise SystemExit("perf/control_logreg_en.py is for the prox_newton_cached cells")
    device.require_device("tpu", cell["chips"])
    rows, n_batches = p["batch_rows"], p["cached_batches"]
    reg, alpha, fit_intercept = cfg["reg"], cfg["elastic_net"], cfg["fit_intercept"]
    whole = float(n_batches * rows)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        planted = logreg_en_data.spec(seed, cfg["n_cols"])
        start = logreg_en_data.start_iterate(seed, planted)
        batches = [logreg_en_data.device_rows(planted, seed, i, rows)
                   for i in range(n_batches)]
        pass0_ref = ref_logreg_en.scan(batches, start["w"], float(start["b"][0]))
        first_ref = ref_logreg_en.first_step(pass0_ref, start, reg, alpha, fit_intercept)
        for precision in args.controls.split(","):
            got = control_logreg_en.fit(ref_logreg_en, batches, start, cfg["max_iter"],
                                        cfg["tol"], reg, alpha, fit_intercept,
                                        precision=precision)
            last_ref = ref_logreg_en.last_step(batches, got["last_w"], got["last_b"], reg,
                                               alpha, fit_intercept)
            # the control folds every row in every pass: only its numbers are off
            model = {"w": got["w"], "b": got["b"], "loss": got["loss"],
                     "pass0": got["pass0"], "pass_rows": [whole]}
            problems = agree_logreg_en.check_fit(model, pass0_ref, last_ref,
                                                 cfg["tolerances"], cfg["support_margin"],
                                                 int(whole))
            compared = agree_logreg_en.compared([{"model": model}], cfg["tolerances"],
                                                int(whole))
            compared[agree_logreg_en.FIRST], first_problems = agree_logreg_en.check_first_step(
                {"w": got["first_w"], "b": got["first_b"]}, first_ref, start,
                cfg["tolerances"])
            problems += first_problems
            all_failed &= bool(problems)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": precision, "correct": not problems,
                              "compared": compared,
                              "nonzero": int((got["w"] != 0).sum()),
                              "pass0_parts": model["_pass0_parts"]}), flush=True)
        del batches  # before the next seed's: two sets of rows do not fit a chip
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
