#!/usr/bin/env python3
"""The control of the multinomial logistic cell's `correct`, on the chip,
at the cell's own size (`perf/control_logreg.py` is the binary cell's).

    python3 perf/control_logreg_mn.py --workload <a mm_newton_cached cell> --seeds 1,2,3

For each seed: the cell's rows and labels made on the device as a run makes
them, the run's own start iterate, and the controls of
`perf/reference/control_logreg_mn.py` — the same reference from rows
rounded to float8_e4m3fn and to bfloat16 (`--controls`, both by default) —
compared by `perf/harness/agree_logreg_mn.py`, under the configuration's
tolerances, with the plain reference (`perf/reference/logreg_mn.py`) over
the same rows: its first pass from the start, its last pass from where the
control's last pass started — the comparison a run makes of the program's
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

    from perf.harness import device, layout

    agree = layout.load_module(ROOT, "harness", "agree_logreg_mn")
    data = layout.load_module(ROOT, "harness", "logreg_mn_data")
    control = layout.load_module(ROOT, "reference", "control_logreg_mn")
    reference = layout.load_module(ROOT, "reference", "logreg_mn")

    _, cell, cfg, traffic, p = layout.resolve(ROOT, args.workload)
    if traffic["generator"] != "mm_newton_cached":
        raise SystemExit("perf/control_logreg_mn.py is for the mm_newton_cached cells")
    device.require_device("tpu", cell["chips"])
    rows, n_batches = p["batch_rows"], p["cached_batches"]
    fit_args = (cfg["max_iter"], cfg["tol"], cfg["reg"], cfg["fit_intercept"])
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        planted = data.spec(seed, cfg["n_cols"], cfg["n_classes"])
        start = data.start_iterate(seed, planted)
        batches = [data.device_rows(planted, seed, i, rows) for i in range(n_batches)]
        pass0_ref = reference.scan(batches, start["w"], start["b"])
        for precision in args.controls.split(","):
            got = control.fit(reference, batches, start, *fit_args, precision=precision)
            # the reference's last pass from where the control's started, as
            # a run takes it from where the program's did
            last_ref = reference.one_pass(batches, got["before_last"], cfg["reg"],
                                          cfg["fit_intercept"])
            # the control folds every row in every pass: only its numbers are off
            model = {"w": got["w"], "b": got["b"], "loss": got["loss"],
                     "pass0": got["pass0"], "pass_rows": [float(n_batches * rows)]}
            problems = agree.check_fit(model, pass0_ref, last_ref, cfg["tolerances"],
                                       n_batches * rows)
            compared = agree.compared([{"model": model}], cfg["tolerances"],
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
