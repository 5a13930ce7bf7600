#!/usr/bin/env python3
"""The control of the forest cell's `correct`, on the chip, at the cell's
own size (`perf/control_logreg.py` is the logistic cell's).

    python3 perf/control_rf.py --workload <a levels_cached cell> --seeds 1,2,3,4

For each seed: the cell's deployment set up as a run sets it up (the job
fed and its pass cached), one fit with its histograms fetched — the fit a
run compares in depth — and then, in the place of the plain reference
(`perf/reference/rf.py`), the control of `perf/reference/control_rf.py`:
the same reference from rows rounded to bfloat16, compared with the
program's fit by `perf/harness/agree_rf.py` under the configuration's
tolerances, as a run compares. Prints one JSON line a seed: each number
compared beside its limit, and whether the control came out correct (it
must not). `--with-reference` also prints the true reference's line (which
must come out correct). Needs a TPU; measures no time. Not run by the
benchmark's own runs."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def lines(root, workload, seed, say, with_reference=False):
    """→ the JSON-ready lines of one seed (the control's last)."""
    from perf.harness import layout

    _, cell, cfg, traffic, p = layout.resolve(root, workload)
    if traffic["generator"] != "levels_cached":
        raise SystemExit("perf/control_rf.py is for the levels_cached cells")
    generator = layout.load_module(root, "generators", "levels_cached")
    agree = layout.load_module(root, "harness", "agree_rf")
    reference = layout.load_module(root, "reference", "rf")
    control = layout.load_module(root, "reference", "control_rf")
    generator.refuse_unless_cacheable()
    forest = generator.CachedForest(root, cfg, p, seed, cell["chips"], say)
    _, captured = forest.captured_fit()
    out = []
    for name, rounded in ([("float32", None)] if with_reference else []) + [
            ("bfloat16", control.lower)]:
        compared = forest.compared(captured, [], agree, reference, say, rounded)
        out.append({"workload": workload, "seed": seed, "rows": name,
                    "correct": not agree.problems(compared), "compared": compared})
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--with-reference", action="store_true")
    args = parser.parse_args(argv)

    from perf.harness import device, layout
    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    device.require_device("tpu", layout.resolve(ROOT, args.workload)[1]["chips"])
    ensure_compile_cache()
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in lines(ROOT, args.workload, seed, lambda m: print(m, file=sys.stderr),
                          args.with_reference):
            if line["rows"] == "bfloat16":
                all_failed &= not line["correct"]
            print(json.dumps(line), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
