#!/usr/bin/env python3
"""What a bound can be set from: the spread of a set of runs, read as the
driver reads it.

    python3 perf/spread.py DIR [DIR ...]

Each DIR is one set: the files `perf/run.py --out DIR` wrote, one a run
(`<cell>.seed<n>.trace<0|1>.json`). For every cell found there and every
metric of its result lines it prints the runs' median, their spread (IQR ÷
median by `statistics.quantiles`) and the same without the run farthest
from the median; and, from the runs' kept `passes`, the same for the two
estimators of a rate: over all the passes' seconds (`stats.rows_per_s`) and
of the median pass (`stats.median_pass_rows_per_s`), with each run's late
passes beside them. It touches no JAX and needs no chip: it reads what chip
runs left behind. The driver refuses a bound under twice the mean of two
sets' spreads without their farthest runs, and one over eight times the
widest spread of all the runs (a bound of 1% is never too loose).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perf.harness import stats  # noqa: E402


def _line(name: str, values) -> str:
    if len(values) < 3:
        return f"  {name}: {len(values)} runs: {', '.join(f'{v:.6g}' for v in values)}"
    return (f"  {name}: median {statistics.median(values):.6g}, spread "
            f"{100 * stats.spread(values):.3f}%, without the farthest run "
            f"{100 * stats.spread_without_farthest(values):.3f}% "
            f"({min(values):.6g} … {max(values):.6g}, {len(values)} runs)")


def read_set(directory: str):
    """{cell: [run, ...]} of one directory, runs in the order of their names."""
    cells = {}
    for name in sorted(os.listdir(directory)):
        if not name.endswith(".json") or ".seed" not in name:
            continue
        with open(os.path.join(directory, name), encoding="utf-8") as f:
            run = json.load(f)
        cells.setdefault(name.split(".seed")[0], []).append({**run, "file": name})
    return cells


def say_set(directory: str, say=print) -> None:
    for cell, runs in read_set(directory).items():
        say(f"{directory}: {cell}, {len(runs)} runs, "
            f"{sum(not r['result']['correct'] for r in runs)} not correct")
        names = sorted({m for r in runs for m in r["result"]["metrics"]})
        for name in names:
            say(_line(name, [r["result"]["metrics"][name]["value"] for r in runs
                             if name in r["result"]["metrics"]]))
        kept = [(r, r["window"][1]) for r in runs if r.get("passes")]
        for label, rate in (("over all the passes' seconds", stats.rows_per_s),
                            ("of the median pass", stats.median_pass_rows_per_s)):
            values = [rate(r["passes"], deadline) for r, deadline in kept]
            say(_line(f"rows/s {label}", [v for v in values if v is not None]))
        for r, deadline in kept:
            done, took = stats.completed(r["passes"], deadline)
            if done:
                median = statistics.median(took)
                say(f"    {r['file']}: {len(done)} passes, median {1e3 * median:.3f} ms, "
                    f"{int((took > stats.LATE * median).sum())} late, late_pass_share "
                    f"{stats.late_pass_share(done, deadline):.4f}%, longest "
                    f"{1e3 * took.max():.1f} ms")


def main(argv=None) -> int:
    directories = (sys.argv[1:] if argv is None else argv)
    if not directories:
        print(__doc__)
        return 2
    for directory in directories:
        say_set(directory)
    return 0


if __name__ == "__main__":
    sys.exit(main())
