#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, in a traced run `breakdown`, and
last `compared`: each number that decided `correct` beside its limit, which
are also the last lines of standard error. With `--trace 0` the metrics are
the cell's end-to-end metrics, with `--trace 1` its per-layer metrics.
Everything else is on earlier lines (or, with `--out DIR`, in files there). It needs a TPU with
the chips the cell asks for: without, it exits non-zero and prints no
result. See perf/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_PERF)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from perf.harness.observe import process_start_wall

    started = process_start_wall()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--out", default=None,
                        help="directory for the run's notes and raw trace")
    args = parser.parse_args(argv)

    def say(message: str) -> None:
        print(message, flush=True)

    def stage(name: str) -> None:
        say(f"  set-up +{time.time() - started:7.2f} s: {name}")

    try:
        from perf.harness import device, layout, runner

        try:
            import spark_rapids_ml_tpu  # noqa: F401 - the system under test
        except ImportError as e:
            raise layout.LayoutError(f"the program is not here: {e}") from e
        stage("interpreter, benchmark and program imported")
        chips = layout.resolve(ROOT, args.workload)[1]["chips"]
        t0 = time.time()
        device.require_device("tpu", chips)
        runtime_s = time.time() - t0
        stage(f"TPU runtime up (took {runtime_s:.2f} s, not counted in setup_s)")
        from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

        say(f"compile cache: {ensure_compile_cache()}")
        result = runner.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            platform="tpu", say=say, process_start=started, runtime_s=runtime_s,
            out_dir=args.out)
    except Exception as e:  # noqa: BLE001 - report, print no result, exit non-zero
        traceback.print_exc()
        print(f"perf/run.py FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
