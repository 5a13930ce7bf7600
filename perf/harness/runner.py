"""Runs one cell once and builds the result line."""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Optional

from perf.harness import device as device_mod
from perf.harness import layout
from perf.harness.observe import Context, process_start_wall


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
             platform: Optional[str] = "tpu", say: Callable[[str], None] = print,
             process_start: Optional[float] = None, runtime_s: float = 0.0,
             out_dir: Optional[str] = None) -> Dict[str, Any]:
    """One run of `workload` → the result object (see perf/README.md).
    `platform=None` is the tests' CPU rehearsal; perf/run.py has no way to
    ask for it. `runtime_s`: what the caller saw the accelerator's runtime
    take to come up, which `setup_s` leaves out."""
    process_start = process_start_wall() if process_start is None else process_start
    bench, cell, config, traffic, params = layout.resolve(root, workload)
    kind = "per_layer" if trace else "end_to_end"
    entries = layout.metric_entries(bench, kind, workload)
    readers = [(m, layout.load_module(root, layout.READER_DIRS[kind], m["name"]))
               for m in entries]
    generator = layout.load_module(root, "generators", traffic["generator"])

    info = device_mod.require_device(platform, cell["chips"])
    say(f"cell {workload}: config {cell['config']}, traffic {cell['traffic']} "
        f"(generator {traffic['generator']}), seed {seed}, {seconds:g} s, "
        f"trace {int(trace)}; {info['count']} x {info['kind']} ({info['platform']})")
    say(f"params: {json.dumps(params, sort_keys=True)}")
    ctx = Context(root=root, cell=cell, config=config, params=params, seed=seed,
                  seconds=seconds, trace=trace, device=info, say=say,
                  process_start=process_start, runtime_s=runtime_s,
                  out_dir=out_dir)
    obs = generator.run(ctx)

    compiles = obs.compiles_in_window()
    say(f"compiles in window: {compiles} (ledgered: "
        f"{obs.ledger_compiles_in_window() or 0})")
    if compiles:
        say("  NOT CORRECT: a program was built or loaded inside the window")
    metrics: Dict[str, Dict[str, Any]] = {}
    for entry, reader in readers:
        value = reader.read(obs)
        if value is None:
            say(f"  metric {entry['name']}: nothing to read, left out")
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    # the window's peak, not the process's: what the benchmark itself puts on
    # the device before and after the window is not the system's (PERF.md §4)
    dev = {"platform": info["platform"], "kind": info["kind"],
           "count": info["count"], "memory_peak_bytes": obs.memory_peak_bytes}
    result: Dict[str, Any] = {
        "correct": bool(obs.correct and compiles == 0),
        "attempted": int(obs.attempted),
        "failed": int(obs.failed),
        "metrics": metrics,
        "device": dev,
    }
    if trace and obs.trace and obs.trace.get("devices"):
        dev["busy_s"] = obs.trace["busy_s"]
        dev["window_s"] = obs.trace["window_s"]
        result["breakdown"] = {"device_ops": obs.trace["device_ops"],
                               "idle_gaps": obs.trace["idle_gaps"]}
    result["compared"] = {**obs.compared,
                          "compiles_in_window": [float(compiles), 0.0]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{workload}.seed{seed}.trace{int(trace)}.json"),
                  "w", encoding="utf-8") as f:
            json.dump({"result": result, "notes": obs.notes, "window": obs.window,
                       "passes": obs.passes, "fits": obs.fits, "setup_s": obs.setup_s},
                      f, default=str)
    return result
