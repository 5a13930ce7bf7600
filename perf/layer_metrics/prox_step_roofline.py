"""Kernels: the proximal-Newton step's share of its roofline, in per cent —
the least time the chip could take for the steps traced (operations and
bytes from `perf/costs/<algo>.py` `prox_step` for their products of the
Hessian, peaks from `perf/harness/peaks.json`) over the device time of the
configuration's `prox_program` on device 0 inside the traced window. The
products are the steps' own (`obs.notes["prox_steps"]`: host interval and
products of every step of the window), summed over the steps that lie
wholly inside the traced interval (`obs.notes["traced"]`); an execution cut
by the interval's edge adds time and no products, so the share can read
low there and never high. Nothing to read without the program in the
trace or the steps' products."""

from perf.harness import cost, device, layout
from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs, key="prox_program")
    traced = obs.notes.get("traced")
    steps = obs.notes.get("prox_steps") or []
    if prog is None or traced is None:
        return None
    lo, hi = traced
    inside = [n for start, end, n in steps if n and start >= lo and end <= hi]
    if not inside:
        return None
    costs = layout.load_module(obs.root, "costs", obs.config["algo"])
    flops, nbytes = (sum(c) for c in zip(*(costs.prox_step(obs.config, n)
                                           for n in inside)))
    line = cost.roofline(flops, nbytes, prog["seconds"],
                         device.peaks_for(obs.device["kind"]))
    obs.notes["prox_step_roofline_bound"] = line["bound"]
    return 100.0 * line["share"]
