"""Kernels: the `colsum` scale's share of its roofline, in per cent:
operations and bytes from `scale(config)` of the algorithm's own cost file
(`perf/costs/colsum.py` of the run's tree), peaks from peaks.json, over the
program's device time. Nothing to read without a device trace."""

from perf.harness import cost, device, layout
from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs, key="scale_program")
    if prog is None:
        return None
    costs = layout.load_module(obs.root, "costs", obs.config["algo"])
    flops, nbytes = costs.scale(obs.config)
    line = cost.roofline(flops, nbytes, prog["seconds"] / prog["count"],
                         device.peaks_for(obs.device["kind"]))
    return 100.0 * line["share"]
