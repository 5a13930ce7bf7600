"""Kernels: the fold's share of its roofline, in per cent — the least time
the chip could take for one chip's rows of a fold (the larger of operations
÷ peak FLOP/s and bytes ÷ peak bytes/s, both from shapes by the file of
the configuration's `algo` under perf/costs/, peaks from
perf/harness/peaks.json) over `fold_device_ms`."""

from perf.harness import cost, device
from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs)
    if prog is None or not obs.fold_rows_per_chip:
        return None
    flops, nbytes = cost.fold_cost(obs.config, obs.fold_rows_per_chip, obs.root)
    line = cost.roofline(flops, nbytes, prog["seconds"] / prog["count"],
                         device.peaks_for(obs.device["kind"]))
    obs.notes["fold_roofline_bound"] = line["bound"]
    return 100.0 * line["share"]
