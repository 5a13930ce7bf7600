"""Collectives: device time of the collective operations (all-reduce …) on
device 0 inside the traced window, over the folds in it."""

from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs)
    if prog is None:
        return None
    first = obs.trace["devices"][min(obs.trace["devices"])]
    if not first["collective_events"]:
        return None
    return 1e3 * first["collective_s"] / prog["count"]
