"""Collectives: the part of the collective time during which no other
operation ran on device 0, over the fold program's device time in the
traced window, in per cent."""

from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs)
    if prog is None:
        return None
    first = obs.trace["devices"][min(obs.trace["devices"])]
    if not first["collective_events"]:
        return None
    return 100.0 * first["collective_exposed_s"] / prog["seconds"]
