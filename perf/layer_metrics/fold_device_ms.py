"""Kernels: device time of one fold — the sum of the device durations of
the fold program's executions on device 0 inside the traced window, over
their number. The program's name is the configuration's `fold_program`."""


def program(obs, key="fold_program"):
    if not obs.trace or not obs.trace.get("devices"):
        return None
    first = obs.trace["devices"][min(obs.trace["devices"])]
    prog = first["programs"].get(obs.config.get(key))
    return prog if prog and prog["count"] else None


def read(obs):
    prog = program(obs)
    return None if prog is None else 1e3 * prog["seconds"] / prog["count"]
