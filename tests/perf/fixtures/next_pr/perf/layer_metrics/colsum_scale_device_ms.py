"""Kernels: device time of one `colsum` scale — the configuration's
`scale_program` on device 0 inside the traced window, over its executions.
Nothing to read without a device trace."""

from perf.layer_metrics import fold_device_ms


def read(obs):
    prog = fold_device_ms.program(obs, key="scale_program")
    return None if prog is None else 1e3 * prog["seconds"] / prog["count"]
