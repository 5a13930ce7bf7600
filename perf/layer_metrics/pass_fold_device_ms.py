"""Kernels: `fold_device_ms` for the cells whose end-to-end rate is
`pass_rows_per_s` — device time of one execution of the configuration's
`fold_program` on device 0 inside the traced window. In those cells a fold
program is `rescan`'s: a group of cached batches a dispatch
(`serve/daemon.py` `_RESCAN_GROUP`). The same reader under a second name,
because a per-layer metric names the one end-to-end metric it moves."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "layer_metrics", "fold_device_ms").read(obs)
