"""Kernels: `fold_roofline` for the cells whose end-to-end rate is
`pass_rows_per_s` — the fold program's share of its roofline, in per cent:
the least time the chip could take for `obs.fold_rows_per_chip` rows (cost
from `perf/costs/<algo>.py`, peaks from `perf/harness/peaks.json`) over
`pass_fold_device_ms`. The same reader under a second name, because a
per-layer metric names the one end-to-end metric it moves."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "layer_metrics", "fold_roofline").read(obs)
