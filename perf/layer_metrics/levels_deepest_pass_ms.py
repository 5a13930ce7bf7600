"""Daemon: mean milliseconds of the deepest level passes (depth `max_depth`
− 1 of the configuration: 32 frontier nodes a tree at depth 5) of the
window's whole fits, on the benchmark's own clock; the other end of
`levels_root_pass_ms`, whose arithmetic it shares. Nothing to read where no
listed pass carries that depth."""

from perf.harness import layout


def read(obs):
    root = layout.load_module(obs.root, "layer_metrics", "levels_root_pass_ms")
    return root.mean_ms(obs, obs.config["max_depth"] - 1)
