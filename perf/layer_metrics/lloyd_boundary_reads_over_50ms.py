"""Daemon: how many of the window's `lloyd.boundary.read` spans (`serve/daemon.py`
`_Job.step`: the wait for the device at a pass boundary) took over 50 ms —
Δ(count − bucket `le=0.05`) of
`srml_phase_duration_seconds{phase=lloyd.boundary.read}`
(`perf/harness/buckets.py`). 50 ms is a bound of the registry's buckets: 4.6 ×
a KMeans pass, under every late pass on record (+50 … +180 ms, PERF.md §7). A
late pass that shows here stalled while the host waited for the device, not
while it dispatched. The count covers the counters' WHOLE window, the profiled
last seconds included (the host clock's pass readers leave those out); the
run's `late:` lines stay the per-pass record. 0.0 when none did; nothing to
read from a program without the series."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "harness", "buckets").over(
        obs, "srml_phase_duration_seconds", "0.05", phase="lloyd.boundary.read")
