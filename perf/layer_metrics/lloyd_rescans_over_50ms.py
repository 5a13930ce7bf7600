"""Daemon: how many of the window's `pass.rescan` spans (`serve/daemon.py`
`_Job.rescan`: two locks and a cached pass's dispatches) took over 50 ms —
Δ(count − bucket `le=0.05`) of `srml_phase_duration_seconds{phase=pass.rescan}`
(`perf/harness/buckets.py`). 50 ms is a bound of the registry's buckets: 4.6 ×
a KMeans pass, under every late pass on record (+50 … +180 ms, PERF.md §7).
Read beside `lloyd_fold_dispatches_over_50ms` and
`lloyd_boundary_reads_over_50ms`: a slow rescan WITH a slow dispatch stalled
inside one call, one with none between two calls; stalls spread over the three
in proportion to the loop's host time are the host thread standing still (what
PR 38 found), stalls in the dispatches alone would be the runtime's queue. The
count covers the counters' WHOLE window, the profiled last seconds included
(the host clock's pass readers leave those out); the run's `late:` lines stay
the per-pass record. 0.0 when none did; nothing to read from a program without
the series."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "harness", "buckets").over(
        obs, "srml_phase_duration_seconds", "0.05", phase="pass.rescan")
