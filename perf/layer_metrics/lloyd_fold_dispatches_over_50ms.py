"""Model programs: how many single dispatches of the cached pass's fold program
took over 50 ms — Δ(count − bucket `le=0.05`) of
`srml_xla_dispatch_duration_seconds{fn=kmeans.streaming_update_group}`
(`utils/xprof.py` `LedgeredJit.__call__`: `lloyd_fold_dispatch_ms`' clock, one
observation a top-level call; `perf/harness/buckets.py`). 50 ms is a bound of
the registry's buckets: 200 × a dispatch, 4.6 × a KMeans pass. It says whether
ONE dispatch held a late pass's 120 ms or none did; against
`lloyd_rescans_over_50ms` it is the share of the stalls that fell inside a call
(PR 38: 9 of 14, the share of a rescan's host time the calls are). The count
covers the counters' WHOLE window, the profiled last seconds included (the host
clock's pass readers leave those out); the run's `late:` lines stay the
per-pass record. 0.0 when none did; nothing to read from a program without the
series."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "harness", "buckets").over(
        obs, "srml_xla_dispatch_duration_seconds", "0.05", fn="kmeans.streaming_update_group")
