"""Model programs: how many single dispatches of the Gram fold took over 50 ms —
Δ(count − bucket `le=0.05`) of
`srml_xla_dispatch_duration_seconds{fn=gram.streaming_update}`
(`utils/xprof.py` `LedgeredJit.__call__`: `fold_dispatch_ms`' clock, one
observation a top-level call; `perf/harness/buckets.py`). 50 ms is a bound of
the registry's buckets: 17 × a dispatch under back-pressure (2.56 ms). The ~115
ms stalls of the host thread (PERF.md §7) show here 2–4 times a window and cost
a fit ~25 ms each, the device draining its queue meanwhile; a fit whose 384
folds take 3–4 s instead of 1.117 would show as one or a few dispatches of
seconds, or as none — the host's doing then. The count covers the counters'
WHOLE window, the profiled last seconds included (the host clock's pass readers
leave those out); the run's `late:` lines stay the per-pass record. 0.0 when
none did; nothing to read from a program without the series."""

from perf.harness import layout


def read(obs):
    return layout.load_module(obs.root, "harness", "buckets").over(
        obs, "srml_xla_dispatch_duration_seconds", "0.05", fn="gram.streaming_update")
