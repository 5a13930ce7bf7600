"""Daemon: mean milliseconds of the program's `forest.boundary` span (named
by `models/random_forest.py` `RandomForestJob.boundary_span`, opened by
`serve/daemon.py` `_Job.step`: from entry under the lock to the info dict —
the scorer (`forest.score`, its child: its read is the wait for the pass's
folds), the host's update of the node tables, the next depth's zero
histogram, the snapshot callback) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=forest.boundary}` across the window's
whole fits, so every depth's boundary weighs the same. It holds the wait
for folds still running; what of it the device spends idle is the
`boundary` idle gap of the traced run. Nothing to read from a program
without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="forest.boundary")
