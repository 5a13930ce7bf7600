"""Daemon: mean milliseconds of the program's `lloyd.boundary` span
(`serve/daemon.py` `_Job.step`, kmeans: from entry under the lock to the
info dict — the wait for the pass's folds, `apply_lloyd_update`, `moved2`
and `cost` to the host, the snapshot callback) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=lloyd.boundary}` across the window. It
holds the wait for folds still running; what of it the device spends idle
is the `boundary` idle gap of the traced run. Nothing to read from a
program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="lloyd.boundary")
