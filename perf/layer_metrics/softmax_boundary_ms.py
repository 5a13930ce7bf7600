"""Daemon: mean milliseconds of the program's `softmax.boundary` span (named
by `models/logistic_regression.py` `LogisticRegressionJob.boundary_span` for
a job of more than two classes, opened by `serve/daemon.py` `_Job.step`:
from entry under the lock to the info dict — the loss's read, which is the
wait for the pass's folds, the C bordered solves (`softmax.solve`, its
child), the zero state, the snapshot) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=softmax.boundary}` across the window. It
holds the wait for folds still running; what of it the device spends idle
is the `boundary` idle gap of the traced run. Nothing to read from a
program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="softmax.boundary")
