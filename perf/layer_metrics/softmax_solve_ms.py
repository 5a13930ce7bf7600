"""Model programs: mean milliseconds of the program's `softmax.solve` span
(`models/logistic_regression.py` `LogisticRegressionJob.step` for a job of
more than two classes: the dispatch of the step program
`logreg.softmax_newton_step` — one bordered Cholesky solve a class, vmapped,
on the device — until the step's length `delta` is on the host; the loss is
read before it, so the wait for the pass's folds is not in it) — Δsum ÷
Δcount of `srml_phase_duration_seconds{phase=softmax.solve}` across the
window. Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="softmax.solve")
