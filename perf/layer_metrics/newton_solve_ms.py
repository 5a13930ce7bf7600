"""Model programs: mean milliseconds of the program's `newton.solve` span
(`models/logistic_regression.py` `LogisticRegressionJob.step`: the dispatch
of the step program `logreg.newton_step` — the (d, d) Newton system solved
on the device — until the step's length `delta` is on the host; the loss is
read before it, so the wait for the pass's folds is not in it) — Δsum ÷
Δcount of `srml_phase_duration_seconds{phase=newton.solve}` across the
window. A latency-bound solve: the device runs little else meanwhile.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="newton.solve")
