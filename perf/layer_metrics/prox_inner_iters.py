"""Model programs: products of the Hessian a proximal-Newton boundary takes
(`models/logistic_regression.py` `_stream_prox_newton_step_fn`: the power
iteration's that set the step size, then one a FISTA iteration of the
inner solve) — Δ`srml_logreg_prox_iterations_total` ÷
Δ`srml_xla_calls_total{fn=logreg.prox_newton_step}` across the window. The
inner solve runs to a tolerance, so this is what a boundary costs in reads
of the (d, d) Hessian. Nothing to read from a program without the counter
or the step."""

FN = "logreg.prox_newton_step"


def read(obs):
    steps = obs.counter_delta("srml_xla_calls_total", fn=FN)
    products = obs.counter_delta("srml_logreg_prox_iterations_total")
    return None if steps <= 0 or products <= 0 else products / steps
