"""Finalize: mean milliseconds of the program's `finalize.center` span
(`models/pca.py` `_finalize_on_host`: one float64 cast of the Gram into an
array of the call's own, the mean, and the rank-1 centring update `dger`
writes into that array) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.center}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.center")
