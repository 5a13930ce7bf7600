"""Finalize: mean milliseconds of the program's `finalize.center` span
(`models/pca.py` `_finalize_on_host`: the float64 casts, the mean and
`gram − outer(mean, colsum)`) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.center}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.center")
