"""Scale: mean milliseconds of the span `colsum.scale` (dispatch of the
scale program → the means on the host) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=colsum.scale}` across the window."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="colsum.scale")
