"""Finalize: mean milliseconds inside the program's own `eig finalize` span
(`models/pca.py` `finalize_pca_stats`: the state's copy to the host, the
centring and the float64 LAPACK `eigh`) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=eig finalize}` across the window. What
`finalize_s` has beyond it is the model's arrays made for the caller."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="eig finalize")
