"""Finalize: mean milliseconds of the program's `finalize.lapack` span
(`ops/eigh.py` `pca_from_gram_host`: `np.linalg.eigh` of the centred float64
Gram, the full spectrum, and nothing else) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.lapack}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.lapack")
