"""Finalize: mean milliseconds of the program's `finalize.lapack` span
(`ops/eigh.py` `pca_from_gram_host`: every LAPACK call on the centred float64
Gram and nothing else — since PR 25 `dsytrd` + `dsterf` + `dstemr` + `dormqr`
for the k kept vectors while k ≤ d/8, `np.linalg.eigh` above that or when a
step reports failure) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.lapack}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.lapack")
