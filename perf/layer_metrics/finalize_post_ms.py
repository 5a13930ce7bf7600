"""Finalize: mean milliseconds of the program's `finalize.post` span
(`ops/eigh.py` `pca_from_gram_host`: descending order, the `argmax|v|` sign
flip, `v * signs`, σ, the ratio and the top-k slice) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.post}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.post")
