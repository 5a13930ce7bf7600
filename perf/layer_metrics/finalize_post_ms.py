"""Finalize: mean milliseconds of the program's `finalize.post` span
(`ops/eigh.py` `pca_from_gram_host`: on the k kept columns, descending order
and the `argmax|v|` sign flip; σ over all d and the ratio) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.post}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.post")
