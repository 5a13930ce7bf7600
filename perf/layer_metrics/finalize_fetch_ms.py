"""Finalize: mean milliseconds of the program's `finalize.fetch` span
(`models/pca.py` `_finalize_on_host`: one `jax.device_get` of count, column
sums and the float32 Gram to host memory, nothing else) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=finalize.fetch}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="finalize.fetch")
