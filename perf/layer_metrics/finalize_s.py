"""Finalize: median, over the fits whose model arrived inside the window, of
`models.pca.finalize_pca_stats` called on the folded state → the model's
arrays (pc, explained variance, mean) in the caller's memory; seconds, on
the benchmark's own clock around the call. Until PR 35 an end-to-end
metric: from run to run of one code it stands 5–11% apart (the finalizes of
a process settle at one of two speeds, or alternate: PERF.md §2), which no
bound of 10% or under holds, so the end-to-end metric the finalize moves is
`fit_rows_per_s`, the whole fit, and this number stands beside it."""

import numpy as np


def read(obs):
    times = [f["finalize_s"] for f in obs.fits if f["end"] <= obs.window[1]]
    return float(np.median(times)) if times else None
