"""Median, over the fits whose model arrived inside the window, of:
`models.pca.finalize_pca_stats` called on the folded state → the model's
arrays (pc, explained variance, mean) in the caller's memory."""

import numpy as np


def read(obs):
    times = [f["finalize_s"] for f in obs.fits if f["end"] <= obs.window[1]]
    return float(np.median(times)) if times else None
