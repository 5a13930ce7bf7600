"""Reference PCA: Gram and column sums in float32 at `highest`, batch by
batch, summed in float64 on the host with each batch's weight (how often
it was fed), then a float64 host `eigh`. Copied from
`chip_smoke.reference_pca`; explained variance has the reference
semantics sigma_i / sum(sigma)."""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

import numpy as np


def batch_moments():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def moments(x):
        with jax.default_matmul_precision("highest"):
            x = x.astype(jnp.float32)
            return x.T @ x, jnp.sum(x, axis=0)

    return moments


def fit(batches: Iterable, weights: Sequence[float], k: int) -> Dict[str, np.ndarray]:
    """`batches`: (rows, d) arrays (device or host) of one shape;
    `weights[i]`: how many times batch i entered the fit."""
    import jax

    moments = batch_moments()
    gram = colsum = None
    n = 0.0
    for x, w in zip(batches, weights):
        if not w:
            continue
        g, s = jax.device_get(moments(x))
        g, s = np.asarray(g, np.float64) * w, np.asarray(s, np.float64) * w
        gram = g if gram is None else gram + g
        colsum = s if colsum is None else colsum + s
        n += w * x.shape[0]
    mean = colsum / n
    w_, v = np.linalg.eigh(gram - np.outer(mean, colsum))
    w_, v = w_[::-1], v[:, ::-1]
    sigma = np.sqrt(np.clip(w_, 0.0, None))
    return {"pc": v[:, :k], "explained_variance": (sigma / sigma.sum())[:k],
            "mean": mean, "rows": n}
