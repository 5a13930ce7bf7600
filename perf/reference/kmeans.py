"""Reference KMeans: plain Lloyd in `jax.numpy`, float32 at `highest`
matmul precision. Distances are ‖x − c‖² written out, row block by row
block (no Gram trick, no mask, no padding, no cache); a batch's statistics
are summed in float64 on the host. The semantics are the program's
`fit_kmeans_stream`: `max_iter` Lloyd passes from the given start (an empty
cluster keeps its centre), a pass stopping early when no centre moved by
more than `tol`, then one cost-only scan at the final centres. Imports
nothing from `spark_rapids_ml_tpu`."""

from __future__ import annotations

import functools
from typing import Dict, Iterable

import numpy as np

BLOCK = 2048  # rows a distance block: (2048, k, d) float32 is 210 MB at k=100, d=256


@functools.lru_cache(maxsize=None)
def _batch_stats(k: int, block: int):
    import jax
    import jax.numpy as jnp

    def one_block(centres, x):
        with jax.default_matmul_precision("highest"):
            d2 = jnp.sum((x[:, None, :] - centres[None, :, :]) ** 2, axis=-1)
            near = jnp.argmin(d2, axis=1)
            onehot = jax.nn.one_hot(near, k, dtype=jnp.float32)
            return onehot.T @ x, jnp.sum(onehot, axis=0), jnp.sum(jnp.min(d2, axis=1))

    @jax.jit
    def stats(centres, x):
        x = x.astype(jnp.float32)
        whole = (x.shape[0] // block) * block
        parts = jax.lax.map(functools.partial(one_block, centres),
                            x[:whole].reshape(-1, block, x.shape[1]))
        out = tuple(jnp.sum(p, axis=0) for p in parts)
        if whole < x.shape[0]:
            out = tuple(a + b for a, b in zip(out, one_block(centres, x[whole:])))
        return out

    return stats


def scan(batches: Iterable, centres: np.ndarray, rounded=None):
    """One pass: (sums (k, d), counts (k,), cost) in float64, over every
    batch at fixed centres. `rounded`: a function applied, on the device, to
    each batch and to the centres the distances are taken to (the control's
    precision: what a fold computed in it would see of both); the centres
    themselves stay float32 between passes, as the program keeps them."""
    import jax

    k = centres.shape[0]
    sums = np.zeros(centres.shape, np.float64)
    counts = np.zeros((k,), np.float64)
    cost = 0.0
    c = jax.numpy.asarray(centres, jax.numpy.float32)
    if rounded is not None:
        c = rounded(c)  # both operands of a distance, as a fold in that precision has them
    for x in batches:
        if rounded is not None:
            x = rounded(x)
        s, n, j = jax.device_get(_batch_stats(k, min(BLOCK, x.shape[0]))(c, x))
        sums += np.asarray(s, np.float64)
        counts += np.asarray(n, np.float64)
        cost += float(j)
    return sums, counts, cost


def fit(batches, start: np.ndarray, max_iter: int, tol: float, rounded=None
        ) -> Dict[str, np.ndarray]:
    """`batches`: a re-scannable sequence of (rows, d) arrays (device or
    host). Returns the final `centers` (float32, as the program keeps
    them), the training `cost` at them, `n_iter`, `rows`, and `pass0`: the
    first pass's (sums, counts, cost) at the start."""
    centres = np.asarray(start, np.float32)
    pass0 = None
    n_iter = 0
    for it in range(max_iter):
        sums, counts, cost = scan(batches, centres, rounded)
        if pass0 is None:
            pass0 = {"sums": sums, "counts": counts, "cost": cost}
        new = np.where((counts > 0)[:, None],
                       sums / np.maximum(counts, 1)[:, None], centres).astype(np.float32)
        moved2 = float(np.max(np.sum((new.astype(np.float64) - centres) ** 2, axis=1)))
        centres = new
        n_iter = it + 1
        if moved2 <= float(tol) ** 2:
            break
    _, counts, cost = scan(batches, centres, rounded)
    return {"centers": centres, "cost": cost, "n_iter": n_iter,
            "rows": float(counts.sum()), "pass0": pass0}
