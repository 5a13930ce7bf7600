"""Seeded rows for the KMeans deployment, made on the device.

The law (the configuration's `assumed.data` states it): `k` Gaussian blobs
of unequal weight, blob j with weight proportional to (j + 1)^-1/2 (the
heaviest ten times the lightest at k = 100) and unit isotropic noise in all
`d` columns. The centres lie in a random `centre_dims`-dimensional subspace:
c_j = mean + B u_j with B (d, r) orthonormal and u_j uniform in a cube of
side `side`, so that a blob's nearest neighbours stand 2-4 noise standard
deviations away (neighbouring blobs overlap, far ones do not) — the side
that gives that for k points in r dimensions is 0.55 * side * k^(-1/r)
between nearest neighbours. The starting centres are `k` of the first
batch's rows, drawn without replacement: a plain random start, far from
the planted centres, so that Lloyd still moves centres after ten passes.

Imports nothing from the program: a later change to it cannot change the
rows a cell folds, nor where its fits start.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

CENTRE_DIMS = 4
#: nearest planted neighbours about three noise standard deviations apart
#: at k = 100 in four dimensions: 0.55 * 17 * 100^(-1/4) = 2.96
SIDE = 17.0


def spec(seed: int, d: int, k: int) -> Dict[str, np.ndarray]:
    """What is planted (host, tiny): the centres (k, d) float32 and the
    blobs' log-weights (k,)."""
    rng = np.random.default_rng(seed)
    basis, _ = np.linalg.qr(rng.standard_normal((d, CENTRE_DIMS)))
    u = rng.uniform(0.0, SIDE, size=(k, CENTRE_DIMS))
    mean = rng.uniform(-0.5, 0.5, size=d)
    weights = (np.arange(k) + 1.0) ** -0.5
    return {
        "centres": (mean + u @ basis.T).astype(np.float32),
        "log_weights": np.log(weights / weights.sum()).astype(np.float32),
    }


@functools.lru_cache(maxsize=None)
def _rows_fn(rows: int, sharding):
    import jax
    import jax.numpy as jnp

    def make(key, centres, log_weights):
        kl, kn = jax.random.split(key)
        label = jax.random.categorical(kl, log_weights, shape=(rows,))
        noise = jax.random.normal(kn, (rows, centres.shape[1]), jnp.float32)
        return centres[label] + noise

    return jax.jit(make, out_shardings=sharding)


def device_rows(planted: Dict[str, np.ndarray], seed: int, index: int, rows: int,
                sharding=None):
    """Batch `index` of the seeded stream: (rows, d) float32 on the device,
    one compiled program per (rows, sharding); the same seed and index give
    the same rows."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), index)
    return _rows_fn(rows, sharding)(key, planted["centres"], planted["log_weights"])


def start_centres(seed: int, first_batch: np.ndarray, k: int) -> np.ndarray:
    """Where every fit of the run starts, program and reference alike: `k`
    rows of the first batch, drawn without replacement from the seed."""
    rng = np.random.default_rng([seed, 1])
    pick = np.sort(rng.choice(first_batch.shape[0], size=k, replace=False))
    return np.ascontiguousarray(first_batch[pick], dtype=np.float32)
