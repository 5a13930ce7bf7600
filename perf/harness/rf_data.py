"""Seeded rows and labels for the forest deployment, made on the device.

The law (the configuration's `assumed.data` states it): the shape of
scikit-learn's `make_regression`, which upstream's `gen_data.py regression`
wraps — every column standard normal, a linear target on `INFORMATIVE`
columns plus Gaussian noise — with the ground-truth coefficients chosen so
that a node's best split is identifiable: the informative columns are a
seeded choice, their weights fall by `DECAY` from `TOP_WEIGHT` (gains fall
by `DECAY`² from one column to the next, where `make_regression` draws
them uniform on (0, 100) and leaves near-ties), signs seeded, and the
noise has standard deviation `NOISE_SD`: a tenth of the strongest column's
effect, so that the gains of the informative columns stand apart from each
other and far above the thousands of noise columns'.

Imports nothing from the program: a later change to it cannot change the
rows a cell folds nor their labels.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

INFORMATIVE = 10
TOP_WEIGHT = 100.0
DECAY = 0.8
NOISE_SD = 10.0


def spec(seed: int, d: int) -> Dict[str, np.ndarray]:
    """What is planted (host, small): `columns` (INFORMATIVE,) int32, the
    informative columns, and `weights` (INFORMATIVE,) float32."""
    rng = np.random.default_rng(seed)
    k = min(INFORMATIVE, d)
    columns = rng.choice(d, size=k, replace=False)
    weights = TOP_WEIGHT * DECAY ** np.arange(k) * rng.choice([-1.0, 1.0], size=k)
    return {"columns": columns.astype(np.int32), "weights": weights.astype(np.float32)}


@functools.lru_cache(maxsize=None)
def _rows_fn(rows: int, d: int, sharding):
    import jax
    import jax.numpy as jnp

    def make(key, columns, weights):
        kx, kn = jax.random.split(key)
        x = jax.random.normal(kx, (rows, d), jnp.float32)
        y = jnp.sum(x[:, columns] * weights[None, :], axis=1)
        return x, y + NOISE_SD * jax.random.normal(kn, (rows,), jnp.float32)

    return jax.jit(make, out_shardings=sharding)


def device_rows(planted: Dict[str, np.ndarray], d: int, seed: int, index: int, rows: int,
                sharding=None) -> Tuple:
    """Batch `index` of the seeded stream: ((rows, d) float32 rows, (rows,)
    float32 labels) on the device, one compiled program per (rows, d,
    sharding); the same seed and index give the same batch."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), index)
    return _rows_fn(rows, d, sharding)(key, planted["columns"], planted["weights"])
