"""Seeded rows and labels for the logistic deployment, made on the device.

The law (the configuration's `assumed.data` states it): a row is
x = z + L u with z standard normal in all `d` columns, u standard normal in
`RANK` dimensions and L (d, RANK) a seeded loading matrix with entries of
variance 1 / RANK — every column has variance about 2, half of it shared
through the low-rank part, so that the Hessian Xᵀ diag(p(1−p)) X is far
from a multiple of the identity (RANK eigenvalues near d / RANK + 1, the
rest near 1). A planted (w*, b*) is scaled so that the logits x·w* + b*
have standard deviation `LOGIT_SD`: the classes overlap and nothing is
separable. A label is Bernoulli of the sigmoid of its row's logit. Every
fit starts from a seeded NON-ZERO iterate whose logits have standard
deviation `START_SD`, in a direction of its own: the first pass's weights
p(1−p) are then not the constant 1/4 they are at w = 0.

Imports nothing from the program: a later change to it cannot change the
rows a cell folds, their labels, nor where its fits start.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

RANK = 8
LOGIT_SD = 2.0
START_SD = 0.5


def _scaled(direction: np.ndarray, loadings: np.ndarray, sd: float) -> np.ndarray:
    """`direction` scaled so that x·w has standard deviation `sd` under the
    law's covariance I + L Lᵀ."""
    var = float(direction @ direction + np.sum((loadings.T @ direction) ** 2))
    return direction * (sd / np.sqrt(var))


def spec(seed: int, d: int) -> Dict[str, np.ndarray]:
    """What is planted (host, small): the loadings (d, RANK) float32, the
    coefficients `w` (d,) float32 and the intercept `b` (float32 scalar)."""
    rng = np.random.default_rng(seed)
    loadings = rng.standard_normal((d, RANK)) / np.sqrt(RANK)
    w = _scaled(rng.standard_normal(d), loadings, LOGIT_SD)
    return {
        "loadings": loadings.astype(np.float32),
        "w": w.astype(np.float32),
        "b": np.float32(rng.uniform(-0.5, 0.5)),
    }


@functools.lru_cache(maxsize=None)
def _rows_fn(rows: int, sharding):
    import jax
    import jax.numpy as jnp

    def make(key, loadings, w, b):
        kz, ku, ky = jax.random.split(key, 3)
        with jax.default_matmul_precision("highest"):
            x = jax.random.normal(kz, (rows, loadings.shape[0]), jnp.float32)
            x = x + jax.random.normal(ku, (rows, loadings.shape[1]), jnp.float32) @ loadings.T
            p = jax.nn.sigmoid(x @ w + b)
        y = (jax.random.uniform(ky, (rows,), jnp.float32) < p).astype(jnp.float32)
        return x, y

    return jax.jit(make, out_shardings=sharding)


def device_rows(planted: Dict[str, np.ndarray], seed: int, index: int, rows: int,
                sharding=None) -> Tuple:
    """Batch `index` of the seeded stream: ((rows, d) float32 rows, (rows,)
    float32 labels in {0, 1}) on the device, one compiled program per
    (rows, sharding); the same seed and index give the same batch."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), index)
    return _rows_fn(rows, sharding)(key, planted["loadings"], planted["w"], planted["b"])


def start_iterate(seed: int, planted: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Where every fit of the run starts, program and reference alike:
    `w` (d,) float32 in a seeded direction of its own, scaled to logits of
    standard deviation `START_SD`, and `b` (1,) float32 in [-0.2, 0.2]."""
    rng = np.random.default_rng([seed, 1])
    loadings = planted["loadings"].astype(np.float64)
    w = _scaled(rng.standard_normal(loadings.shape[0]), loadings, START_SD)
    return {"w": w.astype(np.float32),
            "b": np.asarray([rng.uniform(-0.2, 0.2)], np.float32)}
