"""Seeded data, made on the device.

`pca_spec` and the row generator are copies of `chip_smoke.planted_data`,
with the large arrays drawn on the device; a later change to the program
cannot change the rows a cell folds.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np


# -- what is planted (host, tiny) ---------------------------------------------


def pca_spec(seed: int, d: int, k: int) -> Dict[str, np.ndarray]:
    """k planted orthonormal directions with standard deviations 32·0.93^i
    (covariance eigenvalues ≈ 1025 … 12.6, adjacent ratio 0.865) over a unit
    noise floor, plus a small column mean so that centring does work."""
    rng = np.random.default_rng(seed)
    basis, _ = np.linalg.qr(rng.standard_normal((d, k)))
    return {
        "basis": basis.astype(np.float32),
        "scale": (32.0 * 0.93 ** np.arange(k)).astype(np.float32),
        "mean": rng.uniform(-0.5, 0.5, size=d).astype(np.float32),
    }


# -- rows (device) -------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _pca_rows_fn(rows: int, sharding):
    import jax
    import jax.numpy as jnp

    def make(key, basis, scale, mean):
        kz, kn = jax.random.split(key)
        z = jax.random.normal(kz, (rows, basis.shape[1]), jnp.float32) * scale
        x = jnp.matmul(z, basis.T, precision="highest")
        x = x + jax.random.normal(kn, (rows, basis.shape[0]), jnp.float32)
        return x + mean

    return jax.jit(make, out_shardings=sharding)


def device_rows(spec: Dict[str, np.ndarray], seed: int, index: int, rows: int,
                sharding=None):
    """Batch `index` of the seeded stream of `pca_spec` rows: (rows, d)
    float32 on the device, one compiled program per (rows, sharding)."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), index)
    return _pca_rows_fn(rows, sharding)(
        key, spec["basis"], spec["scale"], spec["mean"])
