"""Seeded rows and class labels for the multinomial logistic deployment,
made on the device.

The law (the configuration's `assumed.data` states it): a row is
x = z + L u, as `harness/logreg_data.py` makes it (z standard normal in all
`d` columns, u standard normal in `RANK` dimensions, L a seeded (d, RANK)
loading matrix with entries of variance 1 / RANK), so that each class's
curvature block Xᵀ diag(p_c) X is far from a multiple of the identity and
still well conditioned. A planted (W*, b*): W* (d, C), each class's column a
seeded direction of its own scaled so that its logits x·w*_c have standard
deviation `LOGIT_SD`; b* (C,) offsets of at most `B_SPREAD` about 0, so
that every class holds near a C-th of the rows. A label is a draw from the
softmax of its row's logits x·W* + b* (the Gumbel-max draw of
`jax.random.categorical`): the classes overlap and nothing is separable.
Every fit starts from a seeded NON-ZERO iterate whose logits have standard
deviation `START_SD` a class, in directions of their own: the first pass's
class weights p_c then vary row by row.

The loadings, the scaling of a direction and the three constants are
`logreg_data`'s; nothing here is imported from the program, so a later
change to it cannot change the rows a cell folds, their labels, nor where
its fits start.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Tuple

import numpy as np

from perf.harness import layout

_BINARY = layout.load_module(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "harness", "logreg_data")
RANK = _BINARY.RANK
LOGIT_SD = _BINARY.LOGIT_SD
START_SD = _BINARY.START_SD
B_SPREAD = 0.25
_scaled = _BINARY._scaled


def _directions(rng, loadings: np.ndarray, n_classes: int, sd: float) -> np.ndarray:
    return np.stack([_scaled(rng.standard_normal(loadings.shape[0]), loadings, sd)
                     for _ in range(n_classes)], axis=1)


def spec(seed: int, d: int, n_classes: int) -> Dict[str, np.ndarray]:
    """What is planted (host, small): the loadings (d, RANK) float32, the
    coefficients `w` (d, C) float32 and the intercepts `b` (C,) float32."""
    rng = np.random.default_rng(seed)
    loadings = rng.standard_normal((d, RANK)) / np.sqrt(RANK)
    w = _directions(rng, loadings, n_classes, LOGIT_SD)
    b = rng.uniform(-B_SPREAD, B_SPREAD, n_classes)
    return {
        "loadings": loadings.astype(np.float32),
        "w": w.astype(np.float32),
        "b": (b - b.mean()).astype(np.float32),
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
            logits = x @ w + b
        y = jax.random.categorical(ky, logits, axis=1).astype(jnp.float32)
        return x, y

    return jax.jit(make, out_shardings=sharding)


def device_rows(planted: Dict[str, np.ndarray], seed: int, index: int, rows: int,
                sharding=None) -> Tuple:
    """Batch `index` of the seeded stream: ((rows, d) float32 rows, (rows,)
    float32 class labels in {0 … C−1}) on the device, one compiled program
    per (rows, sharding); the same seed and index give the same batch."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed), index)
    return _rows_fn(rows, sharding)(key, planted["loadings"], planted["w"], planted["b"])


def start_iterate(seed: int, planted: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Where every fit of the run starts, program and reference alike:
    `w` (d, C) float32, each class in a seeded direction of its own scaled
    to logits of standard deviation `START_SD`, and `b` (C,) float32 in
    [-0.2, 0.2]."""
    rng = np.random.default_rng([seed, 1])
    loadings = planted["loadings"].astype(np.float64)
    w = _directions(rng, loadings, planted["w"].shape[1], START_SD)
    return {"w": w.astype(np.float32),
            "b": rng.uniform(-0.2, 0.2, planted["w"].shape[1]).astype(np.float32)}
