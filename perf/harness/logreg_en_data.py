"""Seeded rows and labels for the elastic-net logistic deployment, made on
the device.

The rows' law is the logistic deployment's (`harness/logreg_data.py`, the
configuration's `assumed.data` states it): x = z + L u, z standard normal in
all `d` columns, u standard normal in `RANK` dimensions, L a seeded (d, RANK)
loading matrix. What differs is what is planted: w* lies on a seeded
support of a tenth of the columns (300 of 3,000), standard normal there,
scaled so that the logits x·w* + b* have standard deviation `LOGIT_SD`; b*
is uniform in [-0.5, 0.5]. A label is Bernoulli of the sigmoid of its row's
logit. Every fit starts where the logistic deployment's do: a seeded DENSE
iterate (`logreg_data.start_iterate`), so that the first boundaries drive
the columns off the support to zero.

Imports nothing from the program: a later change to it cannot change the
rows a cell folds, their labels, nor where its fits start.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from perf.harness import logreg_data

RANK = logreg_data.RANK
LOGIT_SD = logreg_data.LOGIT_SD
#: the share of the columns w* is planted on
SUPPORT_SHARE = 0.1

device_rows = logreg_data.device_rows
start_iterate = logreg_data.start_iterate


def spec(seed: int, d: int) -> Dict[str, np.ndarray]:
    """What is planted (host, small): the loadings (d, RANK) float32, the
    coefficients `w` (d,) float32 — nonzero on `support` alone — the
    intercept `b` (float32 scalar) and `support`, the sorted planted
    columns."""
    rng = np.random.default_rng(seed)
    loadings = rng.standard_normal((d, RANK)) / np.sqrt(RANK)
    support = np.sort(rng.choice(d, size=max(1, int(round(SUPPORT_SHARE * d))),
                                 replace=False))
    direction = np.zeros(d)
    direction[support] = rng.standard_normal(len(support))
    var = float(direction @ direction + np.sum((loadings.T @ direction) ** 2))
    w = direction * (LOGIT_SD / np.sqrt(var))  # under the covariance I + L Lᵀ
    return {
        "loadings": loadings.astype(np.float32),
        "w": w.astype(np.float32),
        "b": np.float32(rng.uniform(-0.5, 0.5)),
        "support": support,
    }
