"""Reference for `colsum`: float64 NumPy column sums on the host, each batch
weighted by how often a pass folds it. Imports nothing from the program."""

import numpy as np


def fit(batches, weights):
    total = sum(w * np.asarray(x, np.float64).sum(axis=0) for x, w in zip(batches, weights))
    rows = sum(w * x.shape[0] for x, w in zip(batches, weights))
    return {"colsum": total, "rows": rows, "mean": total / rows}
