"""Cost of the PCA fold (`algo` "pca"): one chip's rows into (count, colsum,
Gram) at the configuration's width. The arithmetic is
`perf/harness/cost.py` `pca_fold`."""

from perf.harness import cost


def fold(config, rows_per_chip):
    return cost.pca_fold(rows_per_chip, config["n_cols"])
