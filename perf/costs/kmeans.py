"""Cost of the KMeans fold (`algo` "kmeans"): one chip's `n` rows of width
`d` against `k` centres into (sums, counts, cost) — the algorithm's work,
whatever implements it. Operations: 2·n·d·k for the distances, 2·n·k·d for
the one-hot sums, 3·n·d for the rest a row costs (its norm, the mask, the
assignment's compare). Bytes: the float32 rows read once (4·n·d) and the
(k, d) float32 sums read and written. At n = 65,536, d = 256, k = 100 that
is 6.8 GFLOP and 67 MB: 35 us of compute against 82 us of memory on a v5e,
so the fold is memory-bound where PCA's is compute-bound."""


def fold(config, rows_per_chip):
    n, d, k = rows_per_chip, config["n_cols"], config["k"]
    return 4.0 * n * d * k + 3.0 * n * d, 4.0 * n * d + 2.0 * 4.0 * k * d
