"""Cost of the logistic Newton fold (`algo` "logreg"): one chip's `n` rows
of width `d`, with their labels and mask, at a fixed (w, b) into the
gradient, the (d, d) weighted Gram Xᵀ diag(p(1−p)) X with its border, the
loss and the row count — the algorithm's work, whatever implements it, so
that a fused fold is judged on the same count. Operations: 2·n·d² for the
weighted Gram, 2·n·d each for the logits x·w, the gradient Xᵀr and the
weighting of the rows (with the border's column sums): 6·n·d. Bytes: the
float32 rows (4·n·d), labels (4·n) and mask (4·n) read once, and the (d, d)
float32 Hessian read and written. At n = 65,536, d = 1024 that is 137.8
GFLOP and 277 MB: 0.70 ms of compute against 0.34 ms of memory on a v5e, so
the fold is compute-bound at one read of the rows."""


def fold(config, rows_per_chip):
    n, d = rows_per_chip, config["n_cols"]
    return 2.0 * n * d * d + 6.0 * n * d, 4.0 * n * d + 8.0 * n + 2.0 * 4.0 * d * d
