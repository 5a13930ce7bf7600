"""Cost of the multinomial logistic MM-Newton fold (`algo` "logreg_mn"):
one chip's `n` rows of width `d`, with their class labels and mask, at a
fixed (W, b) into the exact gradient, C curvature blocks
Xᵀ diag(p_c) X with their borders, the loss and the row count — the
algorithm's work, whatever implements it, so that a fused fold is judged on
the same count. Operations: 2·C·n·d² for the C curvature blocks; 2·C·n·d
each for the logits X·W, the gradient Xᵀ(P − Y), and the weighting of the
rows by p_c with the border's column sums: 6·C·n·d. Bytes: the float32 rows
(4·n·d), labels (4·n) and mask (4·n) read once, and the C (d, d) float32
blocks read and written. At n = 524,288, d = 3000, C = 3 that is 2.83e13
operations and 6.50 GB: 143.7 ms of compute against 7.9 ms of memory on a
v5e, so the fold is compute-bound at one read of the rows."""


def fold(config, rows_per_chip):
    n, d, c = rows_per_chip, config["n_cols"], config["n_classes"]
    return (2.0 * c * n * d * d + 6.0 * c * n * d,
            4.0 * n * d + 8.0 * n + 2.0 * 4.0 * c * d * d)
