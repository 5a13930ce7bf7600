"""Cost of one level pass of the histogram forest (`algo` "rf"): one chip's
`n` rows of width `d` into the frontier histograms of `T` trees, `B` bins,
`S` = 3 statistics — the ALGORITHM's work, whatever implements it, so that a
one-hot contraction, a scatter-add kernel and a fold over byte-binned rows
are judged on the same count and none can read over 100%.

Operations: every row adds its bag-weighted (count, y, y²) to one bin of
every feature of one node in every tree — n·T·d weighted accumulations of S
statistics, 2·n·T·d·S; a binary search of d values in B edges, n·d·log2(B);
and T·depth table reads a row to find its node. Bytes: ONE byte an element
of the rows (a bin id — the least any histogram method reads of a row at a
level; a program that reads float32 rows reads four and shows a lower
share, which is the truth), 12·n for label, bag key and mask, and the
frontier histogram read and written once a program — its size averaged
over the `max_depth` levels of a fit, (2^max_depth − 1) / max_depth node
widths of T·d·B·S float32, because the levels of a fit are one program name
in a trace and `pass_fold_device_ms` averages over them.

At n = 393,216, T = 30, d = 3000, B = 128, depth 6: 0.22 TFLOP against
1.18 GB + 2 × 1.45 GB = 4.09 GB: 1.1 ms of compute against 5.0 ms of memory
on a v5e — memory-bound, most of it the histogram itself."""

import math


def fold(config, rows_per_chip):
    n, d = rows_per_chip, config["n_cols"]
    trees, bins, depth = config["num_trees"], config["max_bins"], config["max_depth"]
    stats = 3
    mean_width = ((1 << depth) - 1) / depth
    frontier = 4.0 * trees * d * bins * stats * mean_width
    operations = 2.0 * n * trees * d * stats + n * d * math.log2(bins) + n * trees * depth
    return operations, 1.0 * n * d + 12.0 * n + 2.0 * frontier
