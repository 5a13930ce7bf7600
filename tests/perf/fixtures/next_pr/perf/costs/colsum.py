"""Cost of the `colsum` programs, from shapes. A fold adds one chip's rows
into the sums: one add an element, the rows read once, the sums read and
written. The scale divides d sums: one operation and two moves an element."""


def fold(config, rows_per_chip):
    d = config["n_cols"]
    return float(rows_per_chip * d), 4.0 * rows_per_chip * d + 2.0 * 4.0 * d


def scale(config):
    d = config["n_cols"]
    return float(d), 2.0 * 4.0 * d
