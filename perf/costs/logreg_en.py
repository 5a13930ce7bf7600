"""Costs of the elastic-net logistic fit (`algo` "logreg_en").

`fold`: the pass's fold is the L2 logistic fold's (`costs/logreg.py`, the
same program): one chip's `n` rows of width `d` with their labels and mask
into the gradient, the (d, d) weighted Gram with its border, the loss and
the row count — 2·n·d² + 6·n·d operations; the float32 rows, labels and
mask read once and the (d, d) float32 Hessian read and written.

`prox_step`: the boundary's proximal-Newton step (`logreg.prox_newton_step`)
with `products` products of the Hessian — the power iteration's and one a
FISTA iteration, what `srml_logreg_prox_iterations_total` counts. A product
takes 2·d² operations. The least bytes the step needs from HBM are the
(d, d) float32 Hessian read ONCE: the chip holds it on the core across the
loop (on a v5e a product takes ~12 µs, a third of one read of the 36 MB
from HBM), so a product's own bytes are its vectors (a handful of length
d + 1), which stay beside it. Add the step's inputs and outputs, read and
written once: the gradient, the border and the iterate in, the iterate out,
8·d float32 words. At d = 3000 that is 36 MB, 44 µs of HBM on a v5e,
against 0.09 µs of bfloat16 peak compute a product: the step's roofline is
one read of its Hessian until it takes ~2,700 products."""


def fold(config, rows_per_chip):
    n, d = rows_per_chip, config["n_cols"]
    return 2.0 * n * d * d + 6.0 * n * d, 4.0 * n * d + 8.0 * n + 2.0 * 4.0 * d * d


def prox_step(config, products):
    d = config["n_cols"]
    return products * 2.0 * d * d, 4.0 * d * d + 4.0 * 8.0 * d
