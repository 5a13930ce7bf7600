"""The controls of the multinomial logistic cell's `correct`: the plain
reference (`reference/logreg_mn.py`) put in the program's place and
computed in the nearest precision below the one the configuration states,
which has two answers because the configuration states two precisions.
Every batch's rows are rounded to what the lower type holds before a scan,
by the binary cell's `reference/control_logreg.py` `lower`
(`jax.lax.reduce_precision`: a cast there and back is dropped by XLA on the
TPU):

- `float8_e4m3fn` (4/3): below the bfloat16 of the curvature products. The
  control of `pass0_hess_rel`.
- `bfloat16` (8/7): below the float32 of the gradient's and the loss's sums —
  what a fused fold that casts the rows once and takes every statistic from
  the cast tile would compute. Its curvature is the program's own by design,
  so it has to fail by `pass0_grad_rel` and may pass `pass0_hess_rel`.

Labels are class numbers in any precision, and the iterate stays float32
between passes, as the program keeps it. A comparison that lets either pass
would let a later PR fold rows of fewer bits unseen: each has to come out
NOT correct (tests/perf/test_perf_logreg_mn.py at a small size,
`perf/control_logreg_mn.py` on the chip at the cell's own). Imports nothing
from the program."""

from __future__ import annotations

import functools
import os

from perf.harness import layout

_BINARY = layout.load_module(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "reference", "control_logreg")
#: control → (exponent bits, mantissa bits) of the type its rows are rounded
#: to, and the rounding itself: the binary cell's (`reference/control_logreg.py`)
PRECISIONS = _BINARY.PRECISIONS
lower = _BINARY.lower


def fit(ref_logreg_mn, batches, start, max_iter, tol, reg, fit_intercept=True,
        precision: str = "float8_e4m3fn"):
    """`reference/logreg_mn.py` `fit` (handed over as a module) with every
    batch's rows rounded to `precision`, a batch at a time."""
    return ref_logreg_mn.fit(batches, start, max_iter, tol, reg, fit_intercept,
                             rounded=functools.partial(lower, precision=precision))
