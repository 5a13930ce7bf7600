"""The controls of the logistic cell's `correct`: the plain reference
(`reference/logreg.py`) put in the program's place and computed in the
nearest precision below the one the configuration states, which has two
answers because the configuration states two precisions. Every batch's rows
are rounded to what the lower type holds before a scan, by
`jax.lax.reduce_precision` as `reference/control.py` rounds PCA's (a cast
there and back is dropped by XLA on the TPU):

- `float8_e4m3fn` (4/3): below the bfloat16 of the Hessian product. The
  control of `pass0_hess_rel`, and the one the other three limits were first
  read against.
- `bfloat16` (8/7): below the float32 of the gradient's and the loss's sums —
  what a fused fold that casts the rows once and takes every statistic from
  the cast tile would compute. Its Hessian is the program's own by design, so
  it has to fail by `pass0_grad_rel` and may pass the others.

Labels are 0 or 1 in any precision, and the iterate stays float32 between
passes, as the program keeps it. A comparison that lets either pass would
let a later PR fold rows of fewer bits unseen: each has to come out NOT
correct (tests/perf/test_perf_logreg.py at a small size,
`perf/control_logreg.py` on the chip at the cell's own). Imports nothing
from the program."""

from __future__ import annotations

import functools

#: control → (exponent bits, mantissa bits) of the type its rows are rounded to
PRECISIONS = {"float8_e4m3fn": (4, 3), "bfloat16": (8, 7)}


@functools.lru_cache(maxsize=None)
def _lower(exponent_bits: int, mantissa_bits: int):
    import jax

    return jax.jit(lambda x: jax.lax.reduce_precision(
        x, exponent_bits=exponent_bits, mantissa_bits=mantissa_bits))


def lower(x, precision: str = "float8_e4m3fn"):
    """`x` as `precision` holds it, round to nearest even (float8_e4m3fn:
    |x| < 448; the planted rows stay under 12)."""
    return _lower(*PRECISIONS[precision])(x)


def fit(ref_logreg, batches, start, max_iter, tol, reg, fit_intercept=True,
        precision: str = "float8_e4m3fn"):
    """`reference/logreg.py` `fit` (handed over as a module) with every
    batch's rows rounded to `precision`, a batch at a time."""
    return ref_logreg.fit(batches, start, max_iter, tol, reg, fit_intercept,
                          rounded=functools.partial(lower, precision=precision))
