"""The controls of the elastic-net logistic cell's `correct`: the plain
reference (`reference/logreg_en.py`) put in the program's place and
computed in the nearest precision below the one the configuration states —
two answers, as in the L2 cell (`reference/control_logreg.py`), because the
fold states two precisions: every batch's rows rounded, by
`jax.lax.reduce_precision`, to

- `float8_e4m3fn` (4/3): below the bfloat16 of the Hessian product;
- `bfloat16` (8/7): below the float32 of the gradient's and the loss's sums.

Labels are 0 or 1 in any precision, and the iterate stays float32 between
passes, as the program keeps it. The control's whole fit runs from the
common start; it is compared as a program's fit is: its first pass against
the reference's at the start, its first boundary against the reference's
from the start, its last boundary against the reference's from the iterate
the control's last pass started at. Each has to come out
NOT correct (tests/perf/test_perf_logreg_en.py at a small size,
`perf/control_logreg_en.py` on the chip at the cell's own). Imports nothing
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
    """`x` as `precision` holds it, round to nearest even."""
    return _lower(*PRECISIONS[precision])(x)


def fit(ref_logreg_en, batches, start, max_iter, tol, reg, alpha, fit_intercept=True,
        precision: str = "float8_e4m3fn"):
    """`reference/logreg_en.py` `fit` (handed over as a module) with every
    batch's rows rounded to `precision`, a batch at a time."""
    return ref_logreg_en.fit(batches, start, max_iter, tol, reg, alpha, fit_intercept,
                             rounded=functools.partial(lower, precision=precision))
