"""The control of the comparison that decides `correct`: the plain
reference put in the program's place and computed in the nearest precision
below the one the configuration states (bfloat16 compute → float8_e4m3fn):
each batch is rounded to that type and back, then folded by
`reference/pca.py` as it is. A comparison that lets the control pass would
let a later PR trade precision for speed unseen; it has to come out NOT
correct (tests/perf/test_perf_control.py at a small size, `perf/control.py`
on the chip at a cell's own size). Imports nothing from the program."""

from __future__ import annotations

from perf.reference import pca as ref_pca


def lower(x):
    """`x` as float8_e4m3fn holds it: 4 exponent bits, 3 of mantissa, round
    to nearest even — what a cast keeps of a value in the type's range
    (|x| < 448; the planted rows stay under 30). Not the cast itself: on
    the TPU XLA drops a float32 → float8 → float32 round trip (my chip run,
    PR 27: the cast read 0.0 off the reference on every seed)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def fit(batches, weights, k):
    """`reference/pca.py` `fit` over the batches rounded to float8, one at
    a time (a rounded batch lives only while it is folded)."""
    import jax

    rounded = jax.jit(lower)
    return ref_pca.fit((rounded(x) for x in batches), weights, k)
