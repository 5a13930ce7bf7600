"""The control of the KMeans cell's `correct`: the plain reference
(`reference/kmeans.py`) put in the program's place and computed in the
nearest precision below the one the configuration states (bfloat16 compute →
float8_e4m3fn): every batch, and the centres its distances are taken to,
are rounded to what that type holds before a scan — both operands, as the
program's fold casts both to its bfloat16 — by `jax.lax.reduce_precision`
4/3 as `reference/control.py` rounds PCA's (a cast there and back is
dropped by XLA on the TPU). Rounded centres move every boundary between two
blobs one way for all rows, which is what a fold in that precision does and
what rounding the rows alone hides (their errors cancel in the net flow). A comparison
that lets it pass would let a later PR trade precision for speed unseen: it
has to come out NOT correct (tests/perf/test_perf_kmeans.py at a small
size, `perf/control_kmeans.py` on the chip at the cell's own). Imports
nothing from the program."""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _lower():
    import jax

    return jax.jit(lambda x: jax.lax.reduce_precision(
        x, exponent_bits=4, mantissa_bits=3))


def lower(x):
    """`x` as float8_e4m3fn holds it (|x| < 448; the planted rows stay
    under 30): 4 exponent bits, 3 of mantissa, round to nearest even."""
    return _lower()(x)


def fit(ref_kmeans, batches, start, max_iter, tol):
    """`reference/kmeans.py` `fit` (handed over as a module) with batches
    and distance centres rounded to float8, a batch at a time."""
    return ref_kmeans.fit(batches, start, max_iter, tol, rounded=lower)
