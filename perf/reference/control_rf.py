"""The control of the forest cell's `correct`: the plain reference
(`reference/rf.py`) computed in the nearest precision below the one the
configuration states for the rows — float32 — which is bfloat16: every
batch's rows are rounded to what bfloat16 holds before anything is taken
from them, by `jax.lax.reduce_precision` as `reference/control_logreg.py`
rounds its rows (a cast there and back is dropped by XLA on the TPU). A row
value that lay within a bfloat16 step of a bin edge then falls in another
bin: the count channel moves, which no float32 fold of the true rows can
do. What a cache of bfloat16 rows, or a fold that cast the rows once before
binning them, would compute — a comparison that lets it pass would let a
later PR do so unseen. It has to come out NOT correct
(tests/perf/test_perf_rf.py at a small size, `perf/control_rf.py` on the
chip at the cell's own). Labels, edges and tables stay as they are. Imports
nothing from the program."""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _lower():
    import jax

    return jax.jit(lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7))


def lower(x):
    """`x` as bfloat16 holds it, round to nearest even, still float32."""
    return _lower()(x)
