"""On-mesh MapReduce primitives — the package's one collective layer.

DrJAX-style ``map_fn``/``reduce`` building blocks (PAPERS.md: DrJAX
2403.07128) over the data×model mesh (parallel/mesh.py): mapped
per-shard compute composes with named-axis reductions that lower to
``psum``/``all_gather``/``ppermute`` over ICI/DCN inside one compiled
SPMD program — the device-plane replacement for the reference's
JVM-serialized ``RDD.reduce`` hop (RapidsRowMatrix.scala:139).

EVERY collective in the package goes through these wrappers (test_lint's
``test_no_bare_collectives_outside_parallel`` enforces it, the mirror of
the bare-``jax.jit`` gate): a collective that bypasses this module is
invisible to the booking below and to anyone auditing what a program
moves over the interconnect. Booking happens at TRACE time — the
wrappers run once per compiled program, not per dispatch — so the
``srml_parallel_collective_traces_total`` counter reads as "collective
call sites traced, by kind and axis" (per-dispatch device cost lives in
the jit ledger, utils/xprof.py, which covers the whole program).

Not here: host-side cross-process gathers (``multihost_utils`` in
parallel/sharding.py) — those are control-plane allgathers of scalars,
not device-plane collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from spark_rapids_ml_tpu.utils import metrics as metrics_mod

__all__ = [
    "map_fn",
    "reduce_sum",
    "all_concat",
    "ring_shift",
    "reduce_topk",
]

_M_COLLECTIVE_TRACES = metrics_mod.counter(
    "srml_parallel_collective_traces_total",
    "Collective call sites traced into compiled programs, by kind "
    "(psum|all_gather|ppermute) and mesh axis",
)


def _book(kind: str, axis_name: str) -> None:
    _M_COLLECTIVE_TRACES.inc(kind=kind, axis=str(axis_name))


def map_fn(fn, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """Map ``fn`` over mesh shards (the DrJAX ``map_fn``): a named-axis
    SPMD region whose body may call the reduce primitives below. Thin
    veneer over the version-compat ``shard_map`` so call sites read as
    map/reduce pairs rather than sharding plumbing."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def reduce_sum(x, axis_name: str = DATA_AXIS):
    """Cross-shard sum over a mesh axis (lowers to ``psum`` on ICI/DCN).

    The workhorse reduce: Gram/moment partials, k-means statistics,
    Newton gradient/Hessian blocks all combine through this."""
    _book("psum", axis_name)
    return jax.lax.psum(x, axis_name)


def all_concat(x, axis_name: str = DATA_AXIS, *, axis: int = 0,
               tiled: bool = True):
    """Concatenate every shard's block along tensor dim ``axis`` (lowers
    to ``all_gather``): each device ends up holding the full axis —
    feature blocks for the 2-D Gram, per-shard top-k candidate pools."""
    _book("all_gather", axis_name)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ring_shift(x, axis_name: str, perm: Sequence[Tuple[int, int]]):
    """Rotate blocks around a mesh-axis ring (lowers to ``ppermute``):
    the pipelined alternative to ``all_concat`` when the gathered buffer
    would not fit — one block in flight per step (gram ring variant)."""
    _book("ppermute", axis_name)
    return jax.lax.ppermute(x, axis_name, perm)


def reduce_topk(dists, ids, k: int, axis_name: str = DATA_AXIS):
    """Merge per-shard ascending top-k candidate lists into the global
    top-k on every device: gather the (q, k_local) pools along the mesh
    axis, re-select k. Exact as long as each shard contributed its local
    top-min(k, shard_rows) — the union then contains the global winners
    (the knn merge property, models/knn.merge_topk's device-plane twin).
    Returns ``(dists (q, k) ascending, ids (q, k))``."""
    cand_d = all_concat(dists, axis_name, axis=1)
    cand_i = all_concat(ids, axis_name, axis=1)
    neg, pos = jax.lax.top_k(-cand_d, k)
    return -neg, jnp.take_along_axis(cand_i, pos, axis=1)
