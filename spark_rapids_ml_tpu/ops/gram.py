"""Fused second-moment statistics: count / column-sum / Gram matrix.

This is the framework's hot loop, replacing the reference's per-partition
``dgemmCov`` cuBLAS AᵀA (rapidsml_jni.cu:109-127) *and* fixing its known gap:
mean-centering in the reference is a stubbed TODO pushed to upstream ETL
(RapidsRowMatrix.scala:111-117; SURVEY.md §2.4). Here every pass computes the
row count, the column sums, and the Gram matrix in one fused kernel, so a
centered Gram is available for free via G_c = G − n·μμᵀ — one extra rank-1
update instead of a second data pass.

Sharding: rows over the ``data`` mesh axis; partials combine with
``jax.lax.psum`` over ICI — the device-plane reduction the reference's JVM
``RDD.reduce`` (RapidsRowMatrix.scala:139) approximates, and the device-side
combiner its never-implemented ``accumulateCov`` intended (SURVEY.md §2.4).
A 2-D variant shards features over ``model`` as well, lifting the reference's
one-device covariance assumption (RapidsRowMatrix.scala:74-86).

Padded rows are masked out, so stats are exact for any row count.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

Stats = Tuple[jax.Array, jax.Array, jax.Array]  # (count, colsum, gram)

#: Per-device byte budget for a RESIDENT (d, d) Gram accumulator — the
#: ops/pallas_kernels.GRAM_COLSUM_VMEM_BUDGET idea generalized from one
#: kernel's VMEM tile to the fit path's device footprint: the accumulator
#: lives on device for the whole fit (donated streaming state, fused fit
#: program) alongside the row batches, so a width that blows this budget
#: must be SHARDED over the ``model`` axis, not attempted and OOMed.
#: Override via SRML_GRAM_DEVICE_BUDGET_MB (0 = unlimited).
GRAM_DEVICE_BUDGET_BYTES = (
    int(os.environ.get("SRML_GRAM_DEVICE_BUDGET_MB", 256)) << 20
)


class GramCapacityError(ValueError):
    """A (d, d) accumulator does not fit the per-device budget on this
    mesh — raised at fit entry instead of an opaque device OOM mid-pass."""


def require_gram_capacity(n_cols: int, mesh: Mesh, accum_dtype=None) -> bool:
    """Check the (d, d) accumulator against the per-device budget.

    Returns True when the fit MUST keep the Gram model-sharded end to end
    (the full matrix busts the budget but the per-device (d/n_model, d)
    slab fits — the docs/mesh.md model-parallel path); False when a
    replicated accumulator is fine. Raises :class:`GramCapacityError`
    when even the sharded slab is too big (grow ``mesh_model_axis``)."""
    _, ad = _dtypes()
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else ad
    if not GRAM_DEVICE_BUDGET_BYTES:
        return False
    n_model = mesh.shape.get(MODEL_AXIS, 1)
    full = n_cols * n_cols * ad.itemsize
    if full <= GRAM_DEVICE_BUDGET_BYTES:
        return False
    slab = -(-n_cols // n_model) * n_cols * ad.itemsize
    if slab > GRAM_DEVICE_BUDGET_BYTES:
        need = -(-full // GRAM_DEVICE_BUDGET_BYTES)
        raise GramCapacityError(
            f"the ({n_cols}, {n_cols}) {ad.name} Gram accumulator is "
            f"{full >> 20} MiB — over the {GRAM_DEVICE_BUDGET_BYTES >> 20} "
            f"MiB per-device budget even sharded {n_model}-way over the "
            f"'model' axis ({slab >> 20} MiB/device). Use a mesh with "
            f"mesh_model_axis >= {need} (docs/mesh.md 'Model-parallel "
            "Gram/eigh'), or raise SRML_GRAM_DEVICE_BUDGET_MB."
        )
    return True


def mm_precision(*dtypes):
    """Trace-time context: full-precision matmuls when any operand dtype is
    float32/float64.

    TPU's DEFAULT dot precision computes f32 contractions with single-pass
    bf16 mantissas, silently giving "float32 compute" only bf16 accuracy —
    for PCA that surfaces as eigenvector error ~ rounding/eigengap, percent
    level on close spectra. bfloat16 compute paths are unaffected by this
    context (there is no decomposition to control), so it costs nothing on
    the speed-oriented paths.
    """
    if any(
        d is not None and jnp.dtype(d) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64))
        for d in dtypes
    ):
        return jax.default_matmul_precision("float32")
    return contextlib.nullcontext()


def _dtypes():
    return jnp.dtype(config.get("compute_dtype")), jnp.dtype(config.get("accum_dtype"))


def _pallas_backend_ok(use_pallas: Optional[bool] = None) -> bool:
    """Shared Pallas-gate preamble: flag on (None = read config) + TPU backend."""
    if not (config.get("use_pallas") if use_pallas is None else use_pallas):
        return False
    return config.backend_is_tpu()


def _pallas_gram_applicable(shape, cd, ad, use_pallas: Optional[bool] = None) -> bool:
    """Pallas Gram path: TPU backend, f32 in/accum, tile-divisible shapes."""
    if not _pallas_backend_ok(use_pallas):
        return False
    n, d = shape
    return (
        jnp.dtype(cd) == jnp.float32
        and jnp.dtype(ad) == jnp.float32
        and n % 512 == 0
        and d % 256 == 0
    )


def local_stats(
    x: jax.Array,
    mask: Optional[jax.Array] = None,
    compute_dtype=None,
    accum_dtype=None,
    use_pallas: Optional[bool] = None,
) -> Stats:
    """Single-block fused stats. x: (m, d); mask: (m,) of {0,1} or None.

    The GEMM runs in ``compute_dtype`` (bfloat16 engages the MXU at full
    rate) and accumulates in ``accum_dtype`` via ``preferred_element_type``.
    With ``config.use_pallas`` on a TPU backend and tile-divisible shapes,
    the Gram uses the hand-tiled Pallas kernel (mask fused into the load).
    """
    cd, ad = _dtypes()
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else cd
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else ad
    xc = x.astype(cd)
    if mask is not None:
        xm = xc * mask.astype(cd)[:, None]
        # Integer sum: an f32 sum of ones saturates at 2^24 rows.
        count = jnp.sum(mask.astype(jnp.int32)).astype(ad)
    else:
        xm = xc
        count = jnp.asarray(x.shape[0], dtype=ad)
    colsum = jnp.sum(xm.astype(ad), axis=0)
    if mask is not None and _pallas_gram_applicable(x.shape, cd, ad, use_pallas):
        from spark_rapids_ml_tpu.ops.pallas_kernels import gram_pallas

        gram = gram_pallas(xc, mask.astype(cd))
    else:
        with mm_precision(cd):
            gram = jax.lax.dot_general(
                xm,
                xm,
                (((0,), (0,)), ((), ())),  # contract over rows: xᵀx
                preferred_element_type=ad,
            )
    return count, colsum, gram


def _stats_shard(x, mask, compute_dtype, accum_dtype, use_pallas=None):
    count, colsum, gram = local_stats(
        x,
        mask,
        compute_dtype=compute_dtype,
        accum_dtype=accum_dtype,
        use_pallas=use_pallas,
    )
    count = mr.reduce_sum(count, DATA_AXIS)
    colsum = mr.reduce_sum(colsum, DATA_AXIS)
    gram = mr.reduce_sum(gram, DATA_AXIS)
    return count, colsum, gram


def sharded_stats(mesh: Mesh, compute_dtype=None, accum_dtype=None):
    """Build a jitted fn(x_rowsharded, mask) -> replicated (count, colsum, gram).

    One compiled SPMD program: per-shard fused stats + psum over ``data``.
    """
    f = jax.shard_map(
        functools.partial(
            _stats_shard, compute_dtype=compute_dtype, accum_dtype=accum_dtype
        ),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        # pallas_call outputs (gram_pallas under float32 compute) carry no
        # VMA annotation; the psum-ed values are replicated.
        check_vma=False,
    )
    return ledgered_jit("gram.sharded_stats", f)


def _stats_shard_2d(x, mask, compute_dtype, accum_dtype):
    """2-D sharded stats: x block is (rows/data, d/model).

    all_gather the feature blocks along ``model`` so each device computes its
    (d/model, d) horizontal slab of the Gram; psum slabs over ``data``. The
    result stays feature-sharded — the full n×n never materializes on one
    device (the upgrade over RapidsRowMatrix.scala:74-86).
    """
    cd, ad = _dtypes()
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else cd
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else ad
    xc = x.astype(cd) * mask.astype(cd)[:, None]
    # (m_local, d_full) — ICI all-gather of feature blocks.
    x_full = mr.all_concat(xc, MODEL_AXIS, axis=1)
    count = mr.reduce_sum(jnp.sum(mask.astype(jnp.int32)).astype(ad), DATA_AXIS)
    colsum = mr.reduce_sum(jnp.sum(x_full.astype(ad), axis=0), DATA_AXIS)
    with mm_precision(cd):
        slab = jax.lax.dot_general(
            xc, x_full, (((0,), (0,)), ((), ())), preferred_element_type=ad
        )
    gram_slab = mr.reduce_sum(slab, DATA_AXIS)
    return count, colsum, gram_slab


def sharded_stats_2d(mesh: Mesh, compute_dtype=None, accum_dtype=None):
    """fn(x_2dsharded, mask) -> (count repl, colsum repl, gram model-sharded)."""
    f = jax.shard_map(
        functools.partial(
            _stats_shard_2d, compute_dtype=compute_dtype, accum_dtype=accum_dtype
        ),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P(MODEL_AXIS, None)),
        # count/colsum are value-replicated over `model` after the
        # all_gather, which VMA inference can't prove statically.
        check_vma=False,
    )
    return ledgered_jit("gram.sharded_stats_2d", f)


def _stats_shard_ring(x, mask, compute_dtype, accum_dtype, n_model):
    """Ring-collective 2-D sharded stats (the ring-attention pattern applied
    to the Gram): instead of all_gather-ing the full feature width onto
    every device (peak memory m_local×d, _stats_shard_2d), feature blocks
    rotate around the ``model``-axis ring via ``lax.ppermute``. Each step
    computes one (d_local, d_local) off-diagonal Gram block while the next
    block is in flight on ICI; peak extra memory is one block, and total
    comm equals the all_gather but pipelined. This is the long-feature
    analogue of sequence parallelism (SURVEY.md §5 "long-context": the
    reference has no such axis; here it is first-class).
    """
    cd, ad = _dtypes()
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else cd
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else ad
    xc = x.astype(cd) * mask.astype(cd)[:, None]
    d_local = x.shape[1]
    count = mr.reduce_sum(jnp.sum(mask.astype(jnp.int32)).astype(ad), DATA_AXIS)
    my_colsum = jnp.sum(xc.astype(ad), axis=0)  # (d_local,)
    colsum = mr.all_concat(my_colsum, MODEL_AXIS, axis=0)  # (d,) tiny
    colsum = mr.reduce_sum(colsum, DATA_AXIS)
    idx = jax.lax.axis_index(MODEL_AXIS)
    perm = [(i, (i + 1) % n_model) for i in range(n_model)]

    def block_at(s, slab, held):
        with mm_precision(cd):
            block = jax.lax.dot_general(
                xc, held, (((0,), (0,)), ((), ())), preferred_element_type=ad
            )  # (d_local, d_local): G[my_block, held_block]
        col = (((idx - s) % n_model) * d_local).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(slab, block, (jnp.int32(0), col))

    def body(s, carry):
        held, slab = carry
        slab = block_at(s, slab, held)
        held = mr.ring_shift(held, MODEL_AXIS, perm)
        return held, slab

    slab0 = jnp.zeros((d_local, n_model * d_local), dtype=ad)
    # n_model-1 (compute + permute) steps, then the final block without the
    # last permute — its result would be discarded, and the block is the
    # big (m_local, d_local) buffer this path exists to avoid moving.
    held, slab = jax.lax.fori_loop(0, n_model - 1, body, (xc, slab0))
    slab = block_at(n_model - 1, slab, held)
    gram_slab = mr.reduce_sum(slab, DATA_AXIS)
    return count, colsum, gram_slab


def sharded_stats_ring(mesh: Mesh, compute_dtype=None, accum_dtype=None):
    """fn(x_2dsharded, mask) -> (count repl, colsum repl, gram model-sharded),
    computed with the ppermute ring instead of all_gather."""
    n_model = mesh.shape[MODEL_AXIS]
    f = jax.shard_map(
        functools.partial(
            _stats_shard_ring,
            compute_dtype=compute_dtype,
            accum_dtype=accum_dtype,
            n_model=n_model,
        ),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P(MODEL_AXIS, None)),
        check_vma=False,
    )
    return ledgered_jit("gram.sharded_stats_ring", f)


_M_FOLD_PATH = metrics.counter(
    "srml_gram_fold_path_total",
    "Dispatches of the streaming Gram fold (gram.streaming_update) by the "
    "body their program was built with: path=fused (one HBM read of the "
    "batch through gram_colsum_pallas) or path=xla (CPU, float32 compute, "
    "widths off the lane grid, a (d, d) accumulator over the kernel's VMEM "
    "budget)",
)


def _fused_fold_applicable(shard_shape, cd, use_pallas: Optional[bool] = None) -> bool:
    """`streaming_update`'s gate for the one-read kernel, by what the code
    can observe: TPU backend, bfloat16 compute (float32 compute keeps
    `local_stats`: `gram_pallas` or XLA's full-precision dot), lane-aligned
    d, shard rows in multiples of 512 (the kernel picks its row block from
    d and the rows), and a (d, d) float32 accumulator inside the kernel's
    VMEM budget (constants imported from the kernel so the two cannot
    drift)."""
    if not _pallas_backend_ok(use_pallas):
        return False
    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        GRAM_COLSUM_ROW_MULTIPLE,
        GRAM_COLSUM_VMEM_BUDGET,
    )

    m, d = shard_shape
    return (
        jnp.dtype(cd) == jnp.dtype(jnp.bfloat16)
        and d % 128 == 0
        and m % GRAM_COLSUM_ROW_MULTIPLE == 0
        and d * d * 4 <= GRAM_COLSUM_VMEM_BUDGET
    )


def streaming_update(mesh: Mesh, compute_dtype=None, accum_dtype=None):
    """Jitted (state, x_batch, mask) -> state for out-of-HBM datasets.

    State (count, colsum, gram) lives replicated on device; host streams
    row-sharded batches in. Donation makes the accumulate in-place. This is
    the path for BASELINE.json config #2 (100M×2048 ≫ HBM) — the one fold
    of `fit_pca_stream`, the daemon's `PCAJob.fold` (PCA and the scaler
    fits that ride it) and the benchmark's PCA cells.

    Where `_fused_fold_applicable` holds for a shard's rows (TPU backend,
    bfloat16 compute, lane-aligned d, whole blocks, the accumulator inside
    VMEM) the batch is read from HBM ONCE:
    :func:`~spark_rapids_ml_tpu.ops.pallas_kernels.gram_colsum_pallas`
    casts each float32 tile to the compute dtype in VMEM and gives Gram,
    column sums and row count from that one read, and on a mesh with one
    data device and a float32 state the donated state is SEEDED into the
    kernel, so ``state += stats`` is the same dispatch. Elsewhere
    `local_stats`' XLA body runs: the only path a CPU, float32 compute or
    an odd width can take, and the tests' twin.

    **The mask contract.** The kernel takes the mask as a per-shard row
    count, ``sum(mask)``: **each shard's valid rows must be a prefix of
    the shard** (mask = ones, then zeros) — what `shard_rows`
    (`fit_pca_stream`) and the daemon's bucket padding (`_Job.fold`)
    produce: padding at the batch's tail, contiguous row sharding. Whole
    blocks of padding then skip their product. A mask with a hole is
    folded correctly by the XLA body only.
    """
    dcd, dad = _dtypes()
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else dcd
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else dad
    # use_pallas is read by the gates at trace time, so it must be part of
    # the cache key (same reason as _fit_fn's).
    return _streaming_update_cached(mesh, cd.name, ad.name, bool(config.get("use_pallas")))


@functools.lru_cache(maxsize=32)
def _streaming_update_cached(mesh: Mesh, compute_dtype, accum_dtype, use_pallas: bool):
    # Cached per (mesh, dtypes, pallas flag): returning a fresh jitted
    # closure per call would force a full XLA recompile for every job in a
    # long-lived daemon (jit caches are keyed on the function object). The
    # snapshot is threaded to the trace-time gates so a config flip between
    # builder call and first trace can't cache the wrong executable.
    cd = jnp.dtype(compute_dtype)
    ad = jnp.dtype(accum_dtype)
    n_data = mesh.shape[DATA_AXIS]
    # The seeded one-dispatch path folds the donated state INSIDE the
    # kernel, which is only correct when no cross-shard psum sits between
    # the partial and the state add — i.e. a single data device — and when
    # the state dtype is the kernel's f32 accumulator dtype.
    seed = n_data == 1 and ad == jnp.dtype(jnp.float32)

    def shard_update(count, colsum, gram, x, mask):
        if _fused_fold_applicable(x.shape, cd, use_pallas):
            from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

            n_valid = jnp.sum(mask.astype(jnp.int32))  # integer: exact past 2^24 rows
            if seed:
                g, s, c = gram_colsum_pallas(
                    x, n_valid, compute_dtype=cd.name, state=(gram, colsum, count)
                )
                return c, s, g
            g, s, _ = gram_colsum_pallas(x, n_valid, compute_dtype=cd.name)
            c, s, g = n_valid.astype(ad), s.astype(ad), g.astype(ad)
        else:
            c, s, g = local_stats(x, mask, compute_dtype, accum_dtype, use_pallas)
        c = mr.reduce_sum(c, DATA_AXIS)
        s = mr.reduce_sum(s, DATA_AXIS)
        g = mr.reduce_sum(g, DATA_AXIS)
        return count + c, colsum + s, gram + g

    f = jax.shard_map(
        shard_update,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,  # same as sharded_stats above
    )

    @functools.partial(ledgered_jit, "gram.streaming_update", donate_argnums=(0,))
    def update(state, x, mask):
        return f(state[0], state[1], state[2], x, mask)

    @functools.lru_cache(maxsize=None)
    def path(shape) -> str:
        fused = _fused_fold_applicable((shape[0] // n_data, shape[1]), cd, use_pallas)
        return "fused" if fused else "xla"

    # One count a dispatch, by the body the program of that batch shape was
    # built with: the gate's own predicate, asked once a shape.
    update.on_dispatch = lambda state, x, mask: _M_FOLD_PATH.inc(path=path(x.shape))
    return update


def init_stats(n_cols: int, accum_dtype=None) -> Stats:
    _, ad = _dtypes()
    ad = jnp.dtype(accum_dtype) if accum_dtype is not None else ad
    return (
        jnp.zeros((), dtype=ad),
        jnp.zeros((n_cols,), dtype=ad),
        jnp.zeros((n_cols, n_cols), dtype=ad),
    )


def finalize_gram(
    count: jax.Array,
    colsum: jax.Array,
    gram: jax.Array,
    mean_center: bool,
) -> Tuple[jax.Array, jax.Array]:
    """(count, colsum, gram) -> (G, mean).

    ``mean_center=True``: G = Σxxᵀ − n·μμᵀ, the Gram of centered data — the
    real fused fix for the reference's ETL-preprocess stub (SURVEY.md §2.4).
    ``False``: raw Gram, byte-for-byte the reference's ``cov.reduce(_+_)``
    semantics (RapidsRowMatrix.scala:139 — no centering, no normalization).
    """
    n = jnp.maximum(count, 1)
    mean = colsum / n
    if mean_center:
        g = gram - jnp.outer(mean, colsum)
    else:
        g = gram
    return g, mean
