"""KMeans — Lloyd's algorithm as one compiled SPMD program.

Not present in the reference repo (PCA-only), but part of the capability
surface this framework targets (SURVEY.md §0 and BASELINE.json config #3:
"KMeans k=100 on 50M×256, pairwise-dist kernel + centroid allreduce over
ICI"). The architecture reuses the PCA frame (SURVEY.md §7 step 6): a
sharded partition kernel + psum + finalize.

TPU-first design decisions:

* The assignment step is one MXU GEMM (pairwise distances via the Gram
  trick, ops/distances.py), and the update step is another (one-hot
  assignments ᵀ @ points), so the whole Lloyd iteration is GEMM-bound.
* The ENTIRE Lloyd loop runs inside a single ``lax.while_loop`` under
  ``shard_map`` — centroids carry on device, per-iteration psums ride ICI,
  and nothing touches the host until convergence. This is the design the
  reference's per-task JNI-call pattern cannot express (SURVEY.md §3.4).
* Convergence = squared centroid movement ≤ tol², matching Spark MLlib's
  KMeans convergence criterion shape.
* Empty clusters keep their previous centroid (Spark behavior).

Init: "k-means++" on a host-side subsample (the classic D² weighting;
Spark's k-means|| is a distributed approximation of the same thing — for
the sizes where init dominates, the subsample bound keeps it O(sample·k·d)).
"random" picks k distinct rows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core.dataset import as_matrix, with_column
from spark_rapids_ml_tpu.core.params import (
    Estimator,
    HasFeaturesCol,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
    HasTol,
    Model,
    ParamDecl,
    ParamValidators,
    TypeConverters,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops.distances import sq_euclidean
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.sharding import pad_rows, shard_rows
from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


class KMeansSolution(NamedTuple):
    centers: np.ndarray  # (k, d)
    cost: float  # sum of squared distances to nearest center (training cost)
    n_iter: int
    n_rows: int


class KMeansSummary(NamedTuple):
    """Spark's KMeansSummary shape: trainingCost + iteration count."""

    trainingCost: float
    numIter: int
    k: int
    n_rows: int


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _kmeans_plus_plus(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    """Classic k-means++ D² seeding on a host subsample."""
    n = x.shape[0]
    sample = x if n <= 65536 else x[rng.choice(n, 65536, replace=False)]
    m = sample.shape[0]
    centers = np.empty((k, x.shape[1]), dtype=np.float64)
    centers[0] = sample[rng.integers(m)]
    d2 = np.sum((sample - centers[0]) ** 2, axis=1)
    for i in range(1, k):
        total = d2.sum()
        if total <= 0:
            centers[i:] = sample[rng.integers(m, size=k - i)]
            break
        probs = d2 / total
        centers[i] = sample[rng.choice(m, p=probs)]
        d2 = np.minimum(d2, np.sum((sample - centers[i]) ** 2, axis=1))
    return centers


def _random_init(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    idx = rng.choice(x.shape[0], size=k, replace=False)
    return np.asarray(x[idx], dtype=np.float64)


# ---------------------------------------------------------------------------
# Lloyd loop (one compiled program)
# ---------------------------------------------------------------------------


def _pallas_assign_applicable(m_local: int, k: int, d: int, cd, use_pallas=None) -> bool:
    """Fused Pallas assignment path: TPU backend, f32, tile-divisible, and a
    feature width whose (block_m, d) tile fits VMEM."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    if not _pallas_backend_ok(use_pallas):
        return False
    bm = min(1024, m_local)
    bk = min(128, k)
    return (
        jnp.dtype(cd) == jnp.float32
        and d <= 512
        and m_local % bm == 0
        and k % bk == 0
    )


def _lloyd_block_n(
    m_local: int, d: int, k_pad: int, itemsize: int, x_itemsize: Optional[int] = None
) -> int:
    """Largest row-block whose full kernel working set fits a conservative
    VMEM budget: double-buffered x tile + d2/onehot intermediates + the
    resident sums accumulator and centers block. ``x_itemsize`` is the
    width x arrives in where that is not the compute dtype's (float32 rows
    cast in the kernel): the tile is reckoned at that width, and its cast
    copy beside it."""
    from spark_rapids_ml_tpu.ops.pallas_kernels import LLOYD_STEP_BLOCK_N

    if x_itemsize is None:
        x_itemsize = itemsize
    for b in (16384, 8192, LLOYD_STEP_BLOCK_N, 2048, 1024, 512, 256, 128):
        if m_local % b:
            continue
        vmem = (
            2 * b * d * x_itemsize  # double-buffered x tile
            + (b * d * itemsize if x_itemsize != itemsize else 0)  # its cast
            + 2 * b * k_pad * 4  # d2 + onehot f32 intermediates
            + k_pad * d * (4 + itemsize)  # sums accumulator + centers
        )
        if vmem <= 64 * 2**20:
            return b
    return 0


def _pallas_step_applicable(
    m_local: int, k: int, d: int, cd, use_pallas=None, x_itemsize: Optional[int] = None
) -> bool:
    """Fused single-HBM-pass Lloyd step (ops/pallas_kernels.lloyd_step_pallas):
    TPU backend, bf16/f32 compute, lane-aligned d, block-divisible rows, and
    a full working set that fits VMEM (per _lloyd_block_n)."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    if not _pallas_backend_ok(use_pallas):
        return False
    from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to

    k_pad = _ceil_to(k, 128)
    cd = jnp.dtype(cd)
    return (
        cd in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        and d % 128 == 0
        and d <= 2048
        and k_pad <= 1024
        and _lloyd_block_n(m_local, d, k_pad, cd.itemsize, x_itemsize) > 0
    )


def _pad_centers(centers, k_pad: int, dtype):
    """(k, d) centers as the kernel's (k_pad, d) block in the compute dtype;
    the rows past k are zeros, and `lloyd_step_pallas` keeps them out of the
    argmin."""
    cpad = jnp.zeros((k_pad, centers.shape[1]), dtype)
    return jax.lax.dynamic_update_slice(cpad, centers.astype(dtype), (0, 0))


@functools.lru_cache(maxsize=32)
def _lloyd_fn(
    mesh: Mesh, k: int, max_iter: int, tol: float, cd: str, ad: str, use_pallas: bool = False
):
    # `use_pallas` is the builder-time snapshot, threaded to the trace-time
    # gates (never re-read config inside the trace — lru_cache key must
    # match what actually compiled).
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)
    from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to

    k_pad = _ceil_to(k, 128)

    def lloyd_shard(x, mask, centers0):
        # The cast feeds pallas_call inputs, so XLA materializes the bf16
        # copy once before the loop on its own (measured: forcing it with
        # an optimization_barrier is ~20% SLOWER — it pins the layout and
        # defeats a fusion XLA otherwise applies).
        xc = x.astype(compute_dtype)
        maskc = mask.astype(accum_dtype)
        pallas_assign = _pallas_assign_applicable(
            x.shape[0], k, x.shape[1], compute_dtype, use_pallas
        )
        pallas_step = _pallas_step_applicable(
            x.shape[0], k, x.shape[1], compute_dtype, use_pallas
        )
        # Valid rows are a contiguous prefix of each shard (shard_rows pads
        # at the global tail), so the mask collapses to one row count.
        # Integer sum: an f32 sum of ones saturates at 2^24 rows/shard.
        nv_local = jnp.sum(mask.astype(jnp.int32))

        def shard_stats(centers):
            """Per-shard (sums (k, d), counts (k,)) for one Lloyd update."""
            if pallas_step:
                from spark_rapids_ml_tpu.ops.pallas_kernels import lloyd_step_pallas

                # No cost from the kernel: the loop wants it at the FINAL
                # centers only, and on bfloat16 rows it is not free.
                sums, counts, _ = lloyd_step_pallas(
                    xc,
                    _pad_centers(centers, k_pad, compute_dtype),
                    nv_local,
                    k=k,
                    block_n=_lloyd_block_n(
                        x.shape[0], x.shape[1], k_pad, compute_dtype.itemsize
                    ),
                    with_cost=False,
                )
                return sums[:k].astype(accum_dtype), counts[:k].astype(accum_dtype)
            assign, _ = _assign_min(centers)
            onehot = (
                jax.nn.one_hot(assign, k, dtype=compute_dtype)
                * maskc[:, None].astype(compute_dtype)
            )
            # (k, d) sums and (k,) counts — both MXU/VPU friendly.
            from spark_rapids_ml_tpu.ops.gram import mm_precision

            with mm_precision(compute_dtype):
                sums = jax.lax.dot_general(
                    onehot, xc, (((0,), (0,)), ((), ())),
                    preferred_element_type=accum_dtype,
                )
            counts = jnp.sum(onehot.astype(accum_dtype), axis=0)
            return sums, counts

        def _assign_min(centers):
            if pallas_assign:
                from spark_rapids_ml_tpu.ops.pallas_kernels import (
                    assign_min_dist_pallas,
                )

                assign, part_d = assign_min_dist_pallas(
                    xc, centers.astype(compute_dtype)
                )
                x2 = jnp.sum(jnp.square(xc.astype(accum_dtype)), axis=1)
                min_d2 = jnp.maximum(part_d + x2, 0.0)
            else:
                d2 = sq_euclidean(
                    xc, centers.astype(compute_dtype), accum_dtype=accum_dtype
                )
                assign = jnp.argmin(d2, axis=1)
                min_d2 = jnp.min(d2, axis=1)
            return assign, min_d2

        def update(centers):
            sums, counts = shard_stats(centers)
            sums = mr.reduce_sum(sums, DATA_AXIS)
            counts = mr.reduce_sum(counts, DATA_AXIS)
            return jnp.where(
                (counts > 0)[:, None], sums / jnp.maximum(counts, 1)[:, None], centers
            )

        def cond(carry):
            _, moved2, it = carry
            return jnp.logical_and(it < max_iter, moved2 > tol * tol)

        def body(carry):
            centers, _, it = carry
            new_centers = update(centers)
            moved2 = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
            return new_centers, moved2, it + 1

        centers0 = centers0.astype(accum_dtype)
        init = (centers0, jnp.array(jnp.inf, accum_dtype), 0)
        centers, _, n_iter = jax.lax.while_loop(cond, body, init)
        # Final training cost at the converged centers (one assignment pass;
        # the in-loop fused kernel doesn't materialize distances at all).
        _, min_d2 = _assign_min(centers)
        final_cost = mr.reduce_sum(jnp.sum(min_d2 * maskc), DATA_AXIS)
        return centers, final_cost, n_iter

    f = jax.shard_map(
        lloyd_shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
        # pallas_call outputs carry no VMA annotation (same as ops/gram.py).
        check_vma=False,
    )
    return ledgered_jit("kmeans.lloyd", f)


def fit_kmeans(
    x: np.ndarray,
    k: int,
    max_iter: int = 20,
    tol: float = 1e-4,
    seed: int = 0,
    init: str = "k-means++",
    mesh: Optional[Mesh] = None,
) -> KMeansSolution:
    from spark_rapids_ml_tpu.parallel.sharding import require_single_process

    require_single_process("fit_kmeans (k-means++/random init samples local data)")
    mesh = mesh or default_mesh()
    x = np.asarray(x)
    n, d = x.shape
    if not 0 < k <= n:
        raise ValueError(f"k = {k} out of range (0, numRows = {n}]")
    rng = np.random.default_rng(seed)
    with trace_span("kmeans init"):
        if init == "k-means++":
            centers0 = _kmeans_plus_plus(x, k, rng)
        elif init == "random":
            centers0 = _random_init(x, k, rng)
        else:
            raise ValueError(f"unknown init mode {init!r} (k-means++|random)")
    with trace_span("lloyd"):
        xs, mask, n_true = shard_rows(x, mesh)
        fn = _lloyd_fn(
            mesh,
            k,
            max_iter,
            float(tol),
            config.get("compute_dtype"),
            config.get("accum_dtype"),
            use_pallas=bool(config.get("use_pallas")),
        )
        centers, cost, n_iter = jax.device_get(
            fn(xs, mask, jnp.asarray(centers0))
        )
    return KMeansSolution(
        centers=np.asarray(centers, dtype=np.float64),
        cost=float(cost),
        n_iter=int(n_iter),
        n_rows=n_true,
    )


# ---------------------------------------------------------------------------
# Streaming (out-of-HBM) Lloyd: one host scan per iteration
# ---------------------------------------------------------------------------


_M_FOLD_PATH = metrics.counter(
    "srml_kmeans_fold_path_total",
    "Dispatches of the streaming KMeans fold (kmeans.streaming_update and "
    "kmeans.streaming_update_group) by the body their program was built "
    "with: path=fused (one HBM read of the batch through "
    "lloyd_step_pallas) or path=xla (CPU, widths off the lane grid, shapes "
    "over the kernel's VMEM budget)",
)

_M_NEXT_STATE = metrics.counter(
    "srml_kmeans_next_state_total",
    "Next passes' opening states a KMeans job handed the daemon "
    "(next_pass_state): made=ahead (made by its step, in the one boundary "
    "program with the update) or made=on_demand (no step had made one: "
    "made when asked)",
)


#: Largest row-block of the streaming fold's kernel call. The fold is one
#: call a BATCH, and a call's first block is fetched with nothing to overlap
#: it, so the largest block that fits VMEM (`_lloyd_block_n`, right for the
#: in-memory fit's one call over all rows) is not the fastest here: 65,536 x
#: 256 float32 rows took 0.0967 ms a batch in 2,048-row blocks, 0.0987 at
#: 4,096, 0.1034 at 8,192 and 0.1061 at 1,024; 32,768 rows agree (PERF.md
#: §5, PR 29).
_STREAM_BLOCK_N = 2048


@functools.lru_cache(maxsize=32)
def _stream_shard_fn(mesh: Mesh, k: int, cd: str, ad: str, use_pallas: bool = False):
    """One batch's Lloyd statistics at fixed centers, sharded over the
    data axis and added to (sums, counts, cost): the fold's arithmetic,
    shared by the one-batch program and the grouped one below.

    Where `_pallas_step_applicable` holds for the shard's rows (TPU
    backend, lane-aligned d, a working set inside VMEM) the batch is read
    from HBM ONCE: `lloyd_step_pallas` casts each float32 tile to the
    compute dtype in VMEM and gives sums, counts and cost from the one
    distance product. It takes the mask as a row count, so **each shard's
    valid rows must be a prefix of the shard** — what `shard_rows` and the
    daemon's bucket padding produce (padding at the global tail, contiguous
    row sharding). Elsewhere the XLA body below runs: the only path a CPU
    or an odd width can take, and the tests' twin. `use_pallas` is the
    builder-time snapshot of the config flag (part of the cache key, never
    read inside the trace)."""
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)

    def fused_stats(centers, x, mask):
        from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to, lloyd_step_pallas

        k_pad = _ceil_to(k, 128)
        bs, bc, bcost = lloyd_step_pallas(
            x,
            _pad_centers(centers, k_pad, compute_dtype),
            jnp.sum(mask.astype(jnp.int32)),  # integer: exact past 2^24 rows
            k=k,
            block_n=min(
                _STREAM_BLOCK_N,
                _lloyd_block_n(
                    x.shape[0], x.shape[1], k_pad, compute_dtype.itemsize,
                    x.dtype.itemsize,
                ),
            ),
        )
        return (
            bs[:k].astype(accum_dtype),
            bc[:k].astype(accum_dtype),
            bcost.astype(accum_dtype),
        )

    def xla_stats(centers, x, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        xc = x.astype(compute_dtype)
        maskc = mask.astype(accum_dtype)
        d2 = sq_euclidean(
            xc, centers.astype(compute_dtype), accum_dtype=accum_dtype
        )
        assign = jnp.argmin(d2, axis=1)
        min_d2 = jnp.min(d2, axis=1)
        onehot = (
            jax.nn.one_hot(assign, k, dtype=compute_dtype)
            * maskc[:, None].astype(compute_dtype)
        )
        with mm_precision(compute_dtype):
            bs = jax.lax.dot_general(
                onehot, xc, (((0,), (0,)), ((), ())),
                preferred_element_type=accum_dtype,
            )
        bc = jnp.sum(onehot.astype(accum_dtype), axis=0)
        return bs, bc, jnp.sum(min_d2 * maskc)

    def shard(sums, counts, cost, centers, x, mask):
        fused = _pallas_step_applicable(
            x.shape[0], k, x.shape[1], compute_dtype, use_pallas, x.dtype.itemsize
        )
        bs, bc, bcost = (fused_stats if fused else xla_stats)(centers, x, mask)
        return (
            sums + mr.reduce_sum(bs, DATA_AXIS),
            counts + mr.reduce_sum(bc, DATA_AXIS),
            cost + mr.reduce_sum(bcost, DATA_AXIS),
        )

    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        # pallas_call outputs carry no VMA annotation (as in _lloyd_fn).
        check_vma=False,
    )


def _fold_path_counter(mesh: Mesh, k: int, cd: str, use_pallas: bool):
    """`on_dispatch` hook of the fold's two programs: one increment of
    `srml_kmeans_fold_path_total` a dispatch, under the path the program of
    that batch shape was built with — `_stream_shard_fn`'s own predicate,
    asked once a shape."""

    @functools.lru_cache(maxsize=None)
    def path(shape, dtype) -> str:
        fused = _pallas_step_applicable(
            shape[0] // mesh.shape[DATA_AXIS], k, shape[1], cd, use_pallas,
            jnp.dtype(dtype).itemsize,
        )
        return "fused" if fused else "xla"

    def count(state, centers, x, mask):
        first = x[0] if isinstance(x, tuple) else x  # a group is one shape
        _M_FOLD_PATH.inc(path=path(first.shape, first.dtype))

    return count


def _stream_step_fn(mesh: Mesh, k: int, cd: str, ad: str):
    """Jitted donated accumulate of one batch's Lloyd statistics at fixed
    centers: (state, centers, x, mask) -> state with
    state = (sums (k, d), counts (k,), cost ()).

    The running cost comes with the statistics on both of
    `_stream_shard_fn`'s paths — from the materialized (batch, k)
    distances on the XLA one, from inside the fused kernel on the TPU —
    so a scan is also the convergence monitor and the final cost scan.
    """
    return _stream_step_cached(mesh, k, cd, ad, bool(config.get("use_pallas")))


@functools.lru_cache(maxsize=32)
def _stream_step_cached(mesh: Mesh, k: int, cd: str, ad: str, use_pallas: bool):
    f = _stream_shard_fn(mesh, k, cd, ad, use_pallas)

    @functools.partial(ledgered_jit, "kmeans.streaming_update", donate_argnums=(0,))
    def update(state, centers, x, mask):
        return f(state[0], state[1], state[2], centers, x, mask)

    update.on_dispatch = _fold_path_counter(mesh, k, cd, use_pallas)
    return update


def _stream_group_fn(mesh: Mesh, k: int, cd: str, ad: str):
    """The same accumulate over a GROUP of device-resident batches in one
    program: (state, centers, xs, masks) -> state, `xs` and `masks` tuples
    of equal length. Batch by batch, in order, through the one shard
    function `_stream_step_fn` runs — the arithmetic of len(xs) calls of
    it, for one dispatch. For a caller that holds its batches already
    (the daemon's cached pass): at 65,536 x 256 rows a fold takes the
    device ~0.1 ms through the fused kernel (0.22 through the XLA body)
    and the host 0.3–0.4 ms to dispatch (PERF.md §5), so one program a
    batch leaves the device waiting for the host. One compiled program per
    (group length, batch shape)."""
    return _stream_group_cached(mesh, k, cd, ad, bool(config.get("use_pallas")))


@functools.lru_cache(maxsize=32)
def _stream_group_cached(mesh: Mesh, k: int, cd: str, ad: str, use_pallas: bool):
    f = _stream_shard_fn(mesh, k, cd, ad, use_pallas)

    @functools.partial(ledgered_jit, "kmeans.streaming_update_group",
                       donate_argnums=(0,))
    def update_group(state, centers, xs, masks):
        for x, mask in zip(xs, masks):
            state = f(state[0], state[1], state[2], centers, x, mask)
        return state

    update_group.on_dispatch = _fold_path_counter(mesh, k, cd, use_pallas)
    return update_group


def stream_zero_state(k: int, n_cols: int, accum_dtype) -> tuple:
    """Zero (sums, counts, cost) accumulator for one Lloyd pass — shared by
    fit_kmeans_stream and the data-plane daemon's iterative kmeans job."""
    ad = jnp.dtype(accum_dtype)
    return (
        jnp.zeros((k, n_cols), ad),
        jnp.zeros((k,), ad),
        jnp.zeros((), ad),
    )


def apply_lloyd_update(sums, counts, centers):
    """One Lloyd center update from a full pass's statistics.

    Empty clusters keep their previous centroid (Spark behavior). Returns
    (new_centers, moved² max over centers) — the single source of the
    update rule for both the in-process stream fit and the daemon.
    """
    new_centers = jnp.where(
        (counts > 0)[:, None], sums / jnp.maximum(counts, 1)[:, None], centers
    )
    moved2 = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
    return new_centers, moved2


def _lloyd_boundary_fn(mesh: Mesh):
    """One Lloyd pass boundary as ONE program: (sums, counts, centers) ->
    (new_centers, moved2, the next pass's zero (sums, counts, cost)).

    The update is :func:`apply_lloyd_update` as the module holds it when
    a job or a fit is made (the rule is part of the program's key), the
    zeros :func:`stream_zero_state`'s. Every output is replicated over
    `mesh`, as the fold's state is between two of its programs, so the
    fold meets no argument sharding a pass did not already give it. One
    dispatch, not one a primitive: on a cached pass the host's dispatches,
    not the device, set the boundary's length."""
    return _lloyd_boundary_cached(mesh, apply_lloyd_update)


@functools.lru_cache(maxsize=32)
def _lloyd_boundary_cached(mesh: Mesh, rule):
    @functools.partial(ledgered_jit, "kmeans.lloyd_boundary",
                       out_shardings=NamedSharding(mesh, P()))
    def boundary(sums, counts, centers):
        new_centers, moved2 = rule(sums, counts, centers)
        return new_centers, moved2, stream_zero_state(*sums.shape, sums.dtype)

    return boundary


class KMeansJob(JobAlgorithm):
    """Lloyd passes as a daemon job: the iterate is the (k, d) centers, a
    pass's statistics are (sums, counts, cost) at those centers, a pass
    boundary is :func:`apply_lloyd_update` (one program with the next
    pass's zeros, :func:`_lloyd_boundary_fn`). The centers come from a
    ``seed`` op, a pushed iterate, or the first unpartitioned batch."""

    name = "kmeans"
    iterative = True
    cacheable = True
    # what the device waits for between two passes — the update's one
    # dispatch, the wait for the pass's folds and the two scalars' landing
    # on the host, the snapshot
    boundary_span = "lloyd.boundary"
    no_iterate = {
        "staged_feed": (
            "partitioned kmeans feed before centers are seeded; "
            "send a 'seed' op from the driver first "
            "(deterministic init)"
        ),
        "get_iterate": "kmeans job has no centers yet (seed first)",
        "finalize": "finalize before any feed: no centers",
    }

    def __init__(self, n_cols, mesh, params):
        super().__init__(n_cols, mesh, params)
        self.k = int(params.get("k", 0))
        if self.k <= 0:
            raise ValueError("kmeans job needs params={'k': > 0} on first feed")
        self.seed_value = int(params.get("seed", 0))
        self.init = str(params.get("init", "k-means++"))
        if self.init not in ("k-means++", "random"):
            raise ValueError(f"unknown init {self.init!r} (k-means++|random)")
        self.centers = None
        cd, ad = config.get("compute_dtype"), config.get("accum_dtype")
        self._update = _stream_step_fn(mesh, self.k, cd, ad)
        self._update_group = _stream_group_fn(mesh, self.k, cd, ad)
        self._boundary = _lloyd_boundary_fn(mesh)
        # the next pass's zero state, made by `step` with the update
        self._ahead = None

    @classmethod
    def check_first_batch(cls, params, x):
        # a first batch smaller than k must not leave a centerless job
        # parked under the name (whose params later feeds would inherit)
        k_req = int(params.get("k", 0))
        if x.shape[0] < k_req:
            raise ValueError(
                f"first kmeans batch has {x.shape[0]} rows < k={k_req}; "
                f"feed a larger first batch (it seeds the centers)"
            )

    @property
    def installed(self):
        return self.centers is not None

    def check_seed(self, x):
        if x.shape[0] < self.k:
            raise ValueError(f"seed batch has {x.shape[0]} rows < k={self.k}")

    def seed(self, x):
        init_fn = _kmeans_plus_plus if self.init == "k-means++" else _random_init
        c0 = init_fn(x, self.k, np.random.default_rng(self.seed_value))
        self.centers = jnp.asarray(c0, self.accum)

    def iterate_arrays(self):
        return {"centers": np.asarray(jax.device_get(self.centers))}

    def install_iterate(self, arrays):
        c = np.asarray(arrays["centers"])
        if c.shape != (self.k, self.n_cols):
            raise ValueError(
                f"centers shape {c.shape} != ({self.k}, {self.n_cols})"
            )
        self.centers = jnp.asarray(c, self.accum)

    def zero_state(self):
        return stream_zero_state(self.k, self.n_cols, self.accum)

    def next_pass_state(self):
        ahead, self._ahead = self._ahead, None
        _M_NEXT_STATE.inc(made="on_demand" if ahead is None else "ahead")
        return self.zero_state() if ahead is None else ahead

    def fold(self, state, xs, ms, columns=(), n=0):
        return self._update(state, self.centers, xs, ms)

    def fold_group(self, state, xs, ms, columns=()):
        return self._update_group(state, self.centers, xs, ms)

    def step(self, state, params):
        """One boundary, one dispatch: the update and the next pass's zero
        state (kept for `next_pass_state`) queue behind the pass's folds,
        and both scalars are sent to the host as soon as the device has
        them, so the caller's read after its wait makes no round trip.
        `cost` is the fold's own array, not a program output: an output
        that only forwards an input may be that input anyway."""
        sums, counts, cost = state
        self.centers, moved2, self._ahead = self._boundary(sums, counts, self.centers)
        moved2.copy_to_host_async()
        cost.copy_to_host_async()
        return {"moved2": moved2, "cost": cost}

    def finalize(self, state, params, rows, iteration):
        _, _, cost = state
        return {
            "centers": np.asarray(jax.device_get(self.centers)),
            "cost": np.asarray([float(cost)]),
            "n_iter": np.asarray([iteration]),
        }


def fit_kmeans_stream(
    batch_source,
    k: int,
    n_cols: int,
    max_iter: int = 20,
    tol: float = 1e-4,
    seed: int = 0,
    init: str = "k-means++",
    mesh: Optional[Mesh] = None,
    checkpoint_path: Optional[str] = None,
    init_sample_rows: int = 65536,
) -> KMeansSolution:
    """Lloyd's algorithm over a re-scannable stream of host row-batches —
    the capacity path for datasets ≫ HBM (BASELINE.json config #3:
    50M×256 is 51 GB f32, beyond a single chip).

    ``batch_source`` is a CALLABLE returning a fresh iterator of (rows, d)
    arrays; each Lloyd iteration consumes one full scan (that re-scan
    requirement is what distinguishes iterative streaming from the
    single-pass PCA/LinReg accumulators). Per batch, assignment +
    centroid-partials run sharded on device and fold into a donated (k, d)
    accumulator; only the (k, d) centers live across scans. One extra scan
    at the end computes the exact training cost at the final centers
    (Spark ``summary.trainingCost`` semantics, matching the in-memory fit).

    With ``checkpoint_path``, centers are persisted after every iteration
    and an interrupted fit resumes at the saved iteration (the
    preemption-safety gap noted in SURVEY.md §5 "failure detection").

    **Multi-host** (``jax.process_count() > 1``): ``batch_source`` yields
    THIS process's local stream; scans run in lockstep
    (``lockstep_batches`` — uneven stream lengths are fine) and the init
    sample is assembled from every host's stream head (allgathered, f32),
    so all processes compute identical centers. Checkpoints are written
    by process 0 and must be on a shared filesystem to resume.
    """
    from spark_rapids_ml_tpu.core import checkpoint as ckpt
    from spark_rapids_ml_tpu.parallel.sharding import lockstep_batches

    multiproc = jax.process_count() > 1
    if k <= 0:
        raise ValueError(f"k = {k} must be > 0")
    if init not in ("k-means++", "random"):
        raise ValueError(f"unknown init mode {init!r} (k-means++|random)")
    mesh = mesh or default_mesh()
    cd, ad = config.get("compute_dtype"), config.get("accum_dtype")
    update = _stream_step_fn(mesh, k, cd, ad)
    boundary = _lloyd_boundary_fn(mesh)
    accum_dtype = jnp.dtype(ad)

    start_iter = 0
    centers = None
    restored = ckpt.load_state(checkpoint_path) if checkpoint_path else None
    if checkpoint_path:
        ckpt.require_consistent_visibility(restored)
    if restored is not None:
        arrays, meta = restored
        if meta.get("n_cols") != n_cols or meta.get("k") != k:
            raise ValueError(
                f"checkpoint at {checkpoint_path} is for k="
                f"{meta.get('k')}, n_cols={meta.get('n_cols')}, not ({k}, {n_cols})"
            )
        centers = np.asarray(arrays["centers"])
        start_iter = int(meta["it"])
    if centers is None:
        # Init on a bounded host sample drawn from the stream's head —
        # multi-host: every host contributes its share and the allgathered
        # global sample makes all processes compute IDENTICAL centers.
        rng = np.random.default_rng(seed)
        per = (
            -(-init_sample_rows // jax.process_count())
            if multiproc
            else init_sample_rows
        )
        head = []
        got = 0
        for batch in batch_source():
            head.append(np.asarray(batch))
            got += head[-1].shape[0]
            if got >= per:
                break
        local = (
            np.concatenate(head)[:per].astype(np.float32)
            if head
            else np.zeros((0, n_cols), np.float32)
        )
        if multiproc:
            from jax.experimental import multihost_utils as mhu

            counts = np.asarray(
                mhu.process_allgather(np.asarray([local.shape[0]]))
            ).reshape(-1)
            buf = np.zeros((per, n_cols), np.float32)
            buf[: local.shape[0]] = local
            gathered = np.asarray(mhu.process_allgather(buf))
            sample = np.concatenate(
                [gathered[p, : counts[p]] for p in range(len(counts))]
            )
        else:
            sample = local
        if sample.shape[0] == 0:
            raise ValueError("batch_source yielded no batches")
        if k > sample.shape[0]:
            raise ValueError(
                f"k = {k} exceeds the {sample.shape[0]}-row init sample; "
                f"raise init_sample_rows"
            )
        with trace_span("kmeans init"):
            centers = (
                _kmeans_plus_plus(sample, k, rng)
                if init == "k-means++"
                else _random_init(sample, k, rng)
            )

    def scan(centers_dev, state):
        n_rows = 0
        for batch in lockstep_batches(batch_source(), n_cols):
            # shard_rows pads, casts f64→f32 via the threaded native bridge
            # (halving host→device bytes for f64 sources), and places;
            # multi-process it assembles the global array from local rows.
            xs, ms, n_b = shard_rows(np.asarray(batch), mesh, dtype=np.float32)
            n_rows += n_b
            state = update(state, centers_dev, xs, ms)
        return state, n_rows

    n_true = 0
    n_iter = start_iter
    centers_dev = jnp.asarray(centers, accum_dtype)
    zeros = stream_zero_state(k, n_cols, accum_dtype)
    with trace_span("lloyd-stream"):
        for it in range(start_iter, max_iter):
            (sums, counts, _), n_true = scan(centers_dev, zeros)
            centers_dev, moved2, zeros = boundary(sums, counts, centers_dev)
            moved2 = float(moved2)
            n_iter = it + 1
            if checkpoint_path and (not multiproc or jax.process_index() == 0):
                ckpt.save_state(
                    checkpoint_path,
                    {"centers": np.asarray(jax.device_get(centers_dev))},
                    {"it": n_iter, "k": k, "n_cols": n_cols},
                )
            if moved2 <= float(tol) ** 2:
                break
        # Exact cost at the final centers (one cost-only scan).
        (_, _, cost), n_true = scan(centers_dev, zeros)
    if checkpoint_path and (not multiproc or jax.process_index() == 0):
        import os

        if os.path.exists(checkpoint_path):
            os.unlink(checkpoint_path)
    return KMeansSolution(
        centers=np.asarray(jax.device_get(centers_dev), dtype=np.float64),
        cost=float(cost),
        n_iter=n_iter,
        n_rows=n_true,
    )


# ---------------------------------------------------------------------------
# Estimator / Model
# ---------------------------------------------------------------------------


class _KMeansParams(HasFeaturesCol, HasPredictionCol, HasMaxIter, HasTol, HasSeed):
    k = ParamDecl(
        "k",
        "number of clusters (> 0)",
        TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )
    initMode = ParamDecl(
        "initMode",
        "initialization: k-means++ | random",
        TypeConverters.toString,
        validator=ParamValidators.inList(["k-means++", "random"]),
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(
            k=2,
            maxIter=20,
            tol=1e-4,
            seed=0,
            initMode="k-means++",
            featuresCol="features",
            predictionCol="prediction",
        )

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getInitMode(self) -> str:
        return self.getOrDefault(self.initMode)


class KMeans(Estimator, _KMeansParams, MLWritable, MLReadable):
    """``KMeans().setK(100).fit(df)`` — Spark ML clustering API shape."""

    _uid_prefix = "KMeans"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setK(self, value: int) -> "KMeans":
        return self._set(k=value)

    def setInitMode(self, value: str) -> "KMeans":
        return self._set(initMode=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "KMeansModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        sol = fit_kmeans(
            x,
            k=self.getK(),
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            seed=self.getSeed(),
            init=self.getInitMode(),
            mesh=self._mesh,
        )
        model = KMeansModel(centers=sol.centers)
        model.uid = self.uid
        model._training_cost = sol.cost
        model._n_iter = sol.n_iter
        model._summary = KMeansSummary(
            trainingCost=sol.cost, numIter=sol.n_iter, k=self.getK(), n_rows=sol.n_rows
        )
        self._copy_params_to(model)
        return model


class KMeansModel(Model, _KMeansParams, MLWritable, MLReadable):
    """Fitted centers + predict(); ``summary.trainingCost`` equivalent."""

    _uid_prefix = "KMeansModel"

    def __init__(self, centers: Optional[np.ndarray] = None, uid=None):
        super().__init__(uid=uid)
        self.centers = None if centers is None else np.asarray(centers)
        self._training_cost: Optional[float] = None
        self._n_iter: Optional[int] = None
        self._summary: Optional[KMeansSummary] = None
        self._predict_cache: dict = {}

    @property
    def summary(self) -> Optional[KMeansSummary]:
        return self._summary

    @property
    def hasSummary(self) -> bool:
        return self._summary is not None

    def clusterCenters(self) -> np.ndarray:
        return self.centers

    @property
    def trainingCost(self) -> Optional[float]:
        return self._training_cost

    def _model_data(self):
        return {"clusterCenters": self.centers}

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(centers=data["clusterCenters"], uid=uid)

    def _copy_extra_state(self, source):
        self.centers = source.centers
        self._training_cost = source._training_cost
        self._n_iter = source._n_iter
        self._summary = getattr(source, "_summary", None)
        self._predict_cache = {}

    def _predictor(self):
        key = (config.get("compute_dtype"), config.get("accum_dtype"))
        if key not in self._predict_cache:
            centers_dev = jnp.asarray(self.centers, dtype=jnp.dtype(key[0]))
            accum = jnp.dtype(key[1])

            @ledgered_jit("kmeans.predict")
            def predict(x):
                d2 = sq_euclidean(x.astype(centers_dev.dtype), centers_dev, accum_dtype=accum)
                return jnp.argmin(d2, axis=1).astype(jnp.int32)

            self._predict_cache[key] = predict
        return self._predict_cache[key]

    def predict(self, x: np.ndarray) -> np.ndarray:
        from spark_rapids_ml_tpu.parallel.sharding import run_bucketed

        return run_bucketed(self._predictor(), x)

    # Daemon serving contract (serve/daemon.py).
    _serve_algo = "kmeans"
    _serve_outputs = (("prediction", "predictionCol", "int"),)

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py; see PCAModel's)."""
        if self.centers is None:
            return None
        d = int(np.asarray(self.centers).shape[1])
        if int(n_cols) != d:
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"model's fitted width {d}"
            )
        from spark_rapids_ml_tpu.parallel.sharding import bucket_rows

        return [(
            self._predictor(),
            (jax.ShapeDtypeStruct(
                (bucket_rows(int(n_rows)), d), jnp.dtype(dtype)
            ),),
        )]

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed device transform (daemon ``transform`` op surface)."""
        if self.centers is None:
            raise RuntimeError("KMeansModel has no centers (unfitted?)")
        with trace_span("kmeans transform"):
            return {"prediction": self.predict(x)}

    def _transform(self, dataset):
        if self.centers is None:
            raise RuntimeError("KMeansModel has no centers (unfitted?)")
        x = as_matrix(dataset, self.getFeaturesCol())
        return with_column(dataset, self.getPredictionCol(), self.predict(x))
