"""Pallas TPU kernels for the hot ops.

The reference's hand-written device code is a Thrust sign-flip kernel and
cuBLAS GEMM calls (rapidsml_jni.cu). On TPU, XLA already fuses the
mask-multiply + GEMM + accumulate chain well, so Pallas here targets the
places hand-tiling pays:

* ``gram_pallas`` — tiled XᵀX with the mask fused into the load (float32
  compute: ``ops/gram.local_stats``).
* ``gram_colsum_pallas`` — the PCA fold on the chip: count, column sums
  and XᵀX from ONE read of the batch, the (d, d) accumulator VMEM-resident
  and seedable from the donated state. Called by ``ops/gram.streaming_update``
  where its gate holds (``fit_pca_stream``, the daemon's ``PCAJob.fold``,
  the scaler fits that ride it); float32 rows are cast to the compute
  dtype in VMEM. The mask is a row count: each shard's valid rows are a
  prefix of the shard.
* ``assign_min_dist_pallas`` / ``lloyd_step_pallas`` — KMeans assignment
  (+ fused centroid-sum update and cost): distance tile + argmin fused,
  never materializing the (m, k) distance matrix in HBM; the latter also
  casts float32 rows to the compute dtype in VMEM and serves the
  streaming fold (models/kmeans.py ``_stream_shard_fn``) as well as the
  in-memory fit.
* ``newton_stats_pallas`` — one-HBM-pass binomial Newton statistics of the
  in-memory fit (bfloat16 rows, lane-aligned d).
* ``newton_fold_pallas`` — the streaming Newton fold on the chip: one
  batch's gradient, loss, border and row count in float32 and its
  bfloat16 Hessian product from ONE read of the float32 rows, at any width
  (the batch read transposed, as the chip keeps it; lane padding in VMEM),
  the (dp, dp) accumulator VMEM-resident and seedable. Called by
  ``models/logistic_regression.py`` ``_stream_grad_hess_shard_fn`` where
  its gate holds (``fit_logistic_stream``, the daemon's
  ``LogisticRegressionJob``).
* ``hist_onehot_matmul_pallas`` — the forests' histogram contraction on
  the chip: a chunk's int8 operand times the bin one-hot of a feature
  block, the one-hot made tile by tile in VMEM from the bin ids (never in
  HBM, nor inside an XLA fusion), int8 into int32 at the MXU's int8 rate.
  Called by ``ops/histogram.py`` ``hist_update_group_fn`` where its gate
  holds (both forests' in-memory fit, the daemon's ``RandomForestJob``).
* ``ivf_scan_select_pallas`` — IVF bucketed scan: per-list residual GEMM
  + exact packed-key top-k selection, scores VMEM-resident (gated by
  ``config.ann_fused_scan``, not ``use_pallas``).

All are gated with the XLA path as the default/fallback; parity is tested
in interpret mode on CPU (tests/test_pallas.py) so the kernels stay
correct even when no TPU is attached.

See /opt/skills/guides/pallas_guide.md for the tiling constraints used
here (f32 min tile (8, 128); MXU 128×128).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _dot_prec(dt):
    """Mosaic, like XLA, defaults f32 dots to single-pass bf16 mantissas on
    TPU; request full precision for f32 operands. bf16 operands pin
    DEFAULT explicitly — an ambient ``mm_precision`` HIGHEST context would
    otherwise make Mosaic attempt an f32x3 decomposition of a bf16 lhs
    ("Bad lhs type")."""
    return (
        jax.lax.Precision.HIGHEST
        if jnp.dtype(dt) == jnp.float32
        else jax.lax.Precision.DEFAULT
    )


# ---------------------------------------------------------------------------
# Tiled Gram: G = (X·mask)ᵀ (X·mask), accumulated in float32
# ---------------------------------------------------------------------------


def _gram_kernel(x_i_ref, x_j_ref, mask_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    m = mask_ref[:]  # (bn, 1) — 2-D: 1-D operands trip an XLA↔Mosaic
    # layout mismatch on real TPUs (T(1024) vs T(512) tiling)
    xi = x_i_ref[:] * m
    xj = x_j_ref[:] * m
    o_ref[:] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())), preferred_element_type=o_ref.dtype,
        precision=_dot_prec(xi.dtype),
    )


@functools.partial(
    ledgered_jit, "pallas.gram_pallas", static_argnames=("block_n", "block_d", "interpret")
)
def gram_pallas(
    x: jax.Array,
    mask: jax.Array,
    block_n: int = 512,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Masked Gram XᵀX of an (n, d) block, float32 accumulate.

    n must divide block_n and d divide block_d (callers pad; shard_rows
    already zero-pads rows and the mask kills padding contributions).
    """
    n, d = x.shape
    bn = min(block_n, n)
    bd = min(block_d, d)
    if n % bn or d % bd:
        raise ValueError(f"shape ({n},{d}) not divisible by blocks ({bn},{bd})")
    grid = (d // bd, d // bd, n // bn)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bn, bd), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bn, 1), lambda i, j, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((bd, bd), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
    )(x, x, mask.reshape(n, 1))  # x twice: (kk, i) and (kk, j) row-tile views


# ---------------------------------------------------------------------------
# Single-pass fused Gram + column-sum with a VMEM-resident accumulator
# ---------------------------------------------------------------------------


# Shared with the fold's gate (ops/gram.py `_fused_fold_applicable`), so the
# two cannot drift.
GRAM_COLSUM_VMEM_BUDGET = 64 * 2**20  # max (d, d) f32 resident accumulator
GRAM_COLSUM_ROW_MULTIPLE = 512  # the fold's gate: shard rows in multiples of this
GRAM_COLSUM_TILE_BYTES = 4 * 2**20  # a row block's float32 tile
GRAM_COLSUM_MAX_BLOCK_N = 2048


def gram_colsum_block_n(d: int, n: int) -> int:
    """The kernel's row block for n rows of width d: the largest power of
    two that divides n and whose (block, d) float32 tile is within 4 MiB,
    at most 2,048 rows — 512 at d = 2048. Chosen on a v5e from a scratch
    loop of 65,536-row folds onto a seeded state (host clock over 60 folds,
    PERF.md §6, PR 31): at d = 2048 a fold took 2.923 ms in 512-row blocks,
    2.935 at 256, 2.996 at 128 and 4.62 at 1,024 (XLA's dot and its
    separate column sum: 3.954); at d = 4096 11.47 at 256 against 12.83 at
    512; at d = 256 0.0958 at 2,048 against 0.118 at 512 and 0.0992 at
    4,096. A call's first block is fetched with nothing to overlap it and
    the (d, d) accumulator's read-modify-write is paid once a block, so
    neither the smallest nor the largest block is the fastest; Mosaic
    unrolls the block's product, so the build time grows with the block as
    well."""
    rows = max(8, min(GRAM_COLSUM_MAX_BLOCK_N, GRAM_COLSUM_TILE_BYTES // (4 * d)))
    block = 1 << (rows.bit_length() - 1)
    while n % block and block > 8:
        block //= 2
    return block


def _gram_colsum_kernel(
    nvalid_ref, x_ref, *refs, block_n, n_rows, seeded, compute_dtype
):
    if seeded:
        g0_ref, cs0_ref, c0_ref, g_ref, cs_ref, c_ref = refs
    else:
        g_ref, cs_ref, c_ref = refs

    row0 = pl.program_id(0) * block_n
    nv = nvalid_ref[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # The row count is one add a call, as the XLA body's `count + c`.
        lane = jax.lax.broadcasted_iota(jnp.int32, c_ref.shape, 1)
        rows = jnp.clip(nv, 0, n_rows).astype(jnp.float32)
        rows = jnp.where(lane == 0, rows, 0.0)
        if seeded:
            # Accumulators start from the caller's streaming state, so the
            # whole per-batch update (state + batch stats) is ONE dispatch
            # with no separate add kernel reading the (d, d) state again.
            # The Gram comes straight from HBM into the output's buffer
            # (its operand is aliased to the output): no second (d, d)
            # block in VMEM.
            pltpu.sync_copy(g0_ref, g_ref)
            c_ref[:] = c0_ref[:] + rows
        else:
            g_ref[:] = jnp.zeros_like(g_ref)
            c_ref[:] = rows
        cs_ref[:] = jnp.zeros_like(cs_ref)

    # Blocks entirely past n_valid contribute nothing — skip their GEMM
    # (power-of-two bucketing can make half the blocks pure padding).
    @pl.when(row0 < nv)
    def _accumulate():
        # Only the one block straddling the n_valid boundary pays the mask;
        # full blocks skip the iota/select VPU pass entirely.
        @pl.when(row0 + block_n > nv)
        def _mask_boundary():
            rows = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0) + row0
            x_ref[:] = jnp.where(rows < nv, x_ref[:], jnp.zeros_like(x_ref))

        # x may arrive wider than the compute dtype (a float32 batch under
        # the bfloat16 profile): the tile is cast HERE, in VMEM, so the
        # narrow copy of the batch is never written to HBM and read back.
        xb = x_ref[:].astype(compute_dtype)
        g_ref[:] += jax.lax.dot_general(
            xb, xb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_prec(xb.dtype),
        )
        # float32 sums of the tile AFTER the cast: the Gram and the mean it
        # is centred with are statistics of the same rounded rows.
        cs_ref[:] += jnp.sum(xb.astype(jnp.float32), axis=0, keepdims=True)

    if seeded:
        # The call's column sums meet the seed in ONE add, after the last
        # block, as the XLA body's `colsum + s`: a block's partial sums are
        # then not rounded at the magnitude of a fit's running total.
        @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
        def _seed_colsum():
            cs_ref[:] += cs0_ref[:]


@functools.partial(
    ledgered_jit, "pallas.gram_colsum_pallas",
    static_argnames=("block_n", "compute_dtype", "interpret"),
)
def gram_colsum_pallas(
    x: jax.Array,
    n_valid: jax.Array,
    block_n: Optional[int] = None,
    state=None,
    compute_dtype=None,
    interpret: bool = False,
):
    """One-HBM-pass fused count + column sum + XᵀX of the first ``n_valid``
    rows — the full streaming-moment statistic in a single kernel, and the
    body of the PCA fold on the chip: ``ops/gram.streaming_update`` calls it
    where its gate holds (``fit_pca_stream``, the daemon's ``PCAJob.fold``
    and the scaler fits that ride it, the benchmark's three PCA cells).

    x: (n, d) in ``compute_dtype`` OR wider (float32 rows under the
    bfloat16 profile): each (block_n, d) tile is cast in VMEM, so a caller
    never writes a narrow copy of x to HBM. ``compute_dtype`` None means
    x's own. The GEMM is compute dtype x compute dtype with float32
    accumulation; the column sums are float32 sums of the tile after the
    cast. **The mask is a row count**: rows ≥ n_valid are treated as absent,
    so the valid rows must be a prefix of x — no mask array touches HBM,
    only the boundary block pays a select, and whole blocks of padding skip
    their GEMM. The (d, d) accumulator lives in VMEM across the whole
    row-grid (grid is 1-D over row blocks), so X is read exactly once —
    the streaming equivalent of the reference's dgemmCov hot loop
    (rapidsml_jni.cu:109-127) with its mean-stats pass fused in.

    ``state``: optional ``(gram, colsum, count)`` f32 streaming state the
    accumulators are SEEDED from (the Gram copied from HBM into the
    accumulator at the first grid step, its buffer aliased to the output),
    so the per-batch ``state += batch_stats`` of the streaming fit is this
    one dispatch — no separate XLA add re-reads and re-writes the (d, d)
    state per batch (``streaming_update`` does this under donation on
    single-data-device meshes).

    Returns (gram (d, d) float32, colsum (d,) float32, count () float32 —
    one float32 add of min(n_valid, n) a call, as exact as the XLA body's).
    """
    n, d = x.shape
    bn = min(block_n or gram_colsum_block_n(d, n), n)
    if n % bn:
        raise ValueError(f"n={n} not divisible by block_n={bn}")
    if d * d * 4 > GRAM_COLSUM_VMEM_BUDGET:
        raise ValueError(f"d={d}: (d, d) f32 accumulator exceeds the VMEM budget")
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else x.dtype
    nv = jnp.asarray(n_valid, jnp.int32).reshape((1,))
    seeded = state is not None
    extra_in = []
    extra_specs = []
    if seeded:
        g0, cs0, c0 = state
        extra_in = [
            g0.astype(jnp.float32),
            cs0.astype(jnp.float32).reshape(1, d),
            # every lane holds the count; the kernel adds to lane 0, which
            # is the one read back
            jnp.broadcast_to(jnp.asarray(c0, jnp.float32).reshape(1, 1), (1, 128)),
        ]
        extra_specs = [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, d), lambda i, nv: (0, 0)),
            pl.BlockSpec((1, 128), lambda i, nv: (0, 0)),
        ]
    gram, colsum, count = pl.pallas_call(
        functools.partial(
            _gram_colsum_kernel, block_n=bn, n_rows=n, seeded=seeded,
            compute_dtype=cd,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, d), lambda i, nv: (i, 0))] + extra_specs,
            out_specs=[
                pl.BlockSpec((d, d), lambda i, nv: (0, 0)),
                pl.BlockSpec((1, d), lambda i, nv: (0, 0)),
                pl.BlockSpec((1, 128), lambda i, nv: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        # operand 2 (after n_valid and x) is the seed's Gram
        input_output_aliases={2: 0} if seeded else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # (d, d) f32 accumulator + double-buffered input blocks; the
            # default 16M scoped limit rejects d ≥ 1448.
            vmem_limit_bytes=100 * 2**20,
        )
        if not interpret
        else None,
        interpret=interpret,
    )(nv, x, *extra_in)
    return gram, colsum[0], count[0, 0]


# ---------------------------------------------------------------------------
# Fused KMeans Lloyd step: assign + centroid-sum update in one HBM pass
# ---------------------------------------------------------------------------


def _lloyd_step_kernel(
    nvalid_ref, x_ref, c_ref, c2h_ref, sums_ref, counts_ref, cost_ref=None,
    *, block_n, dead_lane
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        if cost_ref is not None:
            cost_ref[:] = jnp.zeros_like(cost_ref)

    row0 = pl.program_id(0) * block_n
    nv = nvalid_ref[0]

    @pl.when(row0 < nv)
    def _accumulate():
        c = c_ref[:]  # (k_pad, d) compute dtype; padded rows are zeros
        # x may arrive wider than the compute dtype (a float32 batch under
        # the bfloat16 profile): the tile is cast HERE, in VMEM, so the
        # narrow copy of the batch is never written to HBM and read back.
        xb = x_ref[:].astype(c.dtype)  # (bn, d) compute dtype
        # TRANSPOSED distance layout (k_pad, bn): the argmin then reduces
        # over the SUBLANE axis instead of the 128-lane axis — sublane
        # reductions are the cheap direction on the VPU, and the profile
        # at d=256/k=100 was assignment(VPU)-bound, not MXU-bound.
        xc = jax.lax.dot_general(
            c, xb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_prec(xb.dtype),
        )  # (k_pad, bn)
        # ½‖x−c‖² up to the row-constant ½‖x‖²: argmin-invariant; the ½c²
        # is precomputed host-side (one VPU subtract per element here).
        # Padded centers carry c2h = LLOYD_PAD_D2 so they never win.
        d2 = c2h_ref[:] - xc  # (k_pad, bn); c2h is (k_pad, 1)
        assign = jnp.argmin(d2, axis=0).astype(jnp.int32)[None, :]  # (1, bn)
        cols = jax.lax.broadcasted_iota(jnp.int32, assign.shape, 1) + row0
        valid = cols < nv  # (1, bn)
        ks = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
        if dead_lane is not None:
            # Padded rows (x = 0) would argmin to the min-norm REAL center
            # and pollute counts; with k < k_pad a spare center row exists
            # — route them there ((1, bn) compare) and skip the
            # (k_pad, bn) row-mask pass entirely (sums[k:] are discarded
            # by the caller).
            assign = jnp.where(valid, assign, dead_lane)
            onehot = (ks == assign).astype(xb.dtype)  # (k_pad, bn)
        else:
            onehot = ((ks == assign) & valid).astype(xb.dtype)
        sums_ref[:] += jax.lax.dot_general(
            onehot, xb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_prec(xb.dtype),
        )
        counts_ref[:] += jnp.sum(onehot.astype(jnp.float32), axis=1)[None, :]
        if cost_ref is None:
            return
        # The batch's cost, ops/distances.sq_euclidean's formula term for
        # term: max(‖x‖² + ‖c‖² − 2x·c, 0) at the nearest centre, ‖x‖² in
        # float32 from the tile after the cast. ‖x‖² is wanted per row in
        # the LANE layout min(d2) has: the 128-lane chunks of the squares
        # are added on the VPU and that (bn, 128) remainder transposed, so
        # the row sum is a sublane reduction as well.
        xf = xb.astype(jnp.float32)
        sq = xf * xf
        part = sq[:, :128]
        for j in range(1, sq.shape[1] // 128):
            part = part + sq[:, j * 128:(j + 1) * 128]
        x2 = jnp.sum(part.T, axis=0, keepdims=True)  # (1, bn)
        row_cost = jnp.maximum(x2 + 2.0 * jnp.min(d2, axis=0, keepdims=True), 0.0)
        cost_ref[:] += jnp.sum(
            jnp.where(valid, row_cost, 0.0), axis=1, keepdims=True
        )  # every lane of the (1, 128) block holds the running cost


LLOYD_PAD_D2 = 1e30  # finite sentinel: padded centers never win the argmin
LLOYD_STEP_BLOCK_N = 4096


@functools.partial(
    ledgered_jit, "pallas.lloyd_step_pallas",
    static_argnames=("k", "block_n", "with_cost", "interpret"),
)
def lloyd_step_pallas(
    x: jax.Array,
    centers: jax.Array,
    n_valid: jax.Array,
    k: int,
    block_n: int = LLOYD_STEP_BLOCK_N,
    with_cost: bool = True,
    interpret: bool = False,
):
    """One fused Lloyd iteration's statistics in a single HBM pass over x.

    x: (n, d), d a multiple of 128, in the compute dtype OR wider (float32
    rows under the bfloat16 profile): each (block_n, d) tile is cast to
    ``centers.dtype`` in VMEM, so a caller never writes a narrow copy of x
    to HBM. centers: (k_pad, d) compute dtype whose rows
    beyond the true ``k`` are padding — they are excluded from the argmin
    via a LLOYD_PAD_D2 distance sentinel. Whole blocks past n_valid skip
    their GEMMs entirely; invalid rows of the boundary block are routed
    to the DEAD LANE ``k`` when k < k_pad (cheaper than a (bn, k_pad)
    row mask), so **sums[k]/counts[k] carry their garbage and callers
    MUST slice [:k]** (counts.sum() is NOT the valid-row count; lanes
    k+1.. stay zero). When k == k_pad the row-mask path runs instead and
    all lanes are exact.

    Per block: pairwise-distance GEMM → argmin → one-hot → centroid-sum
    GEMM, with the (k_pad, d) sums and (1, k_pad) counts accumulators
    VMEM-resident across the row grid. Nothing of size (n, k) or (n, d)
    is ever written back to HBM — the fusion the XLA path can't express
    (it materializes both the distance matrix and the one-hot matrix).

    The third result is the cost of the first ``n_valid`` rows at these
    centers: Σ max(‖x‖² + ‖c‖² − 2x·c, 0) at each row's nearest center —
    ``ops/distances.sq_euclidean`` term for term, clip included, the
    product in the compute dtype with float32 accumulation and both norms
    in float32 from the operands after the cast. It comes from the
    distances the assignment has already formed, so convergence
    monitoring (the streaming fold's running cost) needs no second read
    of x. Under the float32 rows' read it is free (0.0967 against 0.0963
    ms a 65,536 x 256 batch); on bfloat16 rows in 16,384-row blocks, where
    the VPU is the limit, it costs 10% (0.842 against 0.762 ms a
    1,048,576-row call; PERF.md §5, PR 29), so the in-memory fit, which
    wants the cost at its final centers only, passes ``with_cost=False``
    and gets ``None`` for it.

    Returns (sums (k_pad, d) float32, counts (k_pad,) float32, cost ()
    float32 or None).
    """
    n, d = x.shape
    k_pad = centers.shape[0]
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n={n} not divisible by block_n={bn}")
    if k_pad % 128:
        raise ValueError(f"k_pad={k_pad} must be a multiple of 128 lanes")
    if d % 128:
        raise ValueError(f"d={d} must be a multiple of 128 lanes")
    c2h = 0.5 * jnp.sum(
        jnp.square(centers.astype(jnp.float32)), axis=1, keepdims=True
    )  # (k_pad, 1) — column vector for the transposed (k_pad, bn) layout
    ks = jax.lax.broadcasted_iota(jnp.int32, c2h.shape, 0)
    c2h = jnp.where(ks < k, c2h, LLOYD_PAD_D2)
    nv = jnp.asarray(n_valid, jnp.int32).reshape((1,))
    out_specs = [
        pl.BlockSpec((k_pad, d), lambda i, nv: (0, 0)),
        pl.BlockSpec((1, k_pad), lambda i, nv: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((k_pad, d), jnp.float32),
        jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
    ]
    if with_cost:
        out_specs.append(pl.BlockSpec((1, 128), lambda i, nv: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, 128), jnp.float32))
    sums, counts, *cost = pl.pallas_call(
        functools.partial(
            _lloyd_step_kernel, block_n=bn,
            dead_lane=k if k < k_pad else None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, nv: (i, 0)),
                pl.BlockSpec((k_pad, d), lambda i, nv: (0, 0)),
                pl.BlockSpec((k_pad, 1), lambda i, nv: (0, 0)),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(nv, x, centers, c2h)
    return sums, counts[0], cost[0][0, 0] if with_cost else None


# ---------------------------------------------------------------------------
# Fused binomial Newton statistics: one HBM pass per IRLS iteration
# ---------------------------------------------------------------------------


NEWTON_STATS_BLOCK_N = 512
NEWTON_STATS_VMEM_BUDGET = 64 * 2**20  # max (d, d) f32 resident Hessian


def _newton_stats_kernel(b_ref, x_ref, y_ref, m_ref, w_ref, gw_ref, h_ref, s_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        gw_ref[:] = jnp.zeros_like(gw_ref)
        h_ref[:] = jnp.zeros_like(h_ref)
        s_ref[:] = jnp.zeros_like(s_ref)

    xb = x_ref[:]  # (bn, d) compute dtype
    y = y_ref[:]  # (bn, 1) f32
    m = m_ref[:]  # (bn, 1) f32
    w = w_ref[:]  # (128, d) compute dtype; row 0 = w, rest zeros
    hp = _dot_prec(xb.dtype)
    # Row-local IRLS quantities: z → p → (residual, weight). This is why the
    # whole iteration fits in one pass — nothing couples rows except the
    # final sums. Two Mosaic shape/fusion constraints shape the matvec:
    # the MXU pads N to 128 lanes anyway but rejects bf16 dots with a
    # literal N=1, so w arrives pre-padded to (128, d); and the scalar
    # `+ b` must come after the lane slice — fusing an add into a matmul
    # accumulator is rejected ("Only constant accumulator supported").
    z128 = jax.lax.dot_general(
        xb, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hp,
    )  # (bn, 128); only lane 0 is live
    z = z128[:, :1] + b_ref[0]  # (bn, 1)
    p = jax.nn.sigmoid(z)
    r = (p - y) * m
    wgt = jnp.maximum(p * (1.0 - p), 1e-10) * m
    # One (128, bn)×(bn, d) GEMM yields both vector statistics: row 0 the
    # gradient Xᵀr, row 1 the intercept border Xᵀwgt (M is MXU-padded to
    # 128 regardless, and M=2 trips the same Mosaic shape limit as N=1).
    lane = jax.lax.broadcasted_iota(jnp.int32, (xb.shape[0], 128), 1)
    rw = (
        jnp.where(lane == 0, r, 0.0) + jnp.where(lane == 1, wgt, 0.0)
    ).astype(xb.dtype)  # (bn, 128)
    gw_ref[:] += jax.lax.dot_general(
        rw, xb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=hp,
    )
    # Hessian Xᵀdiag(wgt)X at fast DEFAULT precision: it is a
    # preconditioner, not the answer (see models/logistic_regression.py) —
    # the gradient above sets the fixed point.
    xw = xb * wgt.astype(xb.dtype)
    h_ref[:] += jax.lax.dot_general(
        xw, xb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.DEFAULT,
    )
    slane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s_ref[:] += jnp.where(slane == 0, jnp.sum(r), 0.0) + jnp.where(
        slane == 1, jnp.sum(wgt), 0.0
    )


@functools.partial(
    ledgered_jit, "pallas.newton_stats_pallas", static_argnames=("block_n", "interpret")
)
def newton_stats_pallas(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    b: jax.Array,
    block_n: int = NEWTON_STATS_BLOCK_N,
    interpret: bool = False,
):
    """One binomial Newton-IRLS iteration's statistics in a single HBM pass
    — the kernel of the IN-MEMORY fit (`models/logistic_regression.py`
    `_newton_fn_cached` behind `_pallas_newton_applicable`): rows already
    cast to bfloat16, so the gradient is a bfloat16 product as well, d on
    the 128-lane grid, no loss, no row count, no running state. The
    streaming fold (float32 rows, float32 gradient and loss, any width, a
    seedable Hessian) is :func:`newton_fold_pallas`.

    The XLA lowering of the IRLS body reads x ~4× per iteration (z matvec,
    gradient GEMM, weighted copy x·wgt, Hessian GEMM) — at d=1024 the step
    is HBM-bound, not MXU-bound. Here z, p, and the per-row
    residual/weight are computed in VMEM per row block and x feeds both
    GEMMs from the same resident tile, so x streams through HBM exactly
    once per Newton step. The (d, d) Hessian accumulator stays VMEM-
    resident across the whole row grid (same design as
    :func:`gram_colsum_pallas`).

    x: (n, d) in the compute dtype — bfloat16 streams half the HBM bytes
    and runs every dot single-pass on the MXU (the intended speed mode);
    float32 keeps full-precision gradients. y/mask: (n,) f32 (mask
    multiplies both residual and weight, so arbitrary row masks work, not
    just valid-prefixes); w: (d,) f32; b: scalar f32 (prefetched to SMEM).

    Returns raw (unnormalized, pre-psum) sums:
    (grad_w (d,), grad_b (), h_ww (d, d), h_wb (d,), h_bb ()), all f32 —
    the caller divides by the global row count, adds ridge terms, and
    psums across the data axis.
    """
    n, d = x.shape
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n={n} not divisible by block_n={bn}")
    if d * d * 4 > NEWTON_STATS_VMEM_BUDGET:
        raise ValueError(f"d={d}: (d, d) f32 Hessian exceeds the VMEM budget")
    bvec = jnp.asarray(b, jnp.float32).reshape((1,))
    wpad = jnp.zeros((128, d), x.dtype).at[0].set(w.astype(x.dtype))
    gw, h, s = pl.pallas_call(
        _newton_stats_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i, b: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, b: (i, 0)),
                pl.BlockSpec((bn, 1), lambda i, b: (i, 0)),
                pl.BlockSpec((128, d), lambda i, b: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((128, d), lambda i, b: (0, 0)),
                pl.BlockSpec((d, d), lambda i, b: (0, 0)),
                pl.BlockSpec((1, 128), lambda i, b: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((128, d), jnp.float32),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(
        bvec,
        x,
        y.astype(jnp.float32).reshape(n, 1),
        mask.astype(jnp.float32).reshape(n, 1),
        wpad,
    )
    return gw[0], s[0, 0], h, gw[1], s[0, 1]


# ---------------------------------------------------------------------------
# The streaming Newton fold: one batch's statistics from one read of the
# float32 rows, at any width up to the VMEM budget
# ---------------------------------------------------------------------------


# Shared with the fold's gate (models/logistic_regression.py
# `_fused_newton_fold_applicable`), so the two cannot drift.
NEWTON_FOLD_VMEM_BUDGET = 64 * 2**20  # max lane-padded (dp, dp) f32 accumulator
NEWTON_FOLD_ROW_MULTIPLE = 512  # the fold's gate: shard rows in multiples of this


def newton_fold_block_n(d: int, n: int) -> int:
    """The kernel's row block for n rows of width d, by
    `gram_colsum_block_n`'s rule on the lane-padded width: the largest power
    of two that divides n and whose (block, dp) float32 tile is within 4 MiB,
    at most 2,048 rows — 256 at d = 3000 (dp = 3072). Checked on a v5e at
    that width (host clock over eight programs of eight seeded 65,536-row
    folds, PERF.md §6, PR 33): a fold took 7.13 ms in 256-row blocks, 7.47
    at 128 and 7.48 at 512 (the XLA body: 9.97; the product alone, with
    nothing but the cast beside it: 6.67 at 256, 6.69 at 128, 6.92 at 512).
    A block's logits must be whole before its weighted product can start,
    so a smaller block pays that serial start and the (dp, dp)
    accumulator's read-modify-write more often, and a larger one twice the
    unrolled code."""
    return gram_colsum_block_n(_ceil_to(d, 128), n)


def _newton_fold_kernel(b_ref, xt_ref, y_ref, m_ref, w_ref, *refs, d, seeded):
    if seeded:
        h0_ref, gw_ref, hwb_ref, h_ref, s_ref = refs
    else:
        gw_ref, hwb_ref, h_ref, s_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        if seeded:
            # The Hessian accumulator starts from the caller's running one,
            # straight from HBM into the output's buffer (its operand is
            # aliased to the output), as `_gram_colsum_kernel` seeds its
            # Gram: no XLA add reads and writes the (dp, dp) state a batch.
            pltpu.sync_copy(h0_ref, h_ref)
        else:
            h_ref[:] = jnp.zeros_like(h_ref)
        gw_ref[:] = jnp.zeros_like(gw_ref)
        hwb_ref[:] = jnp.zeros_like(hwb_ref)
        s_ref[:] = jnp.zeros_like(s_ref)

    dp, bn = xt_ref.shape
    if d != dp:
        # The block is taller than the array: what lies past feature d is
        # unspecified, so those sublanes are zeroed before anything reads
        # them. Zero features are exact: w's are zero too, and every sum
        # and the product's live (d, d) block are those of the real ones.
        d8 = (d // 8) * 8
        feat = jax.lax.broadcasted_iota(jnp.int32, (dp - d8, bn), 0)
        xt_ref[d8:, :] = jnp.where(feat < d - d8, xt_ref[d8:, :], 0.0)

    xt = xt_ref[:]  # (dp, bn) float32: features on sublanes, rows on lanes
    y = y_ref[:]  # (1, bn) f32
    m = m_ref[:]  # (1, bn) f32
    # Row-local quantities in float32 on the VPU/EUP, from the float32
    # tile: a multiply and a sublane reduction for the logits, not the MXU
    # — at `highest` a float32 matvec is six bfloat16 passes over the tile.
    z = jnp.sum(xt * w_ref[:], axis=0, keepdims=True) + b_ref[0]  # (1, bn)
    p = jax.nn.sigmoid(z)
    r = (p - y) * m
    wgt = jnp.maximum(p * (1.0 - p), 1e-10) * m
    # The gradient Xᵀr and the border Σ x·wgt: float32 sums of float32
    # rows, as the XLA body's `highest` products. The 128-lane chunks are
    # added on the VPU into (dp, 128) accumulators; the one cross-lane
    # reduction a call is the wrapper's.
    xw = xt * wgt  # the weighting in float32, before the cast
    xr = xt * r
    gpart, hpart = xr[:, :128], xw[:, :128]
    for j in range(1, bn // 128):
        gpart = gpart + xr[:, j * 128:(j + 1) * 128]
        hpart = hpart + xw[:, j * 128:(j + 1) * 128]
    gw_ref[:] += gpart
    hwb_ref[:] += hpart
    # Both operands cast in VMEM, then the FULL product (x·wgt)ᵀ x — every
    # one of its 2·bn·dp² operations, no triangle — single-pass on the MXU
    # with float32 accumulation: the arithmetic of the XLA body's
    # `dot_general(xw, xc, precision=DEFAULT)`.
    h_ref[:] += jax.lax.dot_general(
        xw.astype(jnp.bfloat16), xt.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.DEFAULT,
    )
    loss = jnp.sum((jax.nn.softplus(z) - y * z) * m)
    slane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s_ref[:] += (
        jnp.where(slane == 0, jnp.sum(r), 0.0)
        + jnp.where(slane == 1, jnp.sum(wgt), 0.0)
        + jnp.where(slane == 2, loss, 0.0)
        + jnp.where(slane == 3, jnp.sum(m), 0.0)
    )


@functools.partial(
    ledgered_jit, "pallas.newton_fold_pallas", static_argnames=("block_n", "interpret")
)
def newton_fold_pallas(
    xt: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    b: jax.Array,
    hww=None,
    block_n: Optional[int] = None,
    interpret: bool = False,
):
    """One batch's binomial Newton statistics at fixed (w, b) from ONE read
    of the float32 rows — the body of the streaming fold on the chip:
    `models/logistic_regression.py` `_stream_grad_hess_shard_fn` calls it
    where `_fused_newton_fold_applicable` holds (`fit_logistic_stream`, the
    daemon's `LogisticRegressionJob.fold` / `fold_group`, the benchmark's
    `logreg_d3000.newton_cached`).

    xt: **(d, n) float32, the batch TRANSPOSED** — features on sublanes,
    rows on lanes. That is how the TPU keeps an (n, d) float32 batch whose
    width is off the 128-lane grid: its default device layout puts the rows
    minor (an (n, 3000) array costs 786 MB, not the 805 MB of lane-padded
    rows), so `x.T` inside a jit is a bitcast there and the kernel reads the
    cached batch as it lies (the gate sends widths ON the grid, which the
    chip keeps row-major, to the XLA body: the kernel is right there too,
    but its caller would pay a transposing copy). d is **any width** whose
    lane-padded square fits the VMEM budget: a block is (dp, block_n),
    dp = ceil(d / 128) · 128, laid over the d-feature array, and the
    features past d are zeroed in VMEM — no padded copy of x is written to
    HBM. y, mask: (n,) — the mask a general 0/1 array (it multiplies
    residual and weight row by row), read in (1, block_n) blocks. w: (d,)
    float32; b: scalar (prefetched to SMEM).

    Per block, from the float32 tile: logits, sigmoid, residual, weight,
    the gradient Xᵀr, the loss Σ(softplus(z) − y·z)·mask, the border
    Σ x·wgt and the row count in float32 on the VPU; `x·wgt` and `x` cast
    to bfloat16 in VMEM and the full Hessian product `(x·wgt)ᵀ x` single-pass
    on the MXU with float32 accumulation into a VMEM-resident (dp, dp)
    accumulator — the XLA body's arithmetic, term for term.

    ``hww``: optional (dp, dp) float32 running Hessian, ALREADY lane-padded
    (zero past d), the accumulator is SEEDED from — copied from HBM at the
    first grid step, its buffer aliased to the output — so ``hww += batch``
    is this one dispatch. The other statistics are the batch's own.

    Returns (gw (d,), gb (), hww (dp, dp) — zero past d —, hwb (d,), hbb (),
    loss (), n ()), all float32 raw sums of this call's rows.
    """
    d, n = xt.shape
    dp = _ceil_to(d, 128)
    bn = min(block_n or newton_fold_block_n(d, n), n)
    if n % bn or bn % 128:
        raise ValueError(f"n={n} not divisible by block_n={bn}, a multiple of 128")
    if dp * dp * 4 > NEWTON_FOLD_VMEM_BUDGET:
        raise ValueError(f"d={d}: (dp, dp) f32 Hessian exceeds the VMEM budget")
    if xt.dtype != jnp.float32:
        raise ValueError(f"xt must be float32 rows, got {xt.dtype}")
    seeded = hww is not None
    if seeded and hww.shape != (dp, dp):
        raise ValueError(f"hww {hww.shape} is not lane-padded to ({dp}, {dp})")
    bvec = jnp.asarray(b, jnp.float32).reshape((1,))
    wcol = jnp.zeros((dp, 1), jnp.float32).at[:d, 0].set(w.astype(jnp.float32))
    row = pl.BlockSpec((1, bn), lambda i, b: (0, i))
    part = pl.BlockSpec((dp, 128), lambda i, b: (0, 0))
    gw, hwb, h, s = pl.pallas_call(
        functools.partial(_newton_fold_kernel, d=d, seeded=seeded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((dp, bn), lambda i, b: (0, i)),
                row,
                row,
                pl.BlockSpec((dp, 1), lambda i, b: (0, 0)),
            ]
            + ([pl.BlockSpec(memory_space=pl.ANY)] if seeded else []),
            out_specs=[
                part,
                part,
                pl.BlockSpec((dp, dp), lambda i, b: (0, 0)),
                pl.BlockSpec((1, 128), lambda i, b: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((dp, 128), jnp.float32),
            jax.ShapeDtypeStruct((dp, 128), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        # operand 5 (after b, xt, y, mask, w) is the seed's Hessian
        input_output_aliases={5: 2} if seeded else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(
        bvec,
        xt,
        y.astype(jnp.float32).reshape(1, n),
        mask.astype(jnp.float32).reshape(1, n),
        wcol,
        *([hww] if seeded else []),
    )
    return (
        jnp.sum(gw, axis=1)[:d], s[0, 0], h, jnp.sum(hwb, axis=1)[:d],
        s[0, 1], s[0, 2], s[0, 3],
    )


# ---------------------------------------------------------------------------
# The forest's histogram product: lhs × one_hot(bins), the one-hot made in
# VMEM tile by tile and never in HBM
# ---------------------------------------------------------------------------


# Shared with the fold's gate (ops/histogram.py
# `_fused_hist_fold_applicable`), so the two cannot drift.
HIST_ONEHOT_ROW_MULTIPLE = 512  # the fold's gate: chunk rows in multiples of this
HIST_ONEHOT_MAX_ROW_TILE = 2048
HIST_ONEHOT_FEATURES = 8  # features a grid step folds: one int32 tile of bin ids
HIST_ONEHOT_OUT_TILE_BYTES = 16 * 2**20  # a VMEM-resident (rows, 8 · bins) int32 tile


def hist_onehot_tiles(m: int, c: int, n_bins: int) -> tuple[int, int]:
    """(tm, tk): the rows of the left operand a grid step holds — all of
    them, padded to int8's 32-sublane tile, or equal parts whose (tm, 8 ·
    n_bins) int32 output tile is within 16 MiB (4,096 rows at 128 bins) —
    and the rows of the chunk it contracts: the largest power of two up to
    2,048 that divides c. Chosen on a v5e from the product alone at the
    forest cell's heights against a 16,384-row chunk and 232 features (host
    clock over 18 products, PERF.md §6, PR 39): 3,360 rows 8.55 ms at
    2,048 (382 T/s of the chip's 393 int8), 8.72 at 1,024, 9.00 at 512,
    and 10.34 with 16 features a step; 210 rows 0.752 at 2,048 and 4,096,
    0.795 at 1,024, 0.878 at 512 — a longer tile pays the output tile's
    read-modify-write and a grid step's start less often."""
    mp = _ceil_to(m, 32)
    cap = HIST_ONEHOT_OUT_TILE_BYTES // (4 * HIST_ONEHOT_FEATURES * n_bins) // 32 * 32
    parts = -(-mp // cap)
    tm = _ceil_to(-(-mp // parts), 32)
    tk = HIST_ONEHOT_MAX_ROW_TILE
    while c % tk and tk > 128:
        tk //= 2
    return tm, tk


def hist_onehot_vmem_limit(tm: int, tk: int, n_bins: int) -> int:
    """The scoped VMEM the kernel is compiled under: what its tiles take —
    the output tile and both operands' double-buffered, a feature's product
    and one-hot — and a quarter over, at least Mosaic's default 16 MiB and
    at most 66 MiB (4,096 rows), NOT a flat 100 MiB. XLA keeps small arrays
    of the surrounding program in VMEM — a 28-row operand made in the
    fold's program among them — and a kernel that claims 96 MiB or more of
    the chip's 128 overwrites them (seen on a v5e: the product all zeros at
    4 trees under 96, 100 and 112 MiB, right under 80 and less; PERF.md §6,
    PR 39)."""
    feats = HIST_ONEHOT_FEATURES
    tiles = (2 * (tm * feats * n_bins * 4 + tm * tk + feats * tk * 4)
             + tm * n_bins * 4 + 5 * n_bins * tk)
    return max(16 * 2**20, tiles + tiles // 4 + 2 * 2**20)


def _hist_onehot_kernel(lhs_ref, bins_ref, o_ref, *, n_bins):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    lhs = lhs_ref[:]  # (tm, tk) int8: channels on sublanes, rows on lanes
    tk = lhs.shape[1]
    # A feature's one-hot, bins on sublanes and rows on lanes: its row of
    # bin ids broadcast down the sublanes against the bins' own numbers. A
    # blanked column (id -1) and a padded one equal no bin: all zeros.
    bin_of = jax.lax.broadcasted_iota(jnp.int32, (n_bins, tk), 0)
    for f in range(bins_ref.shape[0]):
        one_hot = (bins_ref[f:f + 1, :] == bin_of).astype(jnp.int8)
        # both operands contract their lanes (q·kᵀ's form): int8 into int32
        o_ref[:, f * n_bins:(f + 1) * n_bins] += jax.lax.dot_general(
            lhs, one_hot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )


@functools.partial(
    ledgered_jit, "pallas.hist_onehot_matmul_pallas",
    static_argnames=("n_bins", "row_tile", "interpret"),
)
def hist_onehot_matmul_pallas(
    lhs: jax.Array,
    bins_t: jax.Array,
    n_bins: int,
    row_tile: Optional[int] = None,
    interpret: bool = False,
):
    """A feature block's histogram product ``h (M, db · n_bins) int32 =
    lhs (M, c) int8 × one_hot(bins (c, db), n_bins)`` with the one-hot made
    tile by tile in VMEM — the contraction of the forest's fold on the chip:
    `ops/histogram.py` `hist_update_group_fn` calls it where
    `_fused_hist_fold_applicable` holds (the forests' in-memory fit, the
    daemon's `RandomForestJob.fold` / `fold_group`, the benchmark's
    `rf_reg_d3000.levels_cached`), in place of `jax.nn.one_hot` and a
    matrix product whose right operand XLA has to write to HBM (4.6 MB a
    row of the block at 3,000 columns) or generate inside the product's
    fusion at a third of the MXU's int8 rate.

    lhs: (M, c) int8 — the chunk's node one-hots times bag weight times a
    statistic's whole-number digit, any M (padded here to int8's 32-row
    tile; over 4,096 rows at 128 bins it is walked in equal parts, each
    generating the one-hot again). bins_t: **(db, c) int32, the block's bin
    ids TRANSPOSED** — rows along the lanes, as the chip keeps an (n, 3000)
    batch and what is computed from it; an id outside [0, n_bins) — the
    fold's blanked columns carry -1 — is an all-zero one-hot. ``n_bins`` a
    multiple of 128: a feature's bins are whole lane tiles of the output.
    c a multiple of the row tile (`hist_onehot_tiles`; 512 divides it).

    Grid (part of M, 8 features, row tile), the last the contraction: an
    output tile (tm, 8 · n_bins) int32 stays in VMEM across a chunk's row
    tiles and is written once; a step loads (tm, tk) of lhs and (8, tk) bin
    ids, and for each feature compares its ids with an iota over the bins —
    (n_bins, tk), a sublane broadcast — and multiplies on the MXU, int8 x
    int8 into int32: whole numbers, the XLA product's to the bit in any
    order."""
    m, c = lhs.shape
    db = bins_t.shape[0]
    if lhs.dtype != jnp.int8:
        raise ValueError(f"lhs must be int8, got {lhs.dtype}")
    if bins_t.shape != (db, c) or bins_t.dtype != jnp.int32:
        raise ValueError(
            f"bins_t must be (db, {c}) int32, got {bins_t.shape} {bins_t.dtype}")
    if n_bins % 128 or n_bins <= 0:
        raise ValueError(f"n_bins={n_bins} is not a multiple of 128")
    tm, tk = hist_onehot_tiles(m, c, n_bins)
    tk = min(row_tile or tk, c)
    if c % tk or tk % 128:
        raise ValueError(f"c={c} not divisible by row_tile={tk}, a multiple of 128")
    feats = HIST_ONEHOT_FEATURES
    mp, dbp = _ceil_to(m, tm), _ceil_to(db, feats)
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    if dbp != db:
        bins_t = jnp.pad(bins_t, ((0, dbp - db), (0, 0)), constant_values=-1)
    h = pl.pallas_call(
        functools.partial(_hist_onehot_kernel, n_bins=n_bins),
        grid=(mp // tm, dbp // feats, c // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((feats, tk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tm, feats * n_bins), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, dbp * n_bins), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hist_onehot_vmem_limit(tm, tk, n_bins),
        )
        if not interpret
        else None,
        interpret=interpret,
    )(lhs, bins_t)
    return h[:m, :db * n_bins]


# ---------------------------------------------------------------------------
# Fused KMeans assignment: argmin_k ||x - c_k||² without an (m, k) HBM array
# ---------------------------------------------------------------------------


def _assign_kernel(x_ref, c_ref, c2_ref, best_d_ref, best_i_ref):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        best_d_ref[:] = jnp.full_like(best_d_ref, jnp.inf)
        best_i_ref[:] = jnp.zeros_like(best_i_ref)

    x = x_ref[:]  # (bm, d)
    c = c_ref[:]  # (bk, d)
    c2 = c2_ref[:]  # (bk,)
    # ||x-c||² up to the query-constant ||x||²: c² − 2xc (argmin-invariant).
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_dot_prec(x.dtype),
    )
    d2 = c2[None, :] - 2.0 * xc  # (bm, bk)
    local_best = jnp.min(d2, axis=1)
    bk = c.shape[0]
    local_idx = jnp.argmin(d2, axis=1).astype(jnp.int32) + kk * bk
    improved = local_best < best_d_ref[:]
    best_i_ref[:] = jnp.where(improved, local_idx, best_i_ref[:])
    best_d_ref[:] = jnp.where(improved, local_best, best_d_ref[:])


@functools.partial(
    ledgered_jit, "pallas.assign_min_dist_pallas", static_argnames=("block_m", "block_k", "interpret")
)
def assign_min_dist_pallas(
    x: jax.Array,
    centers: jax.Array,
    block_m: int = 1024,
    block_k: int = 128,
    interpret: bool = False,
):
    """(assignments (m,), partial_min_d2 (m,)) for KMeans, fused tile-wise.

    Returned distances omit the +‖x‖² query constant (argmin-invariant);
    callers needing true distances add it back.
    """
    m, d = x.shape
    k = centers.shape[0]
    bm = min(block_m, m)
    bk = min(block_k, k)
    if m % bm or k % bk:
        raise ValueError(f"shape m={m},k={k} not divisible by blocks ({bm},{bk})")
    c2 = jnp.sum(jnp.square(centers.astype(jnp.float32)), axis=1)
    grid = (m // bm, k // bk)
    best_d, best_i = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, kk: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bk,), lambda i, kk: (kk,)),
        ],
        out_specs=[
            pl.BlockSpec((bm,), lambda i, kk: (i,)),
            pl.BlockSpec((bm,), lambda i, kk: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
        if not interpret
        else None,
        interpret=interpret,
    )(x, centers, c2)
    return best_i, best_d


# ---------------------------------------------------------------------------
# Fused streaming distance + EXACT top-k: kneighbors without the (q, m) matrix
# ---------------------------------------------------------------------------


DIST_TOPK_BLOCK_M = 1024
DIST_TOPK_BLOCK_Q = 256
#: Extraction-pass unroll bound: each of the k selection passes is a pair
#: of sublane reduces over the (block_m + k_pad, qb) tile, statically
#: unrolled — past this, selection cost and program size outgrow the GEMM
#: and the two-step XLA path wins anyway.
DIST_TOPK_MAX_K = 64


def _dist_topk_kernel(rows_ref, r2_ref, ids_ref, qT_ref, q2_ref,
                      d_ref, i_ref, *, k):
    """One candidate block per inner grid step: distance GEMM + merge into
    the running per-query top-k, the (bm, qb) score tile never leaving VMEM.

    Layout is the round-3 selection lesson (benchmarks/README.md) applied
    to the EXACT kneighbors path: candidates ride the SUBLANES, queries the
    LANES, so every one of the k extraction passes reduces over the cheap
    VPU direction. The running (k_pad, qb) best-distance/best-id planes are
    VMEM-resident across the whole candidate grid — nothing of size (q, m)
    is ever written to HBM, the fusion the XLA ``sq_euclidean`` →
    ``lax.top_k`` two-step cannot express (it materializes the full
    distance matrix between the two ops).

    Selection is k lexicographic (distance, id) min-extraction passes over
    the concatenation of the running best and the fresh block: ids are
    globally unique for valid rows, so each pass's equality mask removes
    exactly one element, and ties resolve to the LOWEST id — the
    ``merge_topk`` host-merge contract, pinned by the duplicate-distance
    regression test so sharded and single-daemon answers stay comparable.
    Invalid/padded rows carry (+inf, -1) and sort past every real
    candidate; slots with no finite candidate emit exactly (+inf, -1).
    """
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        d_ref[:] = jnp.full_like(d_ref, jnp.inf)
        i_ref[:] = jnp.full_like(i_ref, -1)

    rows = rows_ref[:]  # (bm, d) compute dtype; padded rows zero
    qT = qT_ref[:]  # (d, qb) compute dtype
    qr = jax.lax.dot_general(
        rows, qT, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_dot_prec(rows.dtype),
    )  # (bm, qb)
    # Same term order as ops/distances.sq_euclidean ((x²+y²) − 2xy, clipped
    # at 0) so fused and unfused distances differ only by GEMM tiling.
    d2 = jnp.maximum(q2_ref[:] + r2_ref[:] - 2.0 * qr, 0.0)  # (bm, qb)
    ids = jnp.broadcast_to(ids_ref[:], d2.shape)  # (bm, qb) int32
    cat_d = jnp.concatenate([d_ref[:], d2], axis=0)  # (k_pad + bm, qb)
    cat_i = jnp.concatenate([i_ref[:], ids], axis=0)
    for j in range(k):
        m = jnp.min(cat_d, axis=0, keepdims=True)  # (1, qb) sublane min
        mi = jnp.min(
            jnp.where(cat_d == m, cat_i, jnp.int32(0x7FFFFFFF)),
            axis=0, keepdims=True,
        )  # lowest id among distance ties: the (distance, id) order
        d_ref[j : j + 1, :] = m
        i_ref[j : j + 1, :] = jnp.where(m < jnp.inf, mi, jnp.int32(-1))
        cat_d = jnp.where((cat_d == m) & (cat_i == mi), jnp.inf, cat_d)


@functools.partial(
    ledgered_jit, "pallas.dist_topk_pallas",
    static_argnames=("k", "block_m", "block_q", "interpret"),
)
def dist_topk_pallas(
    queries: jax.Array,
    db: jax.Array,
    row_ids: jax.Array,
    mask: jax.Array,
    k: int,
    block_m: int = DIST_TOPK_BLOCK_M,
    block_q: int = DIST_TOPK_BLOCK_Q,
    interpret: bool = False,
):
    """Exact fused kneighbors core: per-query top-``k`` squared-Euclidean
    neighbors of ``queries`` (q, d) against ``db`` (m, d), streaming db
    blocks through one HBM pass with the running k-best VMEM-resident —
    the (q, m) distance matrix is never materialized (the ledger's
    ``memory_analysis`` receipt in tests/test_knn.py pins that).

    ``row_ids``: (m,) int32 global ids of the db rows (-1 on padding);
    ``mask``: (m,) {0,1} — masked rows score +inf and emit id -1, matching
    the XLA path's missing-slot contract. Ties resolve by ascending
    (distance, id) — bitwise the ``merge_topk``/``reduce_topk`` order, so
    sharded and single-daemon kneighbors stay comparable. Distances are
    true clipped f32 squared distances (not argmin-residuals).

    Returns (dists (q, k) f32 ascending, ids (q, k) int32).
    """
    q, d = queries.shape
    m = db.shape[0]
    if k > DIST_TOPK_MAX_K:
        raise ValueError(f"k={k} exceeds DIST_TOPK_MAX_K={DIST_TOPK_MAX_K}")
    if k > m:
        raise ValueError(f"k={k} exceeds database rows m={m}")
    qb = min(block_q, _ceil_to(q, 8))
    q_pad = _ceil_to(q, qb)
    bm = min(block_m, _ceil_to(m, 8))
    m_pad = _ceil_to(m, bm)
    qf = queries.astype(jnp.float32)
    q2 = jnp.sum(jnp.square(qf), axis=1)[None, :]  # (1, q) f32
    qT = jnp.swapaxes(queries, 0, 1)  # (d, q) compute dtype
    if q_pad != q:
        qT = jnp.pad(qT, ((0, 0), (0, q_pad - q)))
        q2 = jnp.pad(q2, ((0, 0), (0, q_pad - q)))
    dbf = db.astype(jnp.float32)
    r2 = jnp.where(
        mask.astype(jnp.float32) > 0,
        jnp.sum(jnp.square(dbf), axis=1),
        jnp.inf,
    )[:, None]  # (m, 1) f32; +inf never wins and decodes to id -1
    ids = jnp.asarray(row_ids, jnp.int32)[:, None]
    if m_pad != m:
        db = jnp.pad(db, ((0, m_pad - m), (0, 0)))
        r2 = jnp.pad(r2, ((0, m_pad - m), (0, 0)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, m_pad - m), (0, 0)), constant_values=-1)
    k_pad = _ceil_to(k, 8)
    best_d, best_i = pl.pallas_call(
        functools.partial(_dist_topk_kernel, k=k),
        name="dist_topk",
        grid=(q_pad // qb, m_pad // bm),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((d, qb), lambda i, j: (0, i)),
            pl.BlockSpec((1, qb), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((k_pad, qb), lambda i, j: (0, i)),
            pl.BlockSpec((k_pad, qb), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, q_pad), jnp.float32),
            jax.ShapeDtypeStruct((k_pad, q_pad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 2**20,
        )
        if not interpret
        else None,
        interpret=interpret,
    )(db, r2, ids, qT, q2)
    return best_d[:k, :q].T, best_i[:k, :q].T


# ---------------------------------------------------------------------------
# Fused IVF list scan + EXACT per-slot top-k selection
# ---------------------------------------------------------------------------


# Masked-winner key: int32 max — strictly above every packed finite-score
# key (a finite f32 score maps below 0x7F800000, and the position bits
# only fill the cleared low bits).
IVF_MASKED_KEY = 0x7FFFFFFF
# Emitted in the sublane-pad output rows callers slice away.
IVF_MASKED_D2 = 3.0e38


def _sortable_int(v):
    """The order-preserving f32↔int32 bijection (IEEE trick: flip the
    non-sign bits of negatives). Self-inverse; finite inputs assumed."""
    return v ^ (
        jax.lax.shift_right_arithmetic(v, jnp.int32(31)) & jnp.int32(0x7FFFFFFF)
    )


def _packed_keys(scores, pos_bits):
    """(maxlen, C) f32 scores → UNIQUE packed int32 keys: sortable value
    in the high bits, sublane position in the low ``pos_bits``. Shared by
    the scan-selection and probe-selection kernels."""
    low = jnp.int32((1 << pos_bits) - 1)
    key = _sortable_int(jax.lax.bitcast_convert_type(scores, jnp.int32))
    return (key & ~low) | jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)


def _packed_extract(key, d_ref, p_ref, count, pos_bits):
    """``count`` exact ascending min-extraction passes over packed keys:
    each pass is one sublane min-reduce + one single-element equality mask
    (keys unique ⇒ ties resolve to the lowest position). Decoded values
    are floored within a relative 2^(pos_bits-24) (the packed-key mantissa
    trade). Sublane-pad output rows get the (IVF_MASKED_D2, 0) sentinel so
    the output is deterministic."""
    low = jnp.int32((1 << pos_bits) - 1)
    for j in range(count):
        m = jnp.min(key, axis=0, keepdims=True)  # (1, C) sublane min
        pos = m & low
        vkey = m ^ pos  # position bits cleared: the floored value key
        d_ref[j : j + 1, :] = jax.lax.bitcast_convert_type(
            _sortable_int(vkey), jnp.float32
        )
        p_ref[j : j + 1, :] = pos
        key = jnp.where(key == m, jnp.int32(IVF_MASKED_KEY), key)
    if count < d_ref.shape[0]:
        pad = jax.lax.broadcasted_iota(
            jnp.int32, (d_ref.shape[0] - count, key.shape[1]), 0
        )
        d_ref[count:, :] = jnp.full_like(pad, IVF_MASKED_D2, jnp.float32)
        p_ref[count:, :] = jnp.zeros_like(pad)


def _ivf_scan_select_kernel(
    qv_ref, rows_ref, r2_ref, d_ref, p_ref, *, blk_k, pos_bits
):
    """One probed list per grid step: residual-score GEMM + exact top-blk_k
    per query slot, the (maxlen, C) score tile never leaving VMEM.

    Layout is the round-3 Lloyd lesson applied to selection (see
    benchmarks/README.md): scores are computed as (maxlen, C) — candidate
    ROWS on sublanes, query SLOTS on lanes — so each extraction pass
    reduces over the SUBLANE axis, the cheap VPU direction.

    Selection runs on PACKED sortable keys: the f32 score is mapped to a
    total-order-preserving int32 (IEEE trick: flip the non-sign bits of
    negatives), its low ``pos_bits`` cleared and the row position OR-ed
    in. One int32 word then carries (value, position): each of the blk_k
    extraction passes is a pure min-reduce + one equality mask (keys are
    UNIQUE — position bits make ties impossible, so the mask removes
    exactly one element and ties resolve to the lowest position, the
    first-occurrence contract). This halves the per-pass vreg ops vs
    carrying a separate value/index pair through the reduction tree.

    The price is ``pos_bits`` of score mantissa: emitted distances (and
    the selection boundary) are floored within a relative 2^(pos_bits-24)
    (≈1.2e-4 at maxlen 2048) — an order below the bf16 scan GEMM noise
    (~4e-3) these scores already carry in the shipped configuration.
    """
    rows = rows_ref[:]  # (maxlen_pad, d) compute dtype; padded rows zero
    qv = qv_ref[:]  # (C, d) compute dtype — pre-gathered query residuals
    qr = jax.lax.dot_general(
        rows, qv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_dot_prec(rows.dtype),
    )  # (maxlen_pad, C)
    # Within-list residual score ‖δ‖² − 2(q−c)·δ; padded rows carry the
    # caller's ≥1e30 r2 sentinel (their qr is 0: zero rows) so they sort
    # last yet stay below IVF_MASKED_KEY once packed — a list with fewer
    # than blk_k valid rows emits them, and the caller's id table maps
    # them to -1. Finite scores assumed (no ±inf/NaN reach this kernel).
    scores = r2_ref[:] - 2.0 * qr  # r2 is (maxlen_pad, 1): broadcast lanes
    _packed_extract(_packed_keys(scores, pos_bits), d_ref, p_ref, blk_k, pos_bits)


@functools.partial(
    ledgered_jit, "pallas.ivf_scan_select_pallas", static_argnames=("blk_k", "keep_pad", "interpret")
)
def ivf_scan_select_pallas(
    qv: jax.Array,
    rows: jax.Array,
    r2: jax.Array,
    blk_k: int,
    keep_pad: bool = False,
    interpret: bool = False,
):
    """Fused IVF bucketed scan: per-list residual GEMM + exact per-slot
    top-``blk_k``, one HBM pass over the index, scores VMEM-resident.

    Replaces the XLA scan's einsum → ``approx_min_k`` pipeline
    (models/knn.py `_bucketed_core`), whose measured cost was dominated by
    the selection (9.3 of 26 ms/call at the bench shape) and whose
    PartialReduce positional loss capped fast-config recall at ~0.945
    (benchmarks/README.md round-3 frontier). Exactness restores that
    recall headroom; fusion stops the (nlist, C, maxlen) score tensor
    from ever reaching HBM.

    Args:
      qv: (nlist, C, d) compute dtype — pre-gathered query residuals
        ``(queries − c_list)[bucket]`` (hoisted out of the kernel: dynamic
        per-row gathers don't belong inside; sequential HBM streaming of
        the pre-built buffer is the cheap direction).
      rows: (nlist, maxlen, d) compute dtype — residual list rows
        (index data; padded rows MUST be zero).
      r2: (nlist, maxlen) float32 — per-row ‖δ‖² with a ≥1e30 sentinel on
        invalid/padded rows (strictly below IVF_MASKED_D2).
      blk_k: per-slot selection width (≤ maxlen).

    Returns (best_d (nlist, blk_k, C) f32 ascending, best_p (nlist, blk_k,
    C) int32 row positions). Ties resolve to the lowest position; emitted
    distances are floored within a relative 2^(ceil(log2(maxlen))-24) of
    the f32 score (the packed-key mantissa trade — kernel docstring).
    """
    nlist, C, d = qv.shape
    maxlen = rows.shape[1]
    if blk_k > maxlen:
        raise ValueError(f"blk_k={blk_k} exceeds maxlen={maxlen}")
    ml_pad = _ceil_to(maxlen, 8)
    if ml_pad != maxlen:
        rows = jnp.pad(rows, ((0, 0), (0, ml_pad - maxlen), (0, 0)))
        r2 = jnp.pad(
            r2, ((0, 0), (0, ml_pad - maxlen)), constant_values=1e30
        )
    pos_bits = max(1, (ml_pad - 1).bit_length())
    if pos_bits > 16:
        raise ValueError(f"maxlen={maxlen} too large for packed selection")
    bk_pad = _ceil_to(blk_k, 8)
    best_d, best_p = pl.pallas_call(
        functools.partial(
            _ivf_scan_select_kernel, blk_k=blk_k, pos_bits=pos_bits
        ),
        name="ivf_scan_select",
        grid=(nlist,),
        in_specs=[
            pl.BlockSpec((None, C, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, ml_pad, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, ml_pad, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk_pad, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, bk_pad, C), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nlist, bk_pad, C), jnp.float32),
            jax.ShapeDtypeStruct((nlist, bk_pad, C), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(qv, rows, r2[..., None].astype(jnp.float32))
    if keep_pad:
        # Callers gathering rows from the (…, blk_k_pad) output keep the
        # 8-multiple lane width: slicing BEFORE a gather materializes an
        # unaligned-row copy, and gathering 64B-aligned rows then slicing
        # after measured ~1.7× faster (benchmarks/README.md round 3).
        # Pad rows carry (IVF_MASKED_D2, 0).
        return best_d, best_p
    return best_d[:, :blk_k], best_p[:, :blk_k]


# ---------------------------------------------------------------------------
# Fused IVF probe: centroid distances + EXACT per-query top-nprobe
# ---------------------------------------------------------------------------


def _probe_select_kernel(
    cent_ref, c2h_ref, qT_ref, q2_ref, d_ref, p_ref, *, nprobe, pos_bits
):
    """One query block per grid step: ‖q−c‖² against ALL centroids + exact
    top-nprobe per query, the (nlist, qb) distance tile VMEM-resident.

    Same layout discipline and packed-key extraction as
    ``_ivf_scan_select_kernel`` — here LISTS ride the sublanes and QUERIES
    the lanes, so the per-query selection reduces over sublanes. The f32
    GEMM runs at HIGHEST precision: probe distances feed the residual
    identity's cross-list ‖q−c‖² term, where bf16-magnitude noise corrupts
    the candidate ordering (models/knn.py probe_bucketed). Replacing the
    XLA ``approx_min_k(recall_target=0.95)`` makes probing EXACT — the one
    approximation that op added to probe coverage is gone.
    """
    cq = jax.lax.dot_general(
        cent_ref[:], qT_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (nlist_pad, qb)
    # True ‖q−c‖²: the ‖q‖² term is a per-query (lane) constant — it
    # cannot change this selection OR the downstream cross-list ranking,
    # but the emitted values ARE the user-visible distance components, so
    # keep them true distances. Padded centroid rows carry a 1e30 c2h
    # sentinel and never win (nprobe ≤ nlist enforced by callers).
    scores = c2h_ref[:] - 2.0 * cq + q2_ref[:]
    _packed_extract(
        _packed_keys(scores, pos_bits), d_ref, p_ref, nprobe, pos_bits
    )


@functools.partial(
    ledgered_jit, "pallas.probe_select_pallas", static_argnames=("nprobe", "block_q", "interpret")
)
def probe_select_pallas(
    centroids: jax.Array,
    queries: jax.Array,
    nprobe: int,
    block_q: int = 512,
    interpret: bool = False,
):
    """Exact IVF probe: (probe ids (q, nprobe) int32 ascending-by-distance,
    probe_d2 (q, nprobe) f32 true ‖q−c‖²) in one fused kernel.

    centroids: (nlist, d) — padded rows allowed if masked by the caller
    via huge norms; here rows are taken as-is and ``nprobe ≤ nlist`` is
    the caller's contract. queries: (q, d); q must divide block_q or be
    smaller. Emitted distances carry the packed-key mantissa floor
    (relative 2^(ceil(log2(nlist))-24) — see _ivf_scan_select_kernel).
    """
    nlist, d = centroids.shape
    q = queries.shape[0]
    qb = min(block_q, q)
    if q % qb:
        raise ValueError(f"q={q} not divisible by block_q={qb}")
    nl_pad = _ceil_to(nlist, 8)
    cent = jnp.asarray(centroids, jnp.float32)
    c2 = jnp.sum(jnp.square(cent), axis=1, keepdims=True)  # (nlist, 1)
    if nl_pad != nlist:
        cent = jnp.pad(cent, ((0, nl_pad - nlist), (0, 0)))
        c2 = jnp.pad(c2, ((0, nl_pad - nlist), (0, 0)), constant_values=1e30)
    pos_bits = max(1, (nl_pad - 1).bit_length())
    if pos_bits > 16:
        raise ValueError(f"nlist={nlist} too large for packed probe selection")
    qf = jnp.asarray(queries, jnp.float32)
    qT = qf.T  # (d, q)
    q2 = jnp.sum(jnp.square(qf), axis=1)[None, :]  # (1, q)
    np_pad = _ceil_to(nprobe, 8)
    best_d, best_p = pl.pallas_call(
        functools.partial(
            _probe_select_kernel, nprobe=nprobe, pos_bits=pos_bits
        ),
        name="ivf_probe_select",
        grid=(q // qb,),
        in_specs=[
            pl.BlockSpec((nl_pad, d), lambda i: (0, 0)),
            pl.BlockSpec((nl_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((d, qb), lambda i: (0, i)),
            pl.BlockSpec((1, qb), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((np_pad, qb), lambda i: (0, i)),
            pl.BlockSpec((np_pad, qb), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_pad, q), jnp.float32),
            jax.ShapeDtypeStruct((np_pad, q), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(cent, c2, qT, q2)
    return best_p[:nprobe].T, best_d[:nprobe].T


# ---------------------------------------------------------------------------
# Fused LinearRegression normal-equation statistics: one HBM pass
# ---------------------------------------------------------------------------


def _linreg_stats_kernel(x_ref, y_ref, m_ref, g_ref, xty_ref, cs_ref, ys_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_ref[:] = jnp.zeros_like(g_ref)
        xty_ref[:] = jnp.zeros_like(xty_ref)
        cs_ref[:] = jnp.zeros_like(cs_ref)
        ys_ref[:] = jnp.zeros_like(ys_ref)

    m = m_ref[:]  # (bn, 1) f32 {0,1}
    xb = x_ref[:] * m.astype(x_ref.dtype)
    yf = y_ref[:] * m  # (bn, 1) f32
    g_ref[:] += jax.lax.dot_general(
        xb, xb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_dot_prec(xb.dtype),
    )
    xf = xb.astype(jnp.float32)
    # Xᵀy on the VPU: a (1, bn)×(bn, d) MXU call would waste 127/128 of
    # the systolic array's M tiles; the row-weighted column sum is cheap
    # next to the Gram GEMM and rides the same x read.
    xty_ref[:] += jnp.sum(xf * yf, axis=0, keepdims=True)
    cs_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (m.shape[0], 128), 1)
    ys_ref[:] += jnp.sum(
        jnp.where(
            lane == 0, yf, jnp.where(lane == 1, yf * yf, jnp.where(lane == 2, m, 0.0))
        ),
        axis=0,
        keepdims=True,
    )


# ---------------------------------------------------------------------------
# Multinomial MM curvature: the C per-class Xᵀdiag(p_c)X blocks with x
# streamed through HBM once per class GROUP (shared tile), not once per class
# ---------------------------------------------------------------------------


#: 1024-row blocks: K=1024 per class GEMM ran 262 vs 185 TF/s for K=512 on
#: the measured config (d=1024, C=32, v5e) — deeper contractions amortize
#: the per-class accumulator switch.
SOFTMAX_CURV_BLOCK_N = 1024
#: VMEM budget for the resident (block_c, d, d) f32 accumulator stack; the
#: group width adapts to d (softmax_curv_block_c) so the budget, not the
#: class count, caps residency.
SOFTMAX_CURV_VMEM_BUDGET = 48 * 2**20


def softmax_curv_block_c(d: int, n_classes: int) -> int:
    """Class-group width: largest POWER OF TWO whose (Cb, d, d) f32
    accumulator stack fits the VMEM budget (≥1; measured: 8 beats the
    non-power 12 at d=1024 — Mosaic tiles power-of-two stacks better)."""
    cap = max(1, min(n_classes, SOFTMAX_CURV_VMEM_BUDGET // (4 * d * d)))
    return 1 << (cap.bit_length() - 1)


def _softmax_curv_kernel(x_ref, p_ref, hw_ref, hwb_ref, *, block_c):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hw_ref[:] = jnp.zeros_like(hw_ref)
        hwb_ref[:] = jnp.zeros_like(hwb_ref)

    x = x_ref[:]  # (bn, d) compute dtype — read ONCE for all block_c classes
    p = p_ref[:]  # (bn, block_c) f32 pre-masked probabilities
    for c in range(block_c):  # static unroll; accumulators stay VMEM-resident
        xw = x * p[:, c : c + 1].astype(x.dtype)
        # Curvature blocks are the MM preconditioner, not the answer (the
        # exact gradient pins the fixed point — models/logistic_regression
        # .py): fast DEFAULT precision, f32 accumulate.
        hw_ref[c] += jax.lax.dot_general(
            xw, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        # The intercept border Xᵀp_c rides the same tile on the VPU.
        hwb_ref[c : c + 1, :] += jnp.sum(
            xw.astype(jnp.float32), axis=0, keepdims=True
        )


@functools.partial(
    ledgered_jit, "pallas.softmax_curvature_pallas", static_argnames=("block_n", "block_c", "interpret")
)
def softmax_curvature_pallas(
    x: jax.Array,
    p: jax.Array,
    block_n: int = SOFTMAX_CURV_BLOCK_N,
    block_c: int = 8,
    interpret: bool = False,
):
    """Per-class curvature hw[c] = Xᵀdiag(p_c)X and border hwb[c] = Xᵀp_c
    for every class, with x read from HBM once per class GROUP.

    The XLA lowering of the per-class loop
    (models/logistic_regression._stream_softmax_stats) re-reads the (n, d)
    operand for every one of the C classes — at C=32, d=1024 bf16 that
    traffic caps the multinomial MM pass at ~0.85× the A100 convention
    (benchmarks/README.md). Here each VMEM-resident x tile feeds block_c
    class GEMMs before the next tile loads, dividing x traffic by block_c
    (the one-HBM-pass partition-kernel idiom of ``linreg_stats_pallas`` /
    the reference's dgemmCov, rapidsml_jni.cu:109-127, extended over a
    class axis). One ``pallas_call`` per class group — the group's p
    columns arrive as their own (n, block_c) operand, whose full last dim
    keeps every block shape legal under Mosaic's lane tiling for ANY
    block_c.

    x: (n, d) compute dtype (bfloat16 = the intended speed mode);
    p: (n, C) f32 — softmax probabilities ALREADY masked (p · row_mask).
    The last group may be narrower than block_c.
    Returns (hw (C, d, d) f32, hwb (C, d) f32).
    """
    n, d = x.shape
    n_classes = p.shape[1]
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n={n} not divisible by block_n={bn}")
    bc = min(block_c, n_classes)
    if bc * d * d * 4 > SOFTMAX_CURV_VMEM_BUDGET:
        raise ValueError(
            f"block_c={bc}, d={d}: accumulator stack exceeds the VMEM budget"
        )
    pf = jnp.asarray(p, jnp.float32)
    hw_parts, hwb_parts = [], []
    for g0 in range(0, n_classes, bc):
        gc = min(bc, n_classes - g0)
        hw_g, hwb_g = pl.pallas_call(
            functools.partial(_softmax_curv_kernel, block_c=gc),
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((bn, d), lambda i: (i, 0)),
                pl.BlockSpec((bn, gc), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((gc, d, d), lambda i: (0, 0, 0)),
                pl.BlockSpec((gc, d), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((gc, d, d), jnp.float32),
                jax.ShapeDtypeStruct((gc, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=100 * 2**20,
            )
            if not interpret
            else None,
            interpret=interpret,
        )(x, jax.lax.slice_in_dim(pf, g0, g0 + gc, axis=1))
        hw_parts.append(hw_g)
        hwb_parts.append(hwb_g)
    if len(hw_parts) == 1:
        return hw_parts[0], hwb_parts[0]
    return jnp.concatenate(hw_parts), jnp.concatenate(hwb_parts)


@functools.partial(
    ledgered_jit, "pallas.linreg_stats_pallas", static_argnames=("block_n", "interpret")
)
def linreg_stats_pallas(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    block_n: int = 512,
    interpret: bool = False,
):
    """One-HBM-pass fused (XᵀX, Xᵀy, Σx, Σy, Σy², n) over masked rows —
    the LinearRegression analogue of ``gram_colsum_pallas`` (SURVEY §7.6:
    "literally the PCA reduction with an extra Xᵀy"). The XLA path's
    separate dots re-read X for Xᵀy and the sums (+30% wall measured at
    1M×1024 bf16); here every statistic rides the Gram's single read with
    the accumulators VMEM-resident.

    x: (n, d) compute dtype; y: (n,) any float; mask: (n,) {0,1}.
    Returns (xtx (d, d) f32, xty (d,) f32, sx (d,) f32, sy, syy, n — all
    f32 scalars; exact row counts up to 2^24 rows per call).
    """
    n, d = x.shape
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"n={n} not divisible by block_n={bn}")
    if d * d * 4 > GRAM_COLSUM_VMEM_BUDGET:
        raise ValueError(f"d={d}: (d, d) f32 accumulator exceeds the VMEM budget")
    y2 = jnp.asarray(y, jnp.float32).reshape(n, 1)
    m2 = jnp.asarray(mask, jnp.float32).reshape(n, 1)
    g, xty, cs, ys = pl.pallas_call(
        _linreg_stats_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 * 2**20
        )
        if not interpret
        else None,
        interpret=interpret,
    )(x, y2, m2)
    return g, xty[0], cs[0], ys[0, 0], ys[0, 1], ys[0, 2]
