"""LogisticRegression — distributed full-batch Newton (IRLS) / GD.

BASELINE.json config #4 pairs LogisticRegression with the normal-equations
family. TPU-first shape: every Newton iteration is two sharded GEMMs
(gradient Xᵀr and Hessian XᵀDX) + psum over ICI, then a d×d Cholesky solve
on device — the same partition-kernel + collective + finalize frame as PCA
(SURVEY.md §7 step 6). The whole optimization loop runs inside ONE
``lax.while_loop`` under ``shard_map``: data stays sharded on device for
all iterations, nothing returns to the host until convergence.

Objective (Spark ML LogisticRegression, ``standardization=False``):

    min_w 1/n Σ log(1 + exp(−ŷᵢ·(xᵢw + b))) + λ/2·‖w‖₂²   (binary, L2)

Binary labels are {0, 1}. Multinomial (softmax) runs MM-Newton: the exact
gradient with per-class upper-bound curvature blocks (the (C·d)² Hessian is
never materialized — see _stream_softmax_stats_fn). Intercept is
unpenalized, as in Spark.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core.dataset import as_column, as_matrix, with_column
from spark_rapids_ml_tpu.core.params import (
    Estimator,
    HasFeaturesCol,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasTol,
    Model,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.sharding import shard_rows
from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


class LogisticTrainingSummary(NamedTuple):
    """Final objective + iterations, Spark's training-summary shape."""

    loss: Optional[float]
    numIter: int
    n_rows: int


class LogisticSolution(NamedTuple):
    coefficients: np.ndarray  # (d,) binary or (c, d) multinomial
    intercept: np.ndarray  # scalar (binary) or (c,)
    n_iter: int
    n_rows: int
    loss: Optional[float] = None  # final training objective (binary path)


def _pcg_solve(h, g, x0, max_iter: Optional[int] = None, rtol: float = 1e-2):
    """Jacobi-preconditioned CG on the SPD Newton system ``h @ x = g``.

    XLA's direct LU/Cholesky for a single d×d system is a sequential
    blocked factorization — ~10 ms at d=1024 on a v5e chip, MORE than the
    whole fused statistics pass over 2^19 rows — so the TPU path solves
    iteratively. CG is pure matvec/axpy (MXU/VPU-friendly) and this is an
    inexact-Newton inner solve: a 1e-2 relative-residual direction
    preserves outer convergence (the gradient sets the fixed point, the
    Hessian only preconditions), and the previous iteration's direction
    warm-starts the next. Terminates on negative-curvature breakdown
    (truncated-Newton style: fast-precision Hessians of near-separable
    unregularized fits can be numerically indefinite); if breakdown hits
    before any CG step succeeds, returns the preconditioned gradient
    instead of the stale warm start (Steihaug convention).
    """
    d = h.shape[0]
    if max_iter is None:
        # CG is exact at d iterations, but past ~128 the sequential
        # latency of the tiny matvecs rivals the direct solve's cost —
        # at that point the inexact-Newton outer loop is the cheaper way
        # to buy accuracy, so truncate (forcing-term philosophy).
        max_iter = min(d, 128)
    dinv = 1.0 / jnp.maximum(jnp.diagonal(h), 1e-30)
    gnorm = jnp.linalg.norm(g)

    r0 = g - h @ x0
    z0 = dinv * r0

    def cond(c):
        _, r, _, _, it, _ = c
        return jnp.logical_and(it < max_iter, jnp.linalg.norm(r) > rtol * gnorm)

    def body(c):
        x, r, p, rz, it, nstep = c
        hp = h @ p
        php = p @ hp
        broke = php <= 0.0
        alpha = jnp.where(broke, 0.0, rz / jnp.where(broke, 1.0, php))
        x = x + alpha * p
        r = r - alpha * hp
        z = dinv * r
        rz2 = r @ z
        p = z + (rz2 / jnp.where(rz != 0.0, rz, 1.0)) * p
        # On breakdown, force the loop to exit (it = max_iter) rather than
        # spinning out the remaining matvecs on a frozen residual.
        return (
            x, r, p, rz2,
            jnp.where(broke, max_iter, it + 1),
            nstep + jnp.where(broke, 0, 1),
        )

    x, _, _, _, _, nstep = jax.lax.while_loop(
        cond, body, (x0, r0, z0, r0 @ z0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    )
    # nstep == 0 means either the warm start already satisfied the
    # tolerance (keep it — it IS the solution) or the very first curvature
    # was non-positive (x is then the stale warm start, unrelated to the
    # CURRENT gradient: fall back to the preconditioned gradient,
    # Steihaug convention).
    warm_ok = jnp.linalg.norm(r0) <= rtol * gnorm
    return jnp.where((nstep > 0) | warm_ok, x, dinv * g)


def _pallas_newton_applicable(shape, cd, ad, use_pallas: Optional[bool] = None) -> bool:
    """Fused single-HBM-pass Newton step (ops/pallas_kernels.newton_stats_pallas)
    of the IN-MEMORY fit (`_newton_fn_cached`: the whole loop in one program
    over rows cast to bfloat16 once, before it): TPU backend, bfloat16
    compute (the speed mode the kernel exists for — at float32 the fusion
    saves no wall-clock over XLA's lowering), f32 accumulate, lane-aligned d,
    block-divisible rows, VMEM-resident (d, d) Hessian. The STREAMING fold
    (`fit_logistic_stream`, the daemon's job) has its own kernel and gate:
    `newton_fold_pallas` behind `_fused_newton_fold_applicable`, on float32
    rows at any width."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok
    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        NEWTON_STATS_BLOCK_N,
        NEWTON_STATS_VMEM_BUDGET,
    )

    if not _pallas_backend_ok(use_pallas):
        return False
    n, d = shape
    return (
        jnp.dtype(cd) == jnp.bfloat16
        and jnp.dtype(ad) == jnp.float32
        and n % NEWTON_STATS_BLOCK_N == 0
        and d % 128 == 0
        and d * d * 4 <= NEWTON_STATS_VMEM_BUDGET
    )


def _solve_newton_system(h_ww, h_wb, h_bb, grad_w, grad_b, reg, fit_intercept,
                         accum):
    """Direct solve of the (optionally bordered) Newton system → (dw, db).

    reg > 0: h_ww is symmetric PD — block elimination with LU solves,
    kept bit-identical to the historical path. reg == 0: the Hessian is
    only PSD — collinear/one-hot/constant columns make h_ww singular,
    and one-hot features plus an intercept add a shift-invariance null
    direction that lives in the BORDERED [w; b] system (its Schur
    complement is exactly 0), so flooring h_ww alone still lets the
    intercept step blow up (ADVICE r5(a) — the multinomial finding; the
    binomial Newton shares the failure class). Floor the diagonal of the
    whole system being solved: the floor must clear the accumulation
    noise of the summed statistics — measured negative eigenvalues reach
    a few ulps of the trace — so scale machine epsilon by a 1e3 margin.
    Still a minimum-norm-direction tiebreak, orders of magnitude below
    any statistically meaningful curvature."""
    d = h_ww.shape[0]
    if reg > 0.0:
        if fit_intercept:
            hinv_hwb = jnp.linalg.solve(h_ww, h_wb)
            hinv_gw = jnp.linalg.solve(h_ww, grad_w)
            schur = jnp.maximum(h_bb - h_wb @ hinv_hwb, 1e-12)
            db = (grad_b - h_wb @ hinv_gw) / schur
            dw = hinv_gw - hinv_hwb * db
            return dw, db
        return jnp.linalg.solve(h_ww, grad_w), jnp.zeros((), accum)
    noise = 1e3 * jnp.finfo(accum).eps
    if fit_intercept:
        joint = jnp.concatenate([
            jnp.concatenate([h_ww, h_wb[:, None]], axis=1),
            jnp.concatenate([h_wb, h_bb[None]])[None, :],
        ])
        eps = noise * jnp.trace(joint) / (d + 1) + 1e-12
        cho = jax.scipy.linalg.cho_factor(
            joint + eps * jnp.eye(d + 1, dtype=accum), lower=True
        )
        sol = jax.scipy.linalg.cho_solve(
            cho, jnp.concatenate([grad_w, grad_b[None]])
        )
        return sol[:d], sol[d]
    eps = noise * jnp.trace(h_ww) / d + 1e-12
    cho = jax.scipy.linalg.cho_factor(
        h_ww + eps * jnp.eye(d, dtype=accum), lower=True
    )
    return jax.scipy.linalg.cho_solve(cho, grad_w), jnp.zeros((), accum)


def _newton_fn(mesh: Mesh, reg: float, fit_intercept: bool, max_iter: int, tol: float, ad: str):
    # use_pallas / compute_dtype are read at build time so they participate
    # in the cache key (same snapshot pattern as ops/gram._streaming_update).
    return _newton_fn_cached(
        mesh, reg, fit_intercept, max_iter, tol, ad,
        jnp.dtype(config.get("compute_dtype")).name, bool(config.get("use_pallas")),
    )


@functools.lru_cache(maxsize=32)
def _newton_fn_cached(
    mesh: Mesh, reg: float, fit_intercept: bool, max_iter: int, tol: float, ad: str,
    cd: str, use_pallas: bool,
):
    """Binary Newton-IRLS, whole loop in one compiled SPMD program."""
    accum = jnp.dtype(ad)

    def shard(x, y, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        with mm_precision(accum):  # true-f32 dots (TPU default is bf16)
            return _shard(x, y, mask)

    def _shard(x, y, mask):
        xc = x.astype(accum)
        yc = y.astype(accum)
        maskc = mask.astype(accum)
        # Integer sum: an f32 sum of ones saturates at 2^24 rows/shard.
        n = mr.reduce_sum(jnp.sum(maskc.astype(jnp.int32)).astype(accum), DATA_AXIS)
        d = x.shape[1]
        fused = _pallas_newton_applicable(x.shape, cd, ad, use_pallas)
        if fused:
            # One cast before the loop; every iteration then streams half
            # the HBM bytes and runs single-pass MXU dots.
            xb16 = x.astype(jnp.dtype(cd))
            y2 = yc.reshape(-1, 1)
            m2 = maskc.reshape(-1, 1)

        def grad_hess(w, b):
            if fused:
                # One HBM pass over x per iteration: z/residual/weight are
                # row-local, so the matvec, both vector statistics, and
                # the Hessian GEMM share one resident tile of x.
                from spark_rapids_ml_tpu.ops.pallas_kernels import newton_stats_pallas

                gw, gb, hww, hwb, hbb = newton_stats_pallas(xb16, y2, m2, w, b)
                grad_w = mr.reduce_sum(gw, DATA_AXIS) / n + reg * w
                grad_b = mr.reduce_sum(gb, DATA_AXIS) / n
                h_ww = mr.reduce_sum(hww, DATA_AXIS) / n + reg * jnp.eye(d, dtype=accum)
                h_wb = mr.reduce_sum(hwb, DATA_AXIS) / n
                h_bb = mr.reduce_sum(hbb, DATA_AXIS) / n
                return grad_w, grad_b, h_ww, h_wb, h_bb
            z = xc @ w + b
            p = jax.nn.sigmoid(z)
            r = (p - yc) * maskc  # dL/dz, masked
            grad_w = mr.reduce_sum(xc.T @ r, DATA_AXIS) / n + reg * w
            grad_b = mr.reduce_sum(jnp.sum(r), DATA_AXIS) / n
            wgt = jnp.maximum(p * (1.0 - p), 1e-10) * maskc
            xw = xc * wgt[:, None]
            # The Hessian is a preconditioner, not the answer: inexact
            # Newton converges to the same optimum (the gradient sets the
            # fixed point), so the dominant n·d² GEMM runs at fast DEFAULT
            # precision; gradients keep the surrounding full-f32 scope.
            h_ww = mr.reduce_sum(
                jax.lax.dot_general(xw, xc, (((0,), (0,)), ((), ())),
                                    preferred_element_type=accum,
                                    precision=jax.lax.Precision.DEFAULT),
                DATA_AXIS,
            ) / n + reg * jnp.eye(d, dtype=accum)
            h_wb = mr.reduce_sum(jnp.sum(xw, axis=0), DATA_AXIS) / n
            h_bb = mr.reduce_sum(jnp.sum(wgt), DATA_AXIS) / n
            return grad_w, grad_b, h_ww, h_wb, h_bb

        def loss_of(w, b):
            z = xc @ w + b
            # log(1+e^-z) for y=1, log(1+e^z) for y=0, numerically stable.
            per = (jax.nn.softplus(z) - yc * z) * maskc
            return mr.reduce_sum(jnp.sum(per), DATA_AXIS) / n + 0.5 * reg * (w @ w)

        # Trace-time solver choice: XLA's sequential LU costs ~10 ms at
        # d=1024 on TPU (more than the whole stats pass), so accelerator
        # backends solve with warm-started Jacobi-CG; on CPU LAPACK's
        # direct factorization is fast AND exact — keep it.
        direct_solve = not config.backend_is_tpu()

        def body(carry):
            w, b, _, it, prev_dir = carry
            grad_w, grad_b, h_ww, h_wb, h_bb = grad_hess(w, b)
            if direct_solve:
                # Bordered (d+1) system via block elimination (reg > 0)
                # or floored joint Cholesky (reg == 0, singular-safe):
                # [H_ww h_wb][dw]   [g_w]
                # [h_wbᵀ h_bb][db] = [g_b]
                dw, db = _solve_newton_system(
                    h_ww, h_wb, h_bb, grad_w, grad_b, reg, fit_intercept,
                    accum,
                )
                sol = jnp.concatenate([dw, db[None]]) if fit_intercept else dw
            elif fit_intercept:
                # The same bordered SPD system, solved whole by CG. At
                # reg == 0 it is only PSD (the same null directions the
                # direct path floors — _solve_newton_system): floor the
                # diagonal identically, or CG diverges along the null
                # space on exactly the inputs the Cholesky path survives.
                hfull = jnp.pad(h_ww, ((0, 1), (0, 1)))
                hfull = (
                    hfull.at[d, :d].set(h_wb).at[:d, d].set(h_wb).at[d, d].set(h_bb)
                )
                if reg <= 0.0:
                    eps = (1e3 * jnp.finfo(accum).eps
                           * jnp.trace(hfull) / (d + 1) + 1e-12)
                    hfull = hfull + eps * jnp.eye(d + 1, dtype=accum)
                gfull = jnp.concatenate([grad_w, grad_b[None]])
                sol = _pcg_solve(hfull, gfull, prev_dir)
                dw, db = sol[:d], sol[d]
            else:
                hmat = h_ww
                if reg <= 0.0:
                    eps = (1e3 * jnp.finfo(accum).eps
                           * jnp.trace(h_ww) / d + 1e-12)
                    hmat = h_ww + eps * jnp.eye(d, dtype=accum)
                sol = _pcg_solve(hmat, grad_w, prev_dir)
                dw, db = sol, jnp.zeros((), accum)
            new_w = w - dw
            new_b = b - db
            delta = jnp.sqrt(jnp.sum(dw * dw) + db * db)
            return new_w, new_b, delta, it + 1, sol

        def cond(carry):
            w, _, delta, it, _ = carry
            if fused and tol > 0.0:
                # (tol=0 keeps its "exactly max_iter steps" contract —
                # benchmarks and step-count-controlled callers rely on it.)
                # The bf16 rounding of x (and of w in the kernel's matvec)
                # puts a relative noise floor under the gradient — Newton
                # steps plateau around 2.5e-3·‖w‖ (measured, d=1k gaussian)
                # instead of contracting. Below 2^-8·‖w‖ steps are noise,
                # so stop there rather than burning max_iter on an
                # unreachable absolute tol.
                tol_eff = jnp.maximum(
                    jnp.asarray(tol, accum),
                    jnp.asarray(2.0**-8, accum) * jnp.linalg.norm(w),
                )
            else:
                tol_eff = tol
            return jnp.logical_and(it < max_iter, delta > tol_eff)

        w0 = jnp.zeros((d,), accum)
        b0 = jnp.zeros((), accum)
        dir0 = jnp.zeros((d + 1 if fit_intercept else d,), accum)
        w, b, _, n_iter, _ = jax.lax.while_loop(
            cond, body, (w0, b0, jnp.array(jnp.inf, accum), 0, dir0)
        )
        return w, b, n_iter, loss_of(w, b)

    f = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,  # pallas_call out_shapes carry no vma annotation
    )
    return ledgered_jit("logreg.newton_stats", f)


def fit_logistic_regression(
    x: np.ndarray,
    y: np.ndarray,
    reg: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
) -> LogisticSolution:
    from spark_rapids_ml_tpu.parallel.sharding import require_single_process

    require_single_process("fit_logistic_regression (n_classes inferred from local labels)")
    mesh = mesh or default_mesh()
    x = np.asarray(x)
    y = np.asarray(y).reshape(-1)
    if x.shape[0] != y.shape[0]:
        raise ValueError(f"X rows {x.shape[0]} != y rows {y.shape[0]}")
    classes = np.unique(y)
    n_classes = len(classes)
    if n_classes < 2:
        raise ValueError("need at least 2 classes in the label column")
    if not np.array_equal(classes, np.arange(n_classes)):
        raise ValueError(
            f"labels must be 0..{n_classes - 1} (Spark ML convention); got {classes[:8]}"
        )
    ad = config.get("accum_dtype")
    with trace_span("logreg fit"):
        xs, mask, n_true = shard_rows(x, mesh)
        if n_classes == 2:
            ys, _, _ = shard_rows(y.astype(np.float64), mesh)
            fn = _newton_fn(mesh, float(reg), bool(fit_intercept), int(max_iter), float(tol), ad)
            w, b, n_iter, loss = jax.device_get(fn(xs, ys, mask))
            return LogisticSolution(
                coefficients=np.asarray(w, dtype=np.float64),
                intercept=np.asarray(b, dtype=np.float64),
                n_iter=int(n_iter),
                n_rows=n_true,
                loss=float(loss),
            )
        # Multinomial MM-Newton: the SAME machinery as the streaming path
        # (exact softmax gradient + per-class upper-bound curvature,
        # _stream_softmax_stats_fn) driven over the in-memory shards —
        # one device round-trip per iteration, converging in tens of
        # iterations where the round-2 Nesterov-GD sidecar needed
        # hundreds, and single source of truth for the update rule.
        accum = jnp.dtype(ad)
        state_bytes = n_classes * x.shape[1] ** 2 * accum.itemsize
        if state_bytes > 2**31:
            # The replicated (C, d, d) curvature state is the price of
            # second-order steps; past ~2 GB it would crowd out the data.
            raise ValueError(
                f"multinomial MM-Newton state is C·d² = {state_bytes / 2**30:.1f}"
                f" GiB (C={n_classes}, d={x.shape[1]}, {accum.name}) — too "
                "large for a replicated accumulator. Reduce d (feature "
                "hashing/PCA) or C, or use a float32 accum_dtype."
            )
        ys, _, _ = shard_rows(y.astype(np.float32), mesh)
        update = _stream_softmax_stats_fn(mesh, n_classes, ad)
        mm_step = _stream_multinomial_step_fn(float(reg), bool(fit_intercept), ad)
        W = jnp.zeros((x.shape[1], n_classes), accum)
        b = jnp.zeros((n_classes,), accum)
        n_iter = 0
        for it in range(max_iter):
            state = stream_softmax_zero_state(x.shape[1], n_classes, accum)
            gw, gb, hw, hwb, hbb, _, n = update(state, W, b, xs, ys, mask)
            W, b, delta = mm_step(gw, gb, hw, hwb, hbb, n, W, b)
            n_iter = it + 1
            if float(delta) <= tol:
                break
        return LogisticSolution(
            coefficients=np.asarray(
                jax.device_get(W), dtype=np.float64
            ).T,  # (c, d) Spark layout
            intercept=np.asarray(jax.device_get(b), dtype=np.float64),
            n_iter=n_iter,
            n_rows=n_true,
        )


# ---------------------------------------------------------------------------
# Streaming (out-of-HBM) Newton: one host scan per iteration
# ---------------------------------------------------------------------------


_M_FOLD_PATH = metrics.counter(
    "srml_logreg_fold_path_total",
    "Dispatches of the streaming Newton fold (logreg.streaming_update / "
    "_group) by the body their program was built with: path=fused (one HBM "
    "read of the batch through newton_fold_pallas) or path=xla (CPU, rows or "
    "accumulator not float32, ragged shard rows, a width on the 128-lane "
    "grid, a lane-padded (d, d) accumulator over the kernel's VMEM budget)",
)


def _fused_newton_fold_applicable(
    shard_shape, x_dtype, ad, use_pallas: Optional[bool] = None
) -> bool:
    """`_stream_grad_hess_shard_fn`'s gate for the one-read kernel
    (ops/pallas_kernels.newton_fold_pallas), by what the code can observe:
    TPU backend, float32 rows and float32 accumulate (the kernel's gradient
    and loss are float32 sums of float32 rows), shard rows in multiples of
    512 (the kernel picks its row block from d and the rows), the
    lane-padded (dp, dp) float32 Hessian inside the kernel's VMEM budget
    (constants imported from the kernel so the two cannot drift) — and a
    width the chip keeps ROWS MINOR. The TPU's default layout of an (m, d)
    float32 array is the order that pads fewer bytes, row-major on a tie:
    with m on the lane grid the rows are minor iff ceil(d / 8) · 8 <
    ceil(d / 128) · 128 (asked of the v5e compiler at 28 shapes, PERF.md
    §6, PR 33: d = 3000, 200, 1016, 3064 rows minor; 1024, 2048, 1020, 3068
    row-major). There the kernel's `x.T` is a bitcast and the batch is read
    as it lies; on the lane grid it would be a transposing copy of the
    batch first, and the XLA body is as fast without it (eight 65,536-row
    folds on a v5e: d = 1024 13.98 ms XLA, 14.99 so; d = 2048 38.38, 39.97;
    but d = 3000 79.7 against 57.0 and d = 200 1.95 against 1.85)."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    if not _pallas_backend_ok(use_pallas):
        return False
    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        NEWTON_FOLD_ROW_MULTIPLE,
        NEWTON_FOLD_VMEM_BUDGET,
        _ceil_to,
    )

    m, d = shard_shape
    dp = _ceil_to(d, 128)
    return (
        jnp.dtype(x_dtype) == jnp.dtype(jnp.float32)
        and jnp.dtype(ad) == jnp.dtype(jnp.float32)
        and m > 0
        and m % NEWTON_FOLD_ROW_MULTIPLE == 0
        and _ceil_to(d, 8) < dp
        and dp * dp * 4 <= NEWTON_FOLD_VMEM_BUDGET
    )


def _seeded_hessian_width(mesh: Mesh, fused, x) -> int:
    """The lane-padded width dp a fused body keeps its running (..., d, d)
    curvature at for batches like `x` — where its kernel is SEEDED with it:
    the gate `fused(shard_shape, dtype)` holds and the mesh has one data
    device, so no psum sits between a batch's product and the state's add.
    0 elsewhere."""
    from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to

    n_data = mesh.shape[DATA_AXIS]
    seeded = n_data == 1 and fused((x.shape[0] // n_data, x.shape[1]), x.dtype)
    return _ceil_to(x.shape[1], 128) if seeded else 0


def _pad_hessian(hww, dp: int):
    """(..., d, d) -> (..., dp, dp), zeros past d: exact, and undone by
    `[..., :d, :d]`."""
    d = hww.shape[-1]
    if d == dp:
        return hww
    return jnp.pad(hww, ((0, 0),) * (hww.ndim - 2) + ((0, dp - d),) * 2)


@functools.lru_cache(maxsize=32)
def _stream_grad_hess_shard_fn(mesh: Mesh, ad: str, use_pallas: bool = False):
    """One batch's Newton statistics at fixed (w, b), added to the running
    ones under ``shard_map``: (*state, w, b, x, y, mask) -> state with
    state = (gw (d,), gb (), hww (d, d), hwb (d,), hbb (), loss (), n ()).
    The one body of `_stream_grad_hess_fn` and `_stream_grad_hess_group_fn`.

    Raw sums — normalization by n and the L2 term are applied in the
    finalize step once the scan's true row count is known.

    Where `_fused_newton_fold_applicable` holds for a shard's rows (TPU
    backend, float32 rows and accumulate, whole blocks, a width off the
    lane grid — d = 3000 — whose lane-padded Hessian fits VMEM) the batch is
    read from HBM ONCE: `newton_fold_pallas` takes logits, gradient, loss,
    border and row count in float32 from each float32 tile and casts
    `x·wgt` and `x` to bfloat16 in VMEM for the Hessian product — this
    body's arithmetic in another order of additions. It reads the batch as
    the chip keeps it at such a width, rows minor: `x.T` is a bitcast. On
    one data device the running Hessian is seeded into the kernel,
    lane-padded: `hww` may arrive at (d, d) or already at (dp, dp) (the
    group program pads once) and leaves as it came. Elsewhere — the CPU,
    the tests' float64 profile, ragged rows, a width on the lane grid
    (row-major on the chip), d over the budget — the XLA body below runs,
    unchanged: three reads of the batch. `use_pallas` is the builder-time snapshot of the
    config flag (part of the cache key, never read inside the trace).
    """
    accum = jnp.dtype(ad)
    # The kernel adds to the running Hessian itself only where no psum sits
    # between a batch's product and the state's add: one data device.
    seed = mesh.shape[DATA_AXIS] == 1

    def fused_shard(gw, gb, hww, hwb, hbb, loss, n, w, b, x, y, mask):
        from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to, newton_fold_pallas

        d = x.shape[1]
        dp = _ceil_to(d, 128)
        if seed:
            bgw, bgb, h, bhwb, bhbb, bloss, bn = newton_fold_pallas(
                x.T, y, mask, w, b, hww=_pad_hessian(hww, dp))
            hww = h if hww.shape[0] == dp else h[:d, :d]
        else:
            bgw, bgb, h, bhwb, bhbb, bloss, bn = newton_fold_pallas(
                x.T, y, mask, w, b)
            hww = hww + mr.reduce_sum(h[:d, :d], DATA_AXIS)
        return (
            gw + mr.reduce_sum(bgw, DATA_AXIS),
            gb + mr.reduce_sum(bgb, DATA_AXIS),
            hww,
            hwb + mr.reduce_sum(bhwb, DATA_AXIS),
            hbb + mr.reduce_sum(bhbb, DATA_AXIS),
            loss + mr.reduce_sum(bloss, DATA_AXIS),
            n + mr.reduce_sum(bn, DATA_AXIS),
        )

    def xla_shard(gw, gb, hww, hwb, hbb, loss, n, w, b, x, y, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        with mm_precision(accum):
            xc = x.astype(accum)
            yc = y.astype(accum)
            maskc = mask.astype(accum)
            z = xc @ w + b
            p = jax.nn.sigmoid(z)
            r = (p - yc) * maskc
            wgt = jnp.maximum(p * (1.0 - p), 1e-10) * maskc
            xw = xc * wgt[:, None]
            bloss = jnp.sum((jax.nn.softplus(z) - yc * z) * maskc)
            bn = jnp.sum(maskc.astype(jnp.int32)).astype(accum)
            return (
                gw + mr.reduce_sum(xc.T @ r, DATA_AXIS),
                gb + mr.reduce_sum(jnp.sum(r), DATA_AXIS),
                hww
                + mr.reduce_sum(
                    jax.lax.dot_general(
                        xw, xc, (((0,), (0,)), ((), ())),
                        preferred_element_type=accum,
                        # Preconditioner-only (see _newton_fn): fast path.
                        precision=jax.lax.Precision.DEFAULT,
                    ),
                    DATA_AXIS,
                ),
                hwb + mr.reduce_sum(jnp.sum(xw, axis=0), DATA_AXIS),
                hbb + mr.reduce_sum(jnp.sum(wgt), DATA_AXIS),
                loss + mr.reduce_sum(bloss, DATA_AXIS),
                n + mr.reduce_sum(bn, DATA_AXIS),
            )

    def shard(gw, gb, hww, hwb, hbb, loss, n, w, b, x, y, mask):
        fused = _fused_newton_fold_applicable(x.shape, x.dtype, ad, use_pallas)
        return (fused_shard if fused else xla_shard)(
            gw, gb, hww, hwb, hbb, loss, n, w, b, x, y, mask)

    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P(),
                  P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(),) * 7,
        check_vma=False,  # pallas_call out_shapes carry no vma annotation
    )


def _fold_path_counter(mesh: Mesh, counter, fused):
    """`on_dispatch` hook of a streaming fold's two programs (one batch, a
    group): one increment of `counter` a dispatch, under the path the
    program of that batch shape was built with — `fused(shard_shape,
    dtype)`, the shard body's own predicate, asked once a shape."""

    @functools.lru_cache(maxsize=None)
    def path(shape, dtype) -> str:
        shard = (shape[0] // mesh.shape[DATA_AXIS], shape[1])
        return "fused" if fused(shard, dtype) else "xla"

    def count(state, w, b, x, y, mask):
        first = x[0] if isinstance(x, tuple) else x  # a group is one shape
        counter.inc(path=path(first.shape, first.dtype))

    return count


def _newton_fold_path_counter(mesh: Mesh, ad: str, use_pallas: bool):
    return _fold_path_counter(mesh, _M_FOLD_PATH, lambda shard, dtype: (
        _fused_newton_fold_applicable(shard, dtype, ad, use_pallas)))


def _stream_grad_hess_fn(mesh: Mesh, ad: str):
    """Jitted donated accumulate of one batch's Newton statistics at fixed
    (w, b): (state, w, b, x, y, mask) -> state
    (`_stream_grad_hess_shard_fn`). `use_pallas` is read here, at build
    time, so it keys the cached programs (the `_stream_softmax_stats_fn`
    snapshot pattern)."""
    return _stream_grad_hess_cached(mesh, ad, bool(config.get("use_pallas")))


@functools.lru_cache(maxsize=32)
def _stream_grad_hess_cached(mesh: Mesh, ad: str, use_pallas: bool):
    f = _stream_grad_hess_shard_fn(mesh, ad, use_pallas)

    @functools.partial(ledgered_jit, "logreg.streaming_update", donate_argnums=(0,))
    def update(state, w, b, x, y, mask):
        return f(*state, w, b, x, y, mask)

    update.on_dispatch = _newton_fold_path_counter(mesh, ad, use_pallas)
    return update


def _stream_grad_hess_group_fn(mesh: Mesh, ad: str):
    """The same accumulate over a GROUP of device-resident batches in one
    program: (state, w, b, xs, ys, masks) -> state, the three tuples of
    equal length. Batch by batch, in order, through the one shard function
    `_stream_grad_hess_fn` runs — the arithmetic of len(xs) calls of it,
    for one dispatch (the daemon's cached pass, as kmeans'
    `_stream_group_fn`). The barrier after each batch — over the state AND
    the iterate the next batch reads — is what keeps it so: without it XLA
    merges the batches' products over their shared operand `w` and their
    accumulations, and a group of two is no longer bit-equal to two calls
    (seen on the CPU, one device and eight). Where the fused body seeds its
    kernel with the running Hessian, that is lane-padded ONCE before the
    first batch and sliced once after the last (a zero pad and its slice
    are exact, so the group is still its calls bit for bit). One compiled
    program per (group length, batch shape)."""
    return _stream_grad_hess_group_cached(mesh, ad, bool(config.get("use_pallas")))


@functools.lru_cache(maxsize=32)
def _stream_grad_hess_group_cached(mesh: Mesh, ad: str, use_pallas: bool):
    f = _stream_grad_hess_shard_fn(mesh, ad, use_pallas)

    @functools.partial(ledgered_jit, "logreg.streaming_update_group",
                       donate_argnums=(0,))
    def update_group(state, w, b, xs, ys, masks):
        d = xs[0].shape[1]
        dp = _seeded_hessian_width(mesh, lambda shard, dtype: (
            _fused_newton_fold_applicable(shard, dtype, ad, use_pallas)), xs[0])
        if dp:
            state = (*state[:2], _pad_hessian(state[2], dp), *state[3:])
        for x, y, mask in zip(xs, ys, masks):
            state, w, b = jax.lax.optimization_barrier(
                (f(*state, w, b, x, y, mask), w, b))
        if dp:
            state = (*state[:2], state[2][:d, :d], *state[3:])
        return state

    update_group.on_dispatch = _newton_fold_path_counter(mesh, ad, use_pallas)
    return update_group


@functools.lru_cache(maxsize=64)
def _stream_newton_step_fn(reg: float, fit_intercept: bool, ad: str):
    """Jitted finalize: scan sums + current (w, b) -> (new_w, new_b, delta)."""
    accum = jnp.dtype(ad)

    def step(gw, gb, hww, hwb, hbb, n, w, b):
        n = jnp.maximum(n, 1.0)
        d = gw.shape[0]
        grad_w = gw / n + reg * w
        grad_b = gb / n
        h_ww = hww / n + reg * jnp.eye(d, dtype=accum)
        h_wb = hwb / n
        h_bb = hbb / n
        # Block elimination (reg > 0) or floored joint Cholesky (reg ==
        # 0, singular-safe) — same math as the in-memory _newton_fn body.
        dw, db = _solve_newton_system(
            h_ww, h_wb, h_bb, grad_w, grad_b, reg, fit_intercept, accum
        )
        delta = jnp.sqrt(jnp.sum(dw * dw) + db * db)
        return w - dw, b - db, delta

    return ledgered_jit("logreg.newton_step", step)


_M_SOFTMAX_FOLD_PATH = metrics.counter(
    "srml_logreg_softmax_fold_path_total",
    "Dispatches of the streaming multinomial fold "
    "(logreg.softmax_streaming_update / _group) by the body their program "
    "was built with: path=fused (a width the chip keeps rows minor, float32 "
    "rows: the whole batch's statistics through softmax_fold_pallas, gate "
    "_fused_softmax_fold_applicable; a width on the 128-lane grid: the "
    "per-class curvature blocks through softmax_curvature_pallas) or "
    "path=xla (CPU, rows or accumulator not float32, shard rows off the "
    "kernel's block, a width on neither, a kernel's VMEM budget exceeded)",
)


def _fused_softmax_fold_applicable(
    shard_shape, x_dtype, ad, n_classes: int, use_pallas: Optional[bool] = None
) -> bool:
    """`_stream_softmax_shard_fn`'s gate for the one-kernel fold
    (ops/pallas_kernels.softmax_fold_pallas), by what the code can observe,
    as `_fused_newton_fold_applicable` decides the binary one: TPU backend
    and `use_pallas`, float32 rows and float32 accumulate, shard rows in
    multiples of 512, a width the chip keeps ROWS MINOR (ceil(d / 8) · 8 <
    ceil(d / 128) · 128 — there the kernel's `x.T` is a bitcast), and what
    the kernel holds in VMEM — one lane-padded (dp, dp) class block, the
    shard's logits — within its budget (constants and the VMEM reckoning
    imported from the kernel so the two cannot drift). Widths on the lane
    grid keep `softmax_curvature_pallas` (`_softmax_curv_kernel_applicable`);
    everything else the XLA body."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    if not _pallas_backend_ok(use_pallas):
        return False
    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        SOFTMAX_FOLD_ROW_MULTIPLE,
        SOFTMAX_FOLD_VMEM_BUDGET,
        _ceil_to,
        newton_fold_block_n,
        softmax_fold_vmem_limit,
    )

    m, d = shard_shape
    return (
        jnp.dtype(x_dtype) == jnp.dtype(jnp.float32)
        and jnp.dtype(ad) == jnp.dtype(jnp.float32)
        and m > 0
        and m % SOFTMAX_FOLD_ROW_MULTIPLE == 0
        and _ceil_to(d, 8) < _ceil_to(d, 128)
        and softmax_fold_vmem_limit(d, m, n_classes, newton_fold_block_n(d, m))
        <= SOFTMAX_FOLD_VMEM_BUDGET
    )


def _softmax_curv_kernel_applicable(shard_shape, ad, use_pallas: bool) -> bool:
    """The multinomial fold's gate for the shared-tile Pallas curvature
    (ops/pallas_kernels.softmax_curvature_pallas): TPU backend + float32
    accumulate + block-divisible shard shapes (the streaming path's
    power-of-two row buckets satisfy it from the block size up, smaller
    buckets take the XLA loop, which is fine at that size) + even ONE
    class's (d, d) accumulator inside the VMEM budget (past that the XLA
    loop handles d, not a trace-time raise)."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok
    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        SOFTMAX_CURV_BLOCK_N,
        SOFTMAX_CURV_VMEM_BUDGET,
    )

    n, d = shard_shape
    return (
        _pallas_backend_ok(use_pallas)
        and jnp.dtype(ad) == jnp.dtype(jnp.float32)
        and n % SOFTMAX_CURV_BLOCK_N == 0
        and d % 128 == 0
        and 4 * d * d <= SOFTMAX_CURV_VMEM_BUDGET
    )


def _stream_softmax_shard_fn(
    mesh: Mesh, n_classes: int, ad: str, cd: str, use_pallas: bool
):
    """One batch's multinomial statistics at fixed (W, b), added to the
    running ones under ``shard_map``: (*state, W, b, x, y, mask) -> state
    with state = (gw (d, C), gb (C), hw (C, d, d), hwb (C, d), hbb (C),
    loss (), n ()). The one body of `_stream_softmax_stats_fn` and
    `_stream_softmax_stats_group_fn`; the curvature kernel is looked up
    here, when a program is built.

    The per-class curvature blocks are the MM/upper-bound Hessian
    Xᵀdiag(p_c)X: the softmax Hessian's class-coupling matrix satisfies
    diag(p) − ppᵀ ⪯ diag(p), so solving each class block against the
    EXACT gradient is a majorize-minimize Newton step — monotone descent
    with no line search, O(C·d²) state, one scan per iteration (the same
    streaming contract as the binary path; full-softmax coupling would
    need a (C·d)² Hessian that cannot stream).

    Where `_fused_softmax_fold_applicable` holds for a shard's rows (TPU,
    float32 rows and accumulate, whole blocks, a width the chip keeps rows
    minor — d = 3000) the whole batch goes through ONE kernel,
    `softmax_fold_pallas`: it reads the batch as it lies (`x.T`, a
    bitcast), takes every class's logits in float32 once, and folds one
    class's curvature block at a time — this body's arithmetic in another
    order of additions. On one data device the running blocks are seeded
    into it, lane-padded: `hw` may arrive at (C, d, d) or already at
    (C, dp, dp) (the group program pads once) and leaves as it came; on
    several, the batch's blocks meet the state after the psum."""
    accum = jnp.dtype(ad)
    C = n_classes
    seed = mesh.shape[DATA_AXIS] == 1
    # Curvature blocks set only the MM step DIRECTION (the fixed point is
    # pinned by the exact full-precision gradient below), so their GEMM
    # operands stream at the compute dtype: on the TPU bf16 profile that
    # halves the C-GEMM loop's HBM traffic — the dominant cost at large C
    # (measured 0.69x -> parity-class at C=32, d=1024). f32/f64 accum
    # configs off the bf16 profile keep full-width operands.
    hd = (
        jnp.dtype(jnp.bfloat16)
        if accum == jnp.float32 and jnp.dtype(cd) == jnp.dtype(jnp.bfloat16)
        else accum
    )

    from spark_rapids_ml_tpu.ops.pallas_kernels import (
        softmax_curv_block_c,
        softmax_curvature_pallas,
    )

    def fused_shard(gw, gb, hw, hwb, hbb, loss, n, W, b, x, y, mask):
        from spark_rapids_ml_tpu.ops.pallas_kernels import _ceil_to, softmax_fold_pallas

        d = x.shape[1]
        dp = _ceil_to(d, 128)
        if seed:
            bgw, bgb, h, bhwb, bhbb, bloss, bn = softmax_fold_pallas(
                x.T, y, mask, W, b, hw=_pad_hessian(hw, dp))
            hw = h if hw.shape[-1] == dp else h[:, :d, :d]
        else:
            bgw, bgb, h, bhwb, bhbb, bloss, bn = softmax_fold_pallas(x.T, y, mask, W, b)
            hw = hw + mr.reduce_sum(h[:, :d, :d], DATA_AXIS)
        return (
            gw + mr.reduce_sum(bgw, DATA_AXIS),
            gb + mr.reduce_sum(bgb, DATA_AXIS),
            hw,
            hwb + mr.reduce_sum(bhwb, DATA_AXIS),
            hbb + mr.reduce_sum(bhbb, DATA_AXIS),
            loss + mr.reduce_sum(bloss, DATA_AXIS),
            n + mr.reduce_sum(bn, DATA_AXIS),
        )

    def shard(gw, gb, hw, hwb, hbb, loss, n, W, b, x, y, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        if _fused_softmax_fold_applicable(x.shape, x.dtype, ad, C, use_pallas):
            return fused_shard(gw, gb, hw, hwb, hbb, loss, n, W, b, x, y, mask)
        with mm_precision(accum):
            xc = x.astype(accum)
            maskc = mask.astype(accum)
            yi = y.astype(jnp.int32)
            logits = xc @ W + b  # (n, C)
            p = jax.nn.softmax(logits, axis=1)
            yoh = jax.nn.one_hot(yi, C, dtype=accum)
            r = (p - yoh) * maskc[:, None]
            bloss = jnp.sum(
                (jax.nn.logsumexp(logits, axis=1)
                 - jnp.take_along_axis(logits, yi[:, None], axis=1)[:, 0])
                * maskc
            )
            bn = jnp.sum(maskc.astype(jnp.int32)).astype(accum)

            xh = xc.astype(hd)

            if _softmax_curv_kernel_applicable(x.shape, ad, use_pallas):
                # Shared-tile kernel: each VMEM-resident x tile feeds a
                # class GROUP's GEMMs, dividing the C× HBM re-read of x —
                # the cost that capped this pass at 0.85× (see
                # ops/pallas_kernels.softmax_curvature_pallas).
                pm = (p * maskc[:, None]).astype(jnp.float32)
                bhw, bhwb = softmax_curvature_pallas(
                    xh, pm, block_c=softmax_curv_block_c(x.shape[1], C)
                )
                bhbb = jnp.sum(pm, axis=0).astype(accum)
            else:

                def per_class(c):
                    pc = p[:, c] * maskc  # (n,) full-precision probabilities
                    xw = xh * pc.astype(hd)[:, None]
                    return (
                        jax.lax.dot_general(
                            xw, xh, (((0,), (0,)), ((), ())),
                            preferred_element_type=accum,
                            # Fast-precision is safe here because these
                            # blocks only set the MM step DIRECTION; the
                            # fixed point is pinned by the exact
                            # full-precision gradient above
                            # (approximate-Hessian/exact-gradient).
                            precision=jax.lax.Precision.DEFAULT,
                        ),
                        jnp.sum(xw, axis=0, dtype=accum),
                        jnp.sum(pc),
                    )

                # Sequential over classes: a batched einsum would
                # materialize an (C, n, d) intermediate; C GEMMs stream x
                # from VMEM/HBM.
                bhw, bhwb, bhbb = jax.lax.map(per_class, jnp.arange(C))
            return (
                gw + mr.reduce_sum(
                    jax.lax.dot_general(xc, r, (((0,), (0,)), ((), ())),
                                        preferred_element_type=accum),
                    DATA_AXIS,
                ),
                gb + mr.reduce_sum(jnp.sum(r, axis=0), DATA_AXIS),
                hw + mr.reduce_sum(bhw, DATA_AXIS),
                hwb + mr.reduce_sum(bhwb, DATA_AXIS),
                hbb + mr.reduce_sum(bhbb, DATA_AXIS),
                loss + mr.reduce_sum(bloss, DATA_AXIS),
                n + mr.reduce_sum(bn, DATA_AXIS),
            )

    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), P(),
                  P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(),) * 7,
        check_vma=False,  # pallas_call out_shapes carry no vma annotation
    )


def _softmax_fold_path_counter(mesh: Mesh, n_classes: int, ad: str, use_pallas: bool):
    return _fold_path_counter(mesh, _M_SOFTMAX_FOLD_PATH, lambda shard, dtype: (
        _fused_softmax_fold_applicable(shard, dtype, ad, n_classes, use_pallas)
        or _softmax_curv_kernel_applicable(shard, ad, use_pallas)))


def _stream_softmax_stats_fn(mesh: Mesh, n_classes: int, ad: str):
    # compute_dtype / use_pallas are read at build time so they participate
    # in the cache key (the _newton_fn snapshot pattern): a config flip
    # between fits must not silently reuse a stale-curvature-dtype closure.
    return _stream_softmax_stats_cached(
        mesh, n_classes, ad, jnp.dtype(config.get("compute_dtype")).name,
        bool(config.get("use_pallas")),
    )


@functools.lru_cache(maxsize=32)
def _stream_softmax_stats_cached(
    mesh: Mesh, n_classes: int, ad: str, cd: str, use_pallas: bool = False
):
    """Jitted donated accumulate of one batch's multinomial statistics at
    fixed (W, b): (state, W, b, x, y, mask) -> state
    (`_stream_softmax_shard_fn`)."""
    f = _stream_softmax_shard_fn(mesh, n_classes, ad, cd, use_pallas)

    @functools.partial(ledgered_jit, "logreg.softmax_streaming_update", donate_argnums=(0,))
    def update(state, W, b, x, y, mask):
        return f(*state, W, b, x, y, mask)

    update.on_dispatch = _softmax_fold_path_counter(mesh, n_classes, ad, use_pallas)
    return update


def _stream_softmax_stats_group_fn(mesh: Mesh, n_classes: int, ad: str):
    """The same accumulate over a GROUP of device-resident batches in one
    program: (state, W, b, xs, ys, masks) -> state, the three tuples of
    equal length — the daemon's cached multinomial pass, as
    `_stream_grad_hess_group_fn` is the binary one's. Batch by batch, in
    order, through the one shard body `_stream_softmax_stats_fn` runs, with
    an optimization barrier after each over the state AND the iterate the
    next batch reads, so that a group is bit-equal to len(xs) calls of it.
    Where the fused body seeds its kernel with the running curvature, that
    is lane-padded ONCE before the first batch and sliced once after the
    last. One compiled program per (group length, batch shape)."""
    return _stream_softmax_stats_group_cached(
        mesh, n_classes, ad, jnp.dtype(config.get("compute_dtype")).name,
        bool(config.get("use_pallas")),
    )


@functools.lru_cache(maxsize=32)
def _stream_softmax_stats_group_cached(
    mesh: Mesh, n_classes: int, ad: str, cd: str, use_pallas: bool
):
    f = _stream_softmax_shard_fn(mesh, n_classes, ad, cd, use_pallas)

    @functools.partial(ledgered_jit, "logreg.softmax_streaming_update_group",
                       donate_argnums=(0,))
    def softmax_update_group(state, W, b, xs, ys, masks):
        d = xs[0].shape[1]
        dp = _seeded_hessian_width(mesh, lambda shard, dtype: (
            _fused_softmax_fold_applicable(shard, dtype, ad, n_classes, use_pallas)), xs[0])
        if dp:
            state = (*state[:2], _pad_hessian(state[2], dp), *state[3:])
        for x, y, mask in zip(xs, ys, masks):
            state, W, b = jax.lax.optimization_barrier(
                (f(*state, W, b, x, y, mask), W, b))
        if dp:
            state = (*state[:2], state[2][:, :d, :d], *state[3:])
        return state

    softmax_update_group.on_dispatch = _softmax_fold_path_counter(
        mesh, n_classes, ad, use_pallas)
    return softmax_update_group


@functools.lru_cache(maxsize=64)
def _stream_multinomial_step_fn(reg: float, fit_intercept: bool, ad: str):
    """Jitted finalize of one multinomial MM-Newton pass: scan sums +
    current (W (d, C), b (C)) -> (new_W, new_b, delta). Per-class
    bordered solves, vmapped over the class axis."""
    accum = jnp.dtype(ad)

    def step(gw, gb, hw, hwb, hbb, n, W, b):
        n = jnp.maximum(n, 1.0)
        d = gw.shape[0]
        grad_w = gw / n + reg * W  # (d, C)
        grad_b = gb / n  # (C,)
        h_w = hw / n + reg * jnp.eye(d, dtype=accum)[None, :, :]  # (C, d, d)
        h_wb = hwb / n  # (C, d)
        h_bb = hbb / n  # (C,)

        def solve_c(hww_c, hwb_c, hbb_c, gwc, gbc):
            # h_ww is Xᵀdiag(p)X/n + reg·I — symmetric PD when reg > 0:
            # ONE Cholesky per class with both right-hand sides
            # back-substituted together, where two jnp.linalg.solve calls
            # paid two LU factorizations (measured 35.9 → ~9 ms for the
            # C=32, d=1024 step).
            if reg > 0.0:
                cho = jax.scipy.linalg.cho_factor(hww_c, lower=True)
                if fit_intercept:
                    sol = jax.scipy.linalg.cho_solve(
                        cho, jnp.stack([hwb_c, gwc], axis=1)
                    )
                    hinv_hwb, hinv_gw = sol[:, 0], sol[:, 1]
                    schur = jnp.maximum(hbb_c - hwb_c @ hinv_hwb, 1e-12)
                    db = (gbc - hwb_c @ hinv_gw) / schur
                    dw = hinv_gw - hinv_hwb * db
                    return dw, db
                return (
                    jax.scipy.linalg.cho_solve(cho, gwc),
                    jnp.zeros((), accum),
                )
            # reg == 0: only PSD — the floored singular-safe solve
            # (_solve_newton_system; ADVICE r5(a)).
            return _solve_newton_system(
                hww_c, hwb_c, hbb_c, gwc, gbc, reg, fit_intercept, accum
            )

        dw, db = jax.vmap(solve_c)(h_w, h_wb, h_bb, grad_w.T, grad_b)
        new_W = W - dw.T
        new_b = b - db if fit_intercept else b
        delta = jnp.sqrt(jnp.sum(dw * dw) + jnp.sum(db * db))
        return new_W, new_b, delta

    return ledgered_jit("logreg.softmax_newton_step", step)


def stream_softmax_zero_state(n_cols: int, n_classes: int, accum_dtype) -> tuple:
    """Zero (gw, gb, hw, hwb, hbb, loss, n) accumulator for one
    multinomial pass — shared by fit_multinomial_stream and the daemon."""
    ad = jnp.dtype(accum_dtype)
    d, C = n_cols, n_classes
    return (
        jnp.zeros((d, C), ad),
        jnp.zeros((C,), ad),
        jnp.zeros((C, d, d), ad),
        jnp.zeros((C, d), ad),
        jnp.zeros((C,), ad),
        jnp.zeros((), ad),
        jnp.zeros((), ad),
    )


def stream_softmax_objective(lsum, n, reg: float, W) -> float:
    """Mean multinomial CE + L2 — the objective both the streaming fit
    and the daemon report."""
    return float(lsum / jnp.maximum(n, 1.0)) + 0.5 * float(reg) * float(
        jnp.sum(W * W)
    )


def validate_multiclass_labels(y: np.ndarray, n_classes: int) -> None:
    """Raise unless labels are integers in [0, n_classes) (Spark ML)."""
    ya = np.asarray(y)
    if ya.size == 0:
        return
    if not np.all(np.equal(np.mod(ya, 1), 0)):
        raise ValueError("labels must be integers 0..n_classes-1")
    lo, hi = ya.min(), ya.max()
    if lo < 0 or hi >= n_classes:
        raise ValueError(
            f"labels must be in [0, {n_classes}); got range [{lo}, {hi}]"
        )


def fit_multinomial_stream(
    batch_source,
    n_cols: int,
    n_classes: int,
    reg: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
    checkpoint_path: Optional[str] = None,
) -> LogisticSolution:
    """Multinomial softmax over a re-scannable stream of host (x, y)
    batches — the multiclass peer of :func:`fit_logistic_stream` (round-2
    review: multinomial was an in-memory GD sidecar; Criteo-class
    multiclass needs the streaming/lockstep contract).

    One scan per MM-Newton iteration (see _stream_softmax_stats_fn for
    the upper-bound curvature argument); labels are integers in
    [0, n_classes). Multi-host lockstep and checkpoint/resume follow the
    binary path exactly.
    """
    from spark_rapids_ml_tpu.core import checkpoint as ckpt
    from spark_rapids_ml_tpu.parallel.sharding import lockstep_labeled_batches

    if n_classes < 2:
        raise ValueError("n_classes must be >= 2")
    multiproc = jax.process_count() > 1
    mesh = mesh or default_mesh()
    ad = config.get("accum_dtype")
    accum = jnp.dtype(ad)
    update = _stream_softmax_stats_fn(mesh, int(n_classes), ad)
    mm_step = _stream_multinomial_step_fn(float(reg), bool(fit_intercept), ad)

    W = jnp.zeros((n_cols, n_classes), accum)
    b = jnp.zeros((n_classes,), accum)
    start_iter = 0
    restored = ckpt.load_state(checkpoint_path) if checkpoint_path else None
    if checkpoint_path:
        ckpt.require_consistent_visibility(restored)
    if restored is not None:
        arrays, meta = restored
        if meta.get("n_cols") != n_cols or meta.get("n_classes") != n_classes:
            raise ValueError(
                f"checkpoint at {checkpoint_path} is for n_cols="
                f"{meta.get('n_cols')}, n_classes={meta.get('n_classes')}, "
                f"not ({n_cols}, {n_classes})"
            )
        W = jnp.asarray(arrays["W"], accum)
        b = jnp.asarray(arrays["b"], accum)
        start_iter = int(meta["it"])

    labels_checked = False

    def _check_labels(_x, y):
        if labels_checked:
            return None
        try:
            validate_multiclass_labels(y, n_classes)
        except ValueError as e:
            return str(e)
        return None

    def scan(W_dev, b_dev):
        nonlocal labels_checked
        state = stream_softmax_zero_state(n_cols, n_classes, accum)
        n_rows = 0
        for xb_host, yb_host in lockstep_labeled_batches(
            batch_source(), n_cols, check=_check_labels
        ):
            xs, ms, n_b = shard_rows(np.asarray(xb_host), mesh, dtype=np.float32)
            ys, _, _ = shard_rows(yb_host.astype(np.float32), mesh)
            n_rows += n_b
            state = update(state, W_dev, b_dev, xs, ys, ms)
        labels_checked = True
        return state, n_rows

    n_true = 0
    n_iter = start_iter
    loss = float("nan")
    with trace_span("multinomial-stream"):
        for it in range(start_iter, max_iter):
            (gw, gb, hw, hwb, hbb, lsum, n), n_true = scan(W, b)
            loss = stream_softmax_objective(lsum, n, reg, W)
            W, b, delta = mm_step(gw, gb, hw, hwb, hbb, n, W, b)
            n_iter = it + 1
            if checkpoint_path and (not multiproc or jax.process_index() == 0):
                ckpt.save_state(
                    checkpoint_path,
                    {
                        "W": np.asarray(jax.device_get(W)),
                        "b": np.asarray(jax.device_get(b)),
                    },
                    {"it": n_iter, "n_cols": n_cols, "n_classes": n_classes},
                )
            if float(delta) <= tol:
                break
        if n_true == 0:
            (_, _, _, _, _, lsum, n), n_true = scan(W, b)
            loss = stream_softmax_objective(lsum, n, reg, W)
    if checkpoint_path and (not multiproc or jax.process_index() == 0):
        import os

        if os.path.exists(checkpoint_path):
            os.unlink(checkpoint_path)
    return LogisticSolution(
        coefficients=np.asarray(jax.device_get(W), dtype=np.float64).T,  # (C, d)
        intercept=np.asarray(jax.device_get(b), dtype=np.float64),
        n_iter=n_iter,
        n_rows=n_true,
        loss=loss,
    )


def stream_zero_state(n_cols: int, accum_dtype) -> tuple:
    """Zero (gw, gb, hww, hwb, hbb, loss, n) accumulator for one Newton
    pass — shared by fit_logistic_stream and the data-plane daemon."""
    ad = jnp.dtype(accum_dtype)
    d = n_cols
    return (
        jnp.zeros((d,), ad),
        jnp.zeros((), ad),
        jnp.zeros((d, d), ad),
        jnp.zeros((d,), ad),
        jnp.zeros((), ad),
        jnp.zeros((), ad),
        jnp.zeros((), ad),
    )


def stream_objective(lsum, n, reg: float, w) -> float:
    """Training objective at the iterate a pass evaluated: mean data loss
    plus the L2 term — the single definition both streaming paths report."""
    return float(lsum / jnp.maximum(n, 1.0)) + 0.5 * float(reg) * float(
        jnp.sum(w * w)
    )


def validate_binary_labels(y: np.ndarray) -> None:
    """Raise unless labels are {0, 1} (Spark ML binary convention)."""
    bad = set(np.unique(y)) - {0, 1, 0.0, 1.0}
    if bad:
        raise ValueError(
            f"labels must be binary 0/1 for the streaming path; got {sorted(bad)[:8]}"
        )


def fit_logistic_stream(
    batch_source,
    n_cols: int,
    reg: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
    checkpoint_path: Optional[str] = None,
) -> LogisticSolution:
    """Binary Newton-IRLS over a re-scannable stream of host (x, y) batches
    — the capacity path for label datasets ≫ HBM (BASELINE.json config #4:
    Criteo-1TB normal-equations family).

    ``batch_source`` is a CALLABLE returning a fresh iterator of
    ``(x (rows, d), y (rows,))`` pairs; each Newton iteration consumes one
    full scan, accumulating gradient + Hessian sharded on device into a
    donated O(d²) state. Labels must be {0, 1} (binary only — multiclass
    streams through :func:`fit_multinomial_stream`). The returned
    ``loss`` is the objective at the LAST
    iterate evaluated during its final scan (one iteration stale, standard
    for streaming monitors; a converged fit has delta ≤ tol so the
    difference is below the stopping precision).

    With ``checkpoint_path``, (w, b) persist after every iteration and an
    interrupted fit resumes at the saved iteration.

    **Multi-host**: ``batch_source`` yields this process's local (x, y)
    stream; scans run in lockstep (``lockstep_labeled_batches`` — uneven
    lengths fine, label validation propagates collectively). Checkpoints
    are written by process 0 (shared filesystem to resume).
    """
    from spark_rapids_ml_tpu.core import checkpoint as ckpt
    from spark_rapids_ml_tpu.parallel.sharding import lockstep_labeled_batches

    multiproc = jax.process_count() > 1
    mesh = mesh or default_mesh()
    ad = config.get("accum_dtype")
    accum = jnp.dtype(ad)
    update = _stream_grad_hess_fn(mesh, ad)
    newton_step = _stream_newton_step_fn(float(reg), bool(fit_intercept), ad)

    w = jnp.zeros((n_cols,), accum)
    b = jnp.zeros((), accum)
    start_iter = 0
    restored = ckpt.load_state(checkpoint_path) if checkpoint_path else None
    if checkpoint_path:
        ckpt.require_consistent_visibility(restored)
    if restored is not None:
        arrays, meta = restored
        if meta.get("n_cols") != n_cols:
            raise ValueError(
                f"checkpoint at {checkpoint_path} is for n_cols="
                f"{meta.get('n_cols')}, not {n_cols}"
            )
        w = jnp.asarray(arrays["w"], accum)
        b = jnp.asarray(arrays["b"], accum)
        start_iter = int(meta["it"])

    labels_checked = False

    def _check_labels(_x, y):
        if labels_checked:  # first scan only — data is fixed across scans
            return None
        try:
            validate_binary_labels(y)
        except ValueError as e:
            return str(e)
        return None

    def scan(w_dev, b_dev):
        nonlocal labels_checked
        state = stream_zero_state(n_cols, accum)
        n_rows = 0
        for xb_host, yb_host in lockstep_labeled_batches(
            batch_source(), n_cols, check=_check_labels
        ):
            # shard_rows pads, casts f64→f32 via the threaded native bridge,
            # and places row-sharded (global assembly when multi-process).
            xs, ms, n_b = shard_rows(np.asarray(xb_host), mesh, dtype=np.float32)
            ys, _, _ = shard_rows(yb_host.astype(np.float32), mesh)
            n_rows += n_b
            state = update(state, w_dev, b_dev, xs, ys, ms)
        labels_checked = True
        return state, n_rows

    n_true = 0
    n_iter = start_iter
    loss = float("nan")
    with trace_span("logreg-stream"):
        for it in range(start_iter, max_iter):
            (gw, gb, hww, hwb, hbb, lsum, n), n_true = scan(w, b)
            # Objective at the iterate the scan evaluated (pre-update w).
            loss = stream_objective(lsum, n, reg, w)
            w, b, delta = newton_step(gw, gb, hww, hwb, hbb, n, w, b)
            n_iter = it + 1
            if checkpoint_path and (not multiproc or jax.process_index() == 0):
                ckpt.save_state(
                    checkpoint_path,
                    {
                        "w": np.asarray(jax.device_get(w)),
                        "b": np.asarray(jax.device_get(b)),
                    },
                    {"it": n_iter, "n_cols": n_cols},
                )
            if float(delta) <= tol:
                break
        if n_true == 0:
            # Resumed at/past max_iter: the loop never ran, so evaluate the
            # restored iterate once for a faithful (n_rows, loss).
            (_, _, _, _, _, lsum, n), n_true = scan(w, b)
            loss = stream_objective(lsum, n, reg, w)
    if checkpoint_path and (not multiproc or jax.process_index() == 0):
        import os

        if os.path.exists(checkpoint_path):
            os.unlink(checkpoint_path)
    return LogisticSolution(
        coefficients=np.asarray(jax.device_get(w), dtype=np.float64),
        intercept=np.asarray(jax.device_get(b), dtype=np.float64),
        n_iter=n_iter,
        n_rows=n_true,
        loss=loss,
    )


# ---------------------------------------------------------------------------
# Estimator / Model
# ---------------------------------------------------------------------------


class LogisticRegressionJob(JobAlgorithm):
    """Newton passes (binary) or MM-Newton passes (``n_classes`` > 2: the
    same feed/step/finalize op sequence over a per-class state). The
    iterate is (w, b), zero at creation; a pass's statistics are the
    gradient and Hessian blocks, the loss sum and the row count at it.
    Either job may keep its pass on the device — rows, masks and the
    label column its fold places — and fold it again by the group, with
    the group program of its own state."""

    name = "logreg"
    needs_labels = True
    iterative = True
    cacheable = True

    def __init__(self, n_cols, mesh, params):
        super().__init__(n_cols, mesh, params)
        self._require_gram_capacity()
        self.n_classes = self.feed_classes(params)
        ad = config.get("accum_dtype")
        # The boundary's span names what the device waits for between two
        # passes: the wait for the pass's folds (the loss's read), the
        # solve (its own span), the zero state, the snapshot.
        if self.n_classes > 2:
            self.w = jnp.zeros((n_cols, self.n_classes), self.accum)
            self.b = jnp.zeros((self.n_classes,), self.accum)
            self._update = _stream_softmax_stats_fn(mesh, self.n_classes, ad)
            self._update_group = _stream_softmax_stats_group_fn(
                mesh, self.n_classes, ad)
            self._step_fn, self._objective = (
                _stream_multinomial_step_fn, stream_softmax_objective)
            self.boundary_span, self.solve_span = "softmax.boundary", "softmax.solve"
        else:
            self.w = jnp.zeros((n_cols,), self.accum)
            self.b = jnp.zeros((), self.accum)
            self._update = _stream_grad_hess_fn(mesh, ad)
            self._update_group = _stream_grad_hess_group_fn(mesh, ad)
            self._step_fn, self._objective = (
                _stream_newton_step_fn, stream_objective)
            self.boundary_span, self.solve_span = "newton.boundary", "newton.solve"

    @staticmethod
    def feed_classes(params) -> int:
        """The ONE parse of a request's ``n_classes`` (absent = binary),
        shared by label validation and the job-mismatch guard so the two
        cannot disagree on the coercion rule."""
        return int(params.get("n_classes") or 2)

    @classmethod
    def check_labels(cls, params, y):
        n_classes = cls.feed_classes(params)
        if n_classes > 2:
            validate_multiclass_labels(y, n_classes)
        else:
            validate_binary_labels(y)

    def feed_mismatch(self, params):
        want = self.feed_classes(params)
        if want != self.n_classes:
            return (f"has n_classes={self.n_classes}; "
                    f"feed carried n_classes={want}")
        return None

    def iterate_arrays(self):
        return {
            "w": np.asarray(jax.device_get(self.w)),
            "b": np.asarray(jax.device_get(self.b)).reshape(-1),
        }

    def install_iterate(self, arrays):
        # Full shape validation at the boundary: a mis-shaped iterate
        # installed here would otherwise crash opaquely inside the next
        # feed's jitted update.
        w = np.asarray(arrays["w"])
        b = np.asarray(arrays["b"]).reshape(-1)
        multi = self.n_classes > 2
        want_w = (self.n_cols, self.n_classes) if multi else (self.n_cols,)
        want_b = self.n_classes if multi else 1
        if tuple(w.shape) != want_w:
            raise ValueError(
                f"coefficients shape {tuple(w.shape)} != {want_w} "
                f"(n_cols={self.n_cols}, n_classes={self.n_classes})"
            )
        if b.shape[0] != want_b:
            raise ValueError(
                f"intercept length {b.shape[0]} != {want_b} "
                f"(n_classes={self.n_classes})"
            )
        self.w = jnp.asarray(w, self.accum)
        self.b = jnp.asarray(b if multi else b.reshape(()), self.accum)

    def zero_state(self):
        if self.n_classes > 2:
            return stream_softmax_zero_state(
                self.n_cols, self.n_classes, self.accum)
        return stream_zero_state(self.n_cols, self.accum)

    def place_columns(self, target, y=None, n=0, partition=None, offset=0):
        return (self._place_column(y, target, np.float32),)

    def fold(self, state, xs, ms, columns=(), n=0):
        (ys,) = columns
        return self._update(state, self.w, self.b, xs, ys, ms)

    def fold_group(self, state, xs, ms, columns=()):
        (ys,) = columns
        return self._update_group(state, self.w, self.b, xs, ys, ms)

    def step(self, state, params):
        reg = float(params.get("reg", 0.0))
        fit_intercept = bool(params.get("fit_intercept", True))
        gw, gb, hww, hwb, hbb, lsum, n = state
        step_fn = self._step_fn(reg, fit_intercept, self.accum.name)
        # the loss's read is the wait for the pass's folds: before the
        # solve's span, so that the span holds the solve alone
        loss = self._objective(lsum, n, reg, self.w)
        with trace_span(self.solve_span):
            self.w, self.b, delta = step_fn(
                gw, gb, hww, hwb, hbb, n, self.w, self.b)
            delta = float(delta)
        return {"delta": delta, "loss": loss}

    def finalize(self, state, params, rows, iteration):
        w = np.asarray(jax.device_get(self.w))
        b = np.asarray(jax.device_get(self.b))
        if self.n_classes > 2:
            # Spark layout: (C, d) coefficientMatrix + (C,) intercepts.
            w, b = w.T, b.reshape(-1)
        else:
            b = b.reshape(1)
        return {
            "coefficients": w,
            "intercept": b,
            "n_iter": np.asarray([iteration]),
        }


class _LogisticRegressionParams(
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasFitIntercept,
    HasMaxIter,
    HasTol,
):
    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
            regParam=0.0,
            fitIntercept=True,
            maxIter=100,
            tol=1e-6,
        )


class LogisticRegression(Estimator, _LogisticRegressionParams, MLWritable, MLReadable):
    _uid_prefix = "LogisticRegression"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setRegParam(self, value: float) -> "LogisticRegression":
        return self._set(regParam=value)

    def setFitIntercept(self, value: bool) -> "LogisticRegression":
        return self._set(fitIntercept=value)

    def setMaxIter(self, value: int) -> "LogisticRegression":
        return self._set(maxIter=value)

    def setTol(self, value: float) -> "LogisticRegression":
        return self._set(tol=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "LogisticRegressionModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        y = as_column(dataset, self.getLabelCol())
        sol = fit_logistic_regression(
            x,
            y,
            reg=self.getRegParam(),
            fit_intercept=self.getFitIntercept(),
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            mesh=self._mesh,
        )
        model = LogisticRegressionModel(
            coefficients=sol.coefficients, intercept=sol.intercept
        )
        model.uid = self.uid
        model._summary = LogisticTrainingSummary(
            loss=sol.loss, numIter=sol.n_iter, n_rows=sol.n_rows
        )
        self._copy_params_to(model)
        return model


class LogisticRegressionModel(Model, _LogisticRegressionParams, MLWritable, MLReadable):
    _uid_prefix = "LogisticRegressionModel"

    def __init__(self, coefficients=None, intercept=None, uid=None):
        super().__init__(uid=uid)
        self.coefficients = None if coefficients is None else np.asarray(coefficients)
        self.intercept = None if intercept is None else np.asarray(intercept)
        self._summary: Optional[LogisticTrainingSummary] = None

    @property
    def summary(self) -> Optional[LogisticTrainingSummary]:
        return self._summary

    @property
    def numClasses(self) -> int:
        if self.coefficients is None:
            return 0
        return 2 if self.coefficients.ndim == 1 else self.coefficients.shape[0]

    def _model_data(self):
        return {
            "coefficients": self.coefficients,
            "intercept": np.atleast_1d(self.intercept),
        }

    @classmethod
    def _from_model_data(cls, uid, data):
        coef = data["coefficients"]
        inter = data["intercept"]
        if coef.ndim == 1 or coef.shape[0] == 1:
            coef = coef.reshape(-1)
            inter = np.asarray(inter).reshape(-1)[0]
        return cls(coefficients=coef, intercept=inter, uid=uid)

    def _copy_extra_state(self, source):
        self.coefficients = source.coefficients
        self.intercept = source.intercept
        self._summary = getattr(source, "_summary", None)

    def predict_raw(self, x: np.ndarray) -> np.ndarray:
        """Per-class margins (logits) — Spark's rawPrediction vector.

        Binary: ``[-z, z]`` with z the log-odds, matching Spark's
        BinaryLogisticRegressionModel raw output.
        """
        x = np.asarray(x, dtype=np.float64)
        if self.coefficients.ndim == 1:
            z = x @ self.coefficients + float(np.asarray(self.intercept).reshape(-1)[0])
            return np.stack([-z, z], axis=1)
        return x @ self.coefficients.T + np.asarray(self.intercept)[None, :]

    def _raw_to_proba(self, raw: np.ndarray) -> np.ndarray:
        """Spark's raw2probability: binary -> sigmoid of the margin
        (raw = [-z, z] so softmax would wrongly give sigmoid(2z));
        multiclass -> softmax of the logits."""
        if self.coefficients.ndim == 1:
            z = raw[:, 1]
            # overflow-safe sigmoid: exp only ever sees non-positive input
            p1 = np.where(
                z >= 0,
                1.0 / (1.0 + np.exp(-np.abs(z))),
                np.exp(-np.abs(z)) / (1.0 + np.exp(-np.abs(z))),
            )
            return np.stack([1.0 - p1, p1], axis=1)
        logits = raw - raw.max(axis=1, keepdims=True)
        e = np.exp(logits)
        return e / e.sum(axis=1, keepdims=True)

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        return self._raw_to_proba(self.predict_raw(x))

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.argmax(self.predict_proba(x), axis=1)

    # Daemon serving contract (serve/daemon.py).
    _serve_algo = "logreg"
    _serve_outputs = (
        ("rawPrediction", "rawPredictionCol", "vec"),
        ("probability", "probabilityCol", "vec"),
        ("prediction", "predictionCol", "double"),
    )

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py; see PCAModel's) —
        the device half only: the raw→probability map is host
        elementwise and compiles nothing."""
        if self.coefficients is None:
            return None
        from spark_rapids_ml_tpu.parallel.sharding import bucket_rows

        c = np.asarray(self.coefficients)
        d = int(c.shape[-1] if c.ndim == 2 else c.shape[0])
        if int(n_cols) != d:
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"model's fitted width {d}"
            )
        return [(
            self._raw_scorer(),
            (jax.ShapeDtypeStruct(
                (bucket_rows(int(n_rows)), d), jnp.dtype(dtype)
            ),),
        )]

    def _raw_scorer(self):
        """Jitted per-class margins with W, b device-resident — the device
        scoring path the daemon ``transform`` op serves (the reference ran
        transform on the accelerator, RapidsPCA.scala:128-161; scoring on
        executor CPUs would abandon it)."""
        cache = getattr(self, "_raw_cache", None)
        if cache is None:
            cache = self._raw_cache = {}
        from spark_rapids_ml_tpu import config

        key = (config.get("compute_dtype"), config.get("accum_dtype"))
        if key not in cache:
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops.gram import mm_precision

            cd, accum = jnp.dtype(key[0]), jnp.dtype(key[1])
            binary = self.coefficients.ndim == 1
            W = np.atleast_2d(self.coefficients)  # (C|1, d)
            w_dev = jnp.asarray(W, dtype=cd)
            b_dev = jnp.asarray(np.atleast_1d(self.intercept), accum)

            @ledgered_jit("logreg.raw_scores")
            def raw(x):
                with mm_precision(cd):
                    z = jax.lax.dot_general(
                        x.astype(cd), w_dev,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=accum,
                    ) + b_dev[None, :]
                if binary:
                    # Spark's binary raw output is [-z, z] (the margin).
                    return jnp.concatenate([-z, z], axis=1)
                return z

            cache[key] = raw
        return cache[key]

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed transform of a bare matrix: margins on device, the
        elementwise raw→probability map on host (negligible next to the
        (n, d)×(d, C) GEMM)."""
        if self.coefficients is None:
            raise RuntimeError("model has no coefficients (unfitted?)")
        from spark_rapids_ml_tpu.parallel.sharding import run_bucketed

        with trace_span("logreg transform"):
            raw = run_bucketed(self._raw_scorer(), x).astype(np.float64)
            proba = self._raw_to_proba(raw)
            return {
                "rawPrediction": raw,
                "probability": proba,
                "prediction": np.argmax(proba, axis=1).astype(np.float64),
            }

    def _transform(self, dataset):
        if self.coefficients is None:
            raise RuntimeError("model has no coefficients (unfitted?)")
        x = as_matrix(dataset, self.getFeaturesCol())
        raw = self.predict_raw(x)
        proba = self._raw_to_proba(raw)
        # Emit rawPrediction + probability + prediction like Spark's
        # ProbabilisticClassificationModel (prediction last, so the
        # bare-matrix dataset path still returns hard labels).
        out = with_column(dataset, self.getRawPredictionCol(), raw)
        out = with_column(out, self.getProbabilityCol(), proba)
        return with_column(out, self.getPredictionCol(), np.argmax(proba, axis=1))
