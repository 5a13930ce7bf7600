"""LinearRegression via distributed normal equations.

BASELINE.json config #4 ("LinearRegression / LogisticRegression
normal-equations on Criteo-1TB, Gram-matrix psum"). Architecturally this is
*literally* the PCA reduction with an extra Xᵀy accumulator (SURVEY.md §7
step 6): one sharded pass computes (XᵀX, Xᵀy, Σx, Σy, n) fused, psums ride
ICI, and the d×d solve happens on device.

Solver semantics (objective matches Spark ML's LinearRegression with
``standardization=False``):

    min_w  1/(2n) ‖Xw + b − y‖² + λ·(α‖w‖₁ + (1−α)/2·‖w‖₂²)

* α = 0 (ridge / OLS): closed form, (XᵀX/n + λI) w = Xᵀy/n via Cholesky.
* α > 0 (lasso / elastic net): FISTA on the precomputed normal-equation
  statistics — each iteration is a d×d matvec on device (no further data
  passes), step size 1/L from power iteration, soft-threshold prox. This
  keeps the TPU-native property that data is touched exactly once.
* fitIntercept: solved on centered statistics; intercept = ȳ − x̄·w
  (the intercept is never penalized, as in Spark).
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
    HasElasticNetParam,
    HasFeaturesCol,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasTol,
    Model,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops.linalg import solve_spd
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.sharding import shard_rows
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


class LinearRegressionTrainingSummary(NamedTuple):
    """Training metrics computed FROM THE FIT STATISTICS — zero extra data
    passes (RSS/R²/RMSE are closed forms over the normal-equation moments,
    unlike Spark MLlib which re-scans the data for its summary)."""

    rmse: float
    r2: float
    rss: float
    tss: float
    n_rows: int


class LinearSolution(NamedTuple):
    coefficients: np.ndarray  # (d,)
    intercept: float
    n_rows: int
    summary: Optional[LinearRegressionTrainingSummary] = None


@functools.lru_cache(maxsize=32)
def _normal_eq_stats_fn(mesh: Mesh, cd: str, ad: str, use_pallas: Optional[bool] = None):
    """One fused sharded pass: (XᵀX, Xᵀy, Σx, Σy, Σy², n).

    ``use_pallas`` must be resolved by the caller (it is part of this
    cache's key — the flag is read at trace time, same contract as
    ops/gram.py). When on (TPU backend, f32 accum, block-divisible
    shards), the per-shard statistics run in ``linreg_stats_pallas`` —
    one HBM pass instead of XLA's separate Gram/Xᵀy/sum reads (+30% wall
    measured at 1M×1024 bf16)."""
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)

    def shard(x, y, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        n_local = x.shape[0]
        d = x.shape[1]
        # Explicit True forces the kernel (interpret mode off-TPU — the
        # same force-for-tests semantics as config.ann_fused_scan="on");
        # infeasible shapes or f64 accum fall through to the XLA path.
        pallas_ok = (
            bool(use_pallas)
            and accum_dtype == jnp.float32
            and n_local > 0
            and n_local % min(512, n_local) == 0
            and d % 128 == 0
            and d * d * 4 <= 64 * 2**20
        )
        if pallas_ok:
            from spark_rapids_ml_tpu.ops.pallas_kernels import linreg_stats_pallas

            xtx, xty, sx, sy, syy, n = linreg_stats_pallas(
                x.astype(compute_dtype), y, mask,
                block_n=min(512, n_local),
                interpret=not config.backend_is_tpu(),
            )
            return tuple(
                mr.reduce_sum(v, DATA_AXIS)
                for v in (xtx, xty, sx, sy, syy, n)
            )
        xc = x.astype(compute_dtype) * mask.astype(compute_dtype)[:, None]
        yc = y.astype(accum_dtype) * mask.astype(accum_dtype)
        with mm_precision(compute_dtype):
            xtx = jax.lax.dot_general(
                xc, xc, (((0,), (0,)), ((), ())), preferred_element_type=accum_dtype
            )
            xty = jax.lax.dot_general(
                xc, yc[:, None].astype(compute_dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=accum_dtype,
            )[:, 0]
        sx = jnp.sum(xc.astype(accum_dtype), axis=0)
        sy = jnp.sum(yc)
        syy = jnp.sum(yc * yc)
        # Integer sum: an f32 sum of ones saturates at 2^24 rows.
        n = jnp.sum(mask.astype(jnp.int32)).astype(accum_dtype)
        return tuple(
            mr.reduce_sum(v, DATA_AXIS) for v in (xtx, xty, sx, sy, syy, n)
        )

    f = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P(), P(), P(), P()),
        check_vma=False,  # pallas_call out_shapes carry no vma annotation
    )
    return ledgered_jit("linreg.normal_eq_stats", f)


def init_normal_eq_stats(n_cols: int, accum_dtype=None):
    """Zero (XᵀX, Xᵀy, Σx, Σy, Σy², n) accumulator for streaming fits."""
    ad = jnp.dtype(accum_dtype or config.get("accum_dtype"))
    return (
        jnp.zeros((n_cols, n_cols), dtype=ad),
        jnp.zeros((n_cols,), dtype=ad),
        jnp.zeros((n_cols,), dtype=ad),
        jnp.zeros((), dtype=ad),
        jnp.zeros((), dtype=ad),
        jnp.zeros((), dtype=ad),
    )


def streaming_normal_eq_update(mesh: Mesh, compute_dtype=None, accum_dtype=None):
    """Jitted (state, x_batch, y_batch, mask) -> state, donated in-place.

    The LinearRegression analogue of the PCA streaming accumulator
    (SURVEY.md §7.6: "literally the PCA reduction with an extra Xᵀy
    psum") — for datasets ≫ HBM and for the data-plane daemon's
    executor-fed batches."""
    cd = jnp.dtype(compute_dtype or config.get("compute_dtype")).name
    ad = jnp.dtype(accum_dtype or config.get("accum_dtype")).name
    # The config-fed flag only forces the kernel on real TPU backends —
    # off-TPU it would run in interpret mode (the explicit-True force is
    # for tests calling the private fns directly; ops/gram.py convention).
    return _streaming_normal_eq_update(
        mesh, cd, ad,
        bool(config.get("use_pallas")) and config.backend_is_tpu(),
    )


@functools.lru_cache(maxsize=32)
def _streaming_normal_eq_update(mesh: Mesh, cd: str, ad: str, use_pallas: bool = False):
    # Cached per (mesh, dtypes, pallas flag): jax's jit cache is keyed on
    # the function object, so returning a fresh closure per call would
    # re-trace and re-compile the donated update for every job in a
    # long-lived daemon.
    stats = _normal_eq_stats_fn(mesh, cd, ad, use_pallas)

    @functools.partial(ledgered_jit, "linreg.streaming_update", donate_argnums=(0,))
    def update(state, x, y, mask):
        part = stats(x, y, mask)
        return tuple(s + p for s, p in zip(state, part))

    return update


def _fista(a: jax.Array, b: jax.Array, l1: float, iters: int, tol: float) -> jax.Array:
    """min_w ½wᵀAw − bᵀw + l1‖w‖₁ via FISTA; A is PSD d×d on device.

    Stops early when the iterate movement ‖w_{t+1} − w_t‖ drops below tol
    (the estimator's ``tol`` param), else after ``iters`` steps.
    """
    from spark_rapids_ml_tpu.ops.gram import mm_precision

    with mm_precision(a.dtype):  # trace-time scope over the whole solver
        return _fista_body(a, b, l1, iters, tol)


def _fista_body(a, b, l1, iters, tol):
    d = a.shape[0]

    # Lipschitz constant: largest eigenvalue of A by power iteration.
    def power_step(v, _):
        v = a @ v
        v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
        return v, None

    v0 = jnp.ones((d,), a.dtype) / jnp.sqrt(d)
    v, _ = jax.lax.scan(power_step, v0, None, length=50)
    lip = jnp.maximum(v @ (a @ v), 1e-12)
    step = 1.0 / lip

    def soft(z, t):
        return jnp.sign(z) * jnp.maximum(jnp.abs(z) - t, 0.0)

    def body(carry):
        w, z, t, _, it = carry
        g = a @ z - b
        w_next = soft(z - step * g, step * l1)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_next = w_next + ((t - 1.0) / t_next) * (w_next - w)
        delta = jnp.linalg.norm(w_next - w)
        return w_next, z_next, t_next, delta, it + 1

    def cond(carry):
        _, _, _, delta, it = carry
        return jnp.logical_and(it < iters, delta > tol)

    w0 = jnp.zeros((d,), a.dtype)
    init = (w0, w0, jnp.array(1.0, a.dtype), jnp.array(jnp.inf, a.dtype), 0)
    w, _, _, _, _ = jax.lax.while_loop(cond, body, init)
    return w


@functools.lru_cache(maxsize=64)
def _solve_fn(
    fit_intercept: bool, reg: float, alpha: float, max_iter: int, tol: float
):
    """Jitted finalize: stats -> (coefficients, intercept)."""

    def solve(xtx, xty, sx, sy, syy, n):
        del syy  # summary-only statistic
        n = jnp.maximum(n, 1.0)
        if fit_intercept:
            mx = sx / n
            my = sy / n
            a = xtx - jnp.outer(mx, sx)  # centered XᵀX
            b = xty - sx * my  # centered Xᵀy
        else:
            a, b = xtx, xty
        a = a / n
        b = b / n
        l2 = reg * (1.0 - alpha)
        l1 = reg * alpha
        if l1 > 0:
            eye = jnp.eye(a.shape[0], dtype=a.dtype)
            w = _fista(a + l2 * eye, b, l1, max_iter, tol)
        else:
            w = solve_spd(a, b, reg=l2)
        if fit_intercept:
            intercept = my - mx @ w
        else:
            intercept = jnp.zeros((), a.dtype)
        return w, intercept

    return ledgered_jit("linreg.solve", solve)


def fit_linear_regression(
    x: np.ndarray,
    y: np.ndarray,
    reg: float = 0.0,
    elastic_net: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 500,
    tol: float = 1e-6,
    mesh: Optional[Mesh] = None,
) -> LinearSolution:
    mesh = mesh or default_mesh()
    x = np.asarray(x)
    y = np.asarray(y).reshape(-1)
    if x.shape[0] != y.shape[0]:
        raise ValueError(f"X rows {x.shape[0]} != y rows {y.shape[0]}")
    with trace_span("normal equations"):
        xs, mask, n_true = shard_rows(x, mesh)
        ys, _, _ = shard_rows(y, mesh)
        stats = _normal_eq_stats_fn(
            mesh, config.get("compute_dtype"), config.get("accum_dtype"),
            bool(config.get("use_pallas"))
            and config.backend_is_tpu(),  # see streaming_normal_eq_update
        )(xs, ys, mask)
    return finalize_normal_eq_stats(
        stats, reg, elastic_net, fit_intercept, max_iter, tol, n_true
    )


def finalize_normal_eq_stats(
    stats,
    reg: float,
    elastic_net: float,
    fit_intercept: bool,
    max_iter: int,
    tol: float,
    n_true: int,
) -> LinearSolution:
    """(XᵀX, Xᵀy, Σx, Σy, Σy², n) accumulator → LinearSolution.

    Shared tail of batch and streaming fits — also the finalize entry
    point for the data-plane daemon."""
    with trace_span("solve"):
        w, b = _solve_fn(
            bool(fit_intercept), float(reg), float(elastic_net), int(max_iter), float(tol)
        )(*stats)
        w, b = jax.device_get((w, b))
    w = np.asarray(w, dtype=np.float64)
    b = float(b)
    xtx, xty, sx, sy, syy, n = (np.asarray(s, dtype=np.float64) for s in stats)
    n = float(n)
    # Closed-form training metrics from the moments (no second data pass):
    # RSS = Σy² − 2(wᵀXᵀy + bΣy) + wᵀXᵀXw + 2b·wᵀΣx + b²n.
    rss = max(
        float(
            syy - 2.0 * (w @ xty + b * sy) + w @ xtx @ w + 2.0 * b * (w @ sx) + b * b * n
        ),
        0.0,  # clamp: low-precision compute can round a perfect fit negative
    )
    tss = float(syy - sy * sy / max(n, 1.0))
    summary = LinearRegressionTrainingSummary(
        rmse=float(np.sqrt(rss / max(n, 1.0))),
        r2=float(1.0 - rss / tss) if tss > 0 else 0.0,
        rss=rss,
        tss=tss,
        n_rows=n_true,
    )
    return LinearSolution(
        coefficients=w,
        intercept=b,
        n_rows=n_true,
        summary=summary,
    )


class LinearRegressionJob(JobAlgorithm):
    """(XᵀX, Xᵀy, Σx, Σy, Σy², n) folded in one pass; finalize is the
    normal-equations (or elastic-net) solve."""

    name = "linreg"
    needs_labels = True

    def __init__(self, n_cols, mesh, params):
        super().__init__(n_cols, mesh, params)
        self._require_gram_capacity()
        self._update = streaming_normal_eq_update(mesh)

    def zero_state(self):
        return init_normal_eq_stats(self.n_cols)

    def place_columns(self, target, y=None, n=0, partition=None, offset=0):
        return (self._place_column(y, target, np.asarray(y).dtype),)

    def fold(self, state, xs, ms, columns=(), n=0):
        (ys,) = columns
        return self._update(state, xs, ys, ms)

    def finalize(self, state, params, rows, iteration):
        sol = finalize_normal_eq_stats(
            state,
            reg=float(params.get("reg", 0.0)),
            elastic_net=float(params.get("elastic_net", 0.0)),
            fit_intercept=bool(params.get("fit_intercept", True)),
            max_iter=int(params.get("max_iter", 500)),
            tol=float(params.get("tol", 1e-6)),
            n_true=rows,
        )
        return {
            "coefficients": sol.coefficients,
            "intercept": np.asarray([sol.intercept]),
            "rmse": np.asarray([sol.summary.rmse]),
            "r2": np.asarray([sol.summary.r2]),
        }


# ---------------------------------------------------------------------------
# Estimator / Model
# ---------------------------------------------------------------------------


class _LinearRegressionParams(
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasRegParam,
    HasElasticNetParam,
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
            regParam=0.0,
            elasticNetParam=0.0,
            fitIntercept=True,
            maxIter=500,
            tol=1e-6,
        )


class LinearRegression(Estimator, _LinearRegressionParams, MLWritable, MLReadable):
    """Spark-ML-shaped linear regression on the normal-equations path."""

    _uid_prefix = "LinearRegression"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setRegParam(self, value: float) -> "LinearRegression":
        return self._set(regParam=value)

    def setElasticNetParam(self, value: float) -> "LinearRegression":
        return self._set(elasticNetParam=value)

    def setFitIntercept(self, value: bool) -> "LinearRegression":
        return self._set(fitIntercept=value)

    def setMaxIter(self, value: int) -> "LinearRegression":
        return self._set(maxIter=value)

    def setTol(self, value: float) -> "LinearRegression":
        return self._set(tol=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "LinearRegressionModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        y = as_column(dataset, self.getLabelCol())
        sol = fit_linear_regression(
            x,
            y,
            reg=self.getRegParam(),
            elastic_net=self.getElasticNetParam(),
            fit_intercept=self.getFitIntercept(),
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            mesh=self._mesh,
        )
        model = LinearRegressionModel(
            coefficients=sol.coefficients, intercept=sol.intercept
        )
        model.uid = self.uid
        model._summary = sol.summary
        self._copy_params_to(model)
        return model


class LinearRegressionModel(Model, _LinearRegressionParams, MLWritable, MLReadable):
    _uid_prefix = "LinearRegressionModel"

    def __init__(self, coefficients=None, intercept: float = 0.0, uid=None):
        super().__init__(uid=uid)
        self.coefficients = None if coefficients is None else np.asarray(coefficients)
        self.intercept = float(intercept)
        self._summary: Optional[LinearRegressionTrainingSummary] = None

    @property
    def summary(self) -> Optional[LinearRegressionTrainingSummary]:
        """Training metrics (rmse, r2, ...), Spark's model.summary shape.
        None after persistence reload (metrics are training-time only)."""
        return self._summary

    def _model_data(self):
        return {
            "coefficients": self.coefficients,
            "intercept": np.asarray([self.intercept]),
        }

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(
            coefficients=data["coefficients"],
            intercept=float(np.asarray(data["intercept"]).reshape(-1)[0]),
            uid=uid,
        )

    def _copy_extra_state(self, source):
        self.coefficients = source.coefficients
        self.intercept = source.intercept
        self._summary = getattr(source, "_summary", None)

    def predict(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x)
        return x @ self.coefficients + self.intercept

    # Daemon serving contract (serve/daemon.py).
    _serve_algo = "linreg"
    _serve_outputs = (("prediction", "predictionCol", "double"),)

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py; see PCAModel's)."""
        if self.coefficients is None:
            return None
        from spark_rapids_ml_tpu.parallel.sharding import bucket_rows

        d = int(np.asarray(self.coefficients).reshape(-1).shape[0])
        if int(n_cols) != d:
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"model's fitted width {d}"
            )
        return [(
            self._predictor(),
            (jax.ShapeDtypeStruct(
                (bucket_rows(int(n_rows)), d), jnp.dtype(dtype)
            ),),
        )]

    def _predictor(self):
        """Jitted y = x @ w + b with coefficients device-resident (the
        per-batch-upload fix of SURVEY.md §7(d), same pattern as
        PCAModel._projector)."""
        cache = getattr(self, "_predict_cache", None)
        if cache is None:
            cache = self._predict_cache = {}
        from spark_rapids_ml_tpu import config

        key = (config.get("compute_dtype"), config.get("accum_dtype"))
        if key not in cache:
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops.gram import mm_precision

            w_dev = jnp.asarray(self.coefficients, dtype=jnp.dtype(key[0]))
            accum = jnp.dtype(key[1])
            b = float(self.intercept)

            @ledgered_jit("linreg.predict")
            def predict(x):
                with mm_precision(w_dev.dtype):
                    z = jax.lax.dot_general(
                        x.astype(w_dev.dtype),
                        w_dev.reshape(-1, 1),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=accum,
                    )
                return z[:, 0] + b

            cache[key] = predict
        return cache[key]

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed device transform (daemon ``transform`` op surface)."""
        if self.coefficients is None:
            raise RuntimeError("model has no coefficients (unfitted?)")
        from spark_rapids_ml_tpu.parallel.sharding import run_bucketed

        with trace_span("linreg transform"):
            y = run_bucketed(self._predictor(), x)
            return {"prediction": y.astype(np.float64)}

    def _transform(self, dataset):
        if self.coefficients is None:
            raise RuntimeError("model has no coefficients (unfitted?)")
        x = as_matrix(dataset, self.getFeaturesCol())
        return with_column(
            dataset, self.getPredictionCol(), self.transform_matrix(x)["prediction"]
        )
