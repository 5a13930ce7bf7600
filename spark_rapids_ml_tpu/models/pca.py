"""Principal Component Analysis — the reference's one shipped algorithm,
rebuilt TPU-native.

Reference call stack being replaced (SURVEY.md §3.1):
``com.nvidia.spark.ml.feature.PCA.fit`` (PCA.scala:27-37) →
``RapidsPCA.fit`` (RapidsPCA.scala:72-80) →
``RapidsRowMatrix.computePrincipalComponentsAndExplainedVariance``
(RapidsRowMatrix.scala:59-102): per-partition cuBLAS Gram (dgemmCov) →
JVM ``RDD.reduce`` → single-GPU cuSOLVER eig (calSVD) → top-k slice.

Here the whole fit is ONE compiled SPMD program: row-sharded fused stats
(count/Σx/XᵀX) → ``psum`` over ICI → eigh + sign-flip + slice on device.
No host round-trip between phases, no per-call device context setup
(the anti-pattern noted at SURVEY.md §3.4), and mean-centering is fused
(fixing the reference's ETL-preprocess stub, SURVEY.md §2.4).

Transform matches ``RapidsPCAModel.transform`` (RapidsPCA.scala:122-166):
y = x @ pc with NO re-centering (the reference's CPU fallback is
``pc.transpose.multiply(v)``, :159 — centering is the caller's concern),
and the principal-components matrix stays device-resident across batches
(avoiding the reference's per-batch host→device PC copy, rapidsml_jni.cu:85).
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core.dataset import as_matrix, with_column
from spark_rapids_ml_tpu.core.params import (
    Estimator,
    HasInputCol,
    HasOutputCol,
    Model,
    ParamDecl,
    ParamValidators,
    TypeConverters,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops import gram as gram_ops
from spark_rapids_ml_tpu.ops.eigh import (
    pca_from_gram,
    pca_from_gram_host,
    pca_from_gram_randomized,
)
from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    default_mesh,
    make_mesh,
)
from spark_rapids_ml_tpu.parallel.sharding import pad_rows, row_sharding, shard_rows
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


class PCASolution(NamedTuple):
    """Fit result of the pure-JAX core (host-side numpy)."""

    pc: np.ndarray  # (d, k) principal components, columns descending
    explained_variance: np.ndarray  # (k,) σᵢ/Σσ — reference semantics
    sigma: np.ndarray  # (d,) singular values √λ of the (centered) Gram
    mean: np.ndarray  # (d,) column means observed during fit
    n_rows: int


# ---------------------------------------------------------------------------
# Pure-JAX core
# ---------------------------------------------------------------------------


def _use_host_finalize(mesh: Mesh) -> bool:
    """Host-LAPACK eig finalize on TPU meshes (config ``finalize``).

    eigh is iterative and XLA executes it poorly on TPU for large d; the d×d
    Gram is tiny to fetch, and the reference likewise ran its eig as a
    separate single-device stage (RapidsRowMatrix.scala:70-86)."""
    mode = config.get("finalize")
    if mode == "host":
        return True
    if mode == "device":
        return False
    platform = next(iter(mesh.devices.flat)).platform
    return platform == "tpu"


@functools.lru_cache(maxsize=32)
def _fit_fn(
    mesh: Mesh,
    k: int,
    mean_center: bool,
    two_d: bool,
    cd: str,
    ad: str,
    fuse_finalize: bool = True,
    gram_algo: str = "auto",
    use_pallas: bool = False,
    solver: str = "full",
):
    # `use_pallas` is unused in the body but MUST be in the cache key:
    # local_stats reads config.use_pallas at trace time, so a config flip
    # has to miss the cache and retrace (same reason cd/ad are keys).
    """Compile the fit (stats + psum [+ eig finalize]) once per config.

    ``cd``/``ad`` (compute/accum dtype names) are part of the cache key so a
    config change recompiles rather than silently reusing old-dtype programs.
    With ``fuse_finalize=False`` the program stops at the replicated stats
    (host finalize path).
    """

    def fit(x, mask):
        if two_d:
            if gram_algo == "ring":
                shard_fn = functools.partial(
                    gram_ops._stats_shard_ring,
                    compute_dtype=cd,
                    accum_dtype=ad,
                    n_model=mesh.shape[MODEL_AXIS],
                )
            else:
                shard_fn = lambda xb, mb: gram_ops._stats_shard_2d(xb, mb, cd, ad)
            stats = jax.shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS)),
                out_specs=(P(), P(), P(MODEL_AXIS, None)),
                # count/colsum are value-replicated over `model` after the
                # gather/ring, which VMA inference can't prove statically.
                check_vma=False,
            )
        else:
            stats = jax.shard_map(
                lambda xb, mb: gram_ops._stats_shard(xb, mb, cd, ad),
                mesh=mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
                out_specs=(P(), P(), P()),
                check_vma=False,  # pallas_call out_shapes carry no vma annotation
            )
        count, colsum, g = stats(x, mask)
        if not fuse_finalize:
            return count, colsum, g
        g, mean = gram_ops.finalize_gram(count, colsum, g, mean_center)
        if solver == "randomized":
            if two_d:
                # Keep the Gram model-sharded through the eigensolve too
                # (docs/mesh.md "Model-parallel Gram/eigh"): for widths
                # over the per-device accumulator budget this is the only
                # shape in which the finalize fits at all.
                from spark_rapids_ml_tpu.ops.eigh import (
                    pca_from_gram_model_sharded,
                )

                pc, ev, s = pca_from_gram_model_sharded(g, k, mesh)
            else:
                pc, ev, s = pca_from_gram_randomized(g, k)
        else:
            pc, ev, s = pca_from_gram(g, k)
        return pc, ev, s, mean, count

    return ledgered_jit("pca.fit", fit)


_SOLVERS = ("full", "randomized")


def _resolve_solver(solver: Optional[str]) -> str:
    """None/"auto" → config ``solver``; otherwise validate explicitly —
    a typo must not silently select the slow exact path."""
    if solver is None or solver == "auto":
        solver = config.get("solver")
    if solver == "auto":  # config itself left at/reset to auto → exact path
        solver = "full"
    if solver not in _SOLVERS:
        raise ValueError(f"solver must be one of {_SOLVERS} or 'auto', got {solver!r}")
    return solver


def _finalize_on_host(count, colsum, gram, mean_center: bool, k: int):
    """Centering + calSVD-equivalent on host float64 (TPU finalize path).

    Runs inside the caller's ``eig finalize`` span and splits it into
    children (docs/observability.md "Phases"): ``finalize.wait`` (device
    work the caller had not waited for — the folds a daemon acked after
    dispatch land here), ``finalize.fetch`` (the state's copy to host
    memory, nothing else), ``finalize.center`` (one float64 cast of the
    Gram into an array of this call's own, the mean, and the rank-1
    centring update written into that array), then ``finalize.lapack`` /
    ``finalize.post`` inside :func:`pca_from_gram_host`, which is handed
    the array to work in. Nothing the caller holds is written."""
    with trace_span("finalize.wait"):
        jax.block_until_ready((count, colsum, gram))
    with trace_span("finalize.fetch"):
        count, colsum, gram = jax.device_get((count, colsum, gram))
    with trace_span("finalize.center"):
        count = float(count)
        colsum = np.asarray(colsum, dtype=np.float64)
        g = np.array(gram, dtype=np.float64, order="C")
        n = max(count, 1.0)
        mean = colsum / n
        if mean_center:
            from scipy.linalg.blas import dger

            # g -= mean ⊗ colsum with no outer product materialised: g.T is
            # g's memory read as a Fortran array, where it is colsum ⊗ mean
            g = dger(-1.0, colsum, mean, a=g.T, overwrite_a=1).T
    pc, ev, s = pca_from_gram_host(g, k, overwrite_gram=True)
    return pc, ev, s, mean, count


def fit_pca(
    x: np.ndarray,
    k: int,
    mean_center: bool = True,
    mesh: Optional[Mesh] = None,
    solver: Optional[str] = None,
) -> PCASolution:
    """Fit PCA on a host matrix, sharding rows (and features if the mesh has a
    model axis > 1) across the mesh.

    ``solver``: None → config ``solver``; "full" = exact eigh finalize
    (host LAPACK on TPU), "randomized" = on-device subspace iteration
    (:func:`...ops.eigh.pca_from_gram_randomized`).
    """
    mesh = mesh or default_mesh()
    solver = _resolve_solver(solver)
    d = x.shape[1]
    if not 0 < k <= d:
        # require(k > 0 && k <= n) — RapidsRowMatrix.scala:60
        raise ValueError(f"k = {k} out of range (0, n = {d}]")
    two_d = mesh.shape[MODEL_AXIS] > 1 and d % mesh.shape[MODEL_AXIS] == 0
    # Capacity gate: a (d, d) accumulator over the per-device budget must
    # stay model-sharded end to end (docs/mesh.md) — with a model axis the
    # 2-D path + sharded eigensolve carries it; without one this raises
    # GramCapacityError here instead of OOMing mid-fit.
    must_shard = gram_ops.require_gram_capacity(d, mesh)
    if must_shard and not two_d:
        raise gram_ops.GramCapacityError(
            f"d={d} needs the model-sharded Gram but is not divisible by "
            f"the model axis ({mesh.shape[MODEL_AXIS]}); pick a divisor "
            "mesh_model_axis (docs/mesh.md 'Model-parallel Gram/eigh')"
        )
    with trace_span("compute cov"):  # phase names kept from the reference
        if two_d:
            from jax.sharding import NamedSharding

            n_true = x.shape[0]
            xp, mask_np = pad_rows(np.asarray(x), mesh.shape[DATA_AXIS])
            xs = jax.device_put(xp, NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS)))
            mask = jax.device_put(mask_np, NamedSharding(mesh, P(DATA_AXIS)))
        else:
            xs, mask, n_true = shard_rows(x, mesh)
        # must_shard forces the exact ("full") eigh onto the HOST: a d×d
        # on-device eigh would re-materialize the over-budget Gram on one
        # device, while the host assembles it from the slabs comfortably.
        # The randomized solver instead stays fused and model-sharded
        # (pca_from_gram_model_sharded) — nothing full-width on any chip.
        host_finalize = (
            _use_host_finalize(mesh) or must_shard
        ) and solver != "randomized"
        fit = _fit_fn(
            mesh,
            k,
            mean_center,
            two_d,
            config.get("compute_dtype"),
            config.get("accum_dtype"),
            fuse_finalize=not host_finalize,
            gram_algo=config.get("gram_algorithm"),
            use_pallas=bool(config.get("use_pallas")),
            solver=solver,
        )
        out = fit(xs, mask)
    with trace_span("eig finalize"):
        if host_finalize:
            count, colsum, g = out
            pc, ev, s, mean, _ = _finalize_on_host(count, colsum, g, mean_center, k)
        else:
            with trace_span("finalize.device"):
                pc, ev, s, mean, count = out
                pc, ev, s, mean = jax.device_get((pc, ev, s, mean))
    return PCASolution(
        pc=np.asarray(pc, dtype=np.float64),
        explained_variance=np.asarray(ev, dtype=np.float64),
        sigma=np.asarray(s, dtype=np.float64),
        mean=np.asarray(mean, dtype=np.float64),
        n_rows=n_true,
    )


def fit_pca_stream(
    batches: Iterable[np.ndarray],
    k: int,
    n_cols: int,
    mean_center: bool = True,
    mesh: Optional[Mesh] = None,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 16,
    solver: Optional[str] = None,
) -> PCASolution:
    """Fit PCA over a stream of host row-batches (dataset ≫ HBM).

    The accumulator state lives on device; each batch is row-sharded,
    reduced with psum, and folded in with buffer donation. This is the
    scale path for BASELINE.json config #2 (100M×2048).

    With ``checkpoint_path``, the O(d²) accumulator is atomically persisted
    every ``checkpoint_every`` batches and the fit RESUMES from it if the
    file exists: callers re-supply the same batch iterator and already-
    consumed batches are skipped. (Preemption safety the reference lacks —
    SURVEY.md §5 "failure detection".)

    **Multi-host** (``jax.process_count() > 1``, e.g. a v5e-16 pod):
    ``batches`` is THIS process's local stream — each host reads only its
    own shard of the dataset. Batches are assembled into global arrays via
    the multi-process branch of ``shard_rows`` and iterated in lockstep
    (``lockstep_batches``: uneven stream lengths are fine — exhausted
    hosts contribute empty batches). Checkpoints are written by process 0
    only and must be resumable by every process (shared filesystem);
    because the accumulator is fully replicated, one file restores all.
    """
    if not 0 < k <= n_cols:
        # require(k > 0 && k <= n) — RapidsRowMatrix.scala:60
        raise ValueError(f"k = {k} out of range (0, n = {n_cols}]")
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    solver = _resolve_solver(solver)  # fail fast, before consuming batches
    from spark_rapids_ml_tpu.core import checkpoint as ckpt
    from spark_rapids_ml_tpu.parallel.sharding import lockstep_batches, shard_rows

    mesh = mesh or default_mesh()
    if gram_ops.require_gram_capacity(n_cols, mesh):
        # The streaming accumulator is REPLICATED on every device (the
        # donated P() state), so a model axis does not shelter it; the
        # model-sharded accumulate is the in-memory fit's 2-D path.
        raise gram_ops.GramCapacityError(
            f"the ({n_cols}, {n_cols}) streaming accumulator is over the "
            "per-device budget and the streaming path keeps it replicated; "
            "use fit_pca with mesh_model_axis > 1 (docs/mesh.md) or raise "
            "SRML_GRAM_DEVICE_BUDGET_MB"
        )
    multiproc = jax.process_count() > 1
    update = gram_ops.streaming_update(mesh)
    state = gram_ops.init_stats(n_cols)
    n_true = 0
    skip_batches = 0
    if checkpoint_path:
        restored = ckpt.load_state(checkpoint_path)
        ckpt.require_consistent_visibility(restored)
        if restored is not None:
            arrays, meta = restored
            if meta.get("n_cols") != n_cols:
                raise ValueError(
                    f"checkpoint at {checkpoint_path} is for n_cols="
                    f"{meta.get('n_cols')}, not {n_cols}"
                )
            state = (
                jnp.asarray(arrays["count"]),
                jnp.asarray(arrays["colsum"]),
                jnp.asarray(arrays["gram"]),
            )
            n_true = int(meta["n_rows"])
            skip_batches = int(meta["n_batches"])
    with trace_span("compute cov"):
        for i, batch in enumerate(lockstep_batches(batches, n_cols)):
            if i < skip_batches:
                continue
            xs, ms, n_b = shard_rows(batch, mesh)
            n_true += n_b
            state = update(state, xs, ms)
            if checkpoint_path and (i + 1) % checkpoint_every == 0:
                count, colsum, g = jax.device_get(state)
                if not multiproc or jax.process_index() == 0:
                    ckpt.save_state(
                        checkpoint_path,
                        {"count": count, "colsum": colsum, "gram": g},
                        {"n_rows": n_true, "n_batches": i + 1, "n_cols": n_cols},
                    )
    if checkpoint_path and (not multiproc or jax.process_index() == 0):
        # Success: remove the checkpoint so a FUTURE fit against the same
        # path starts fresh instead of silently merging this run's
        # accumulator into different data.
        import os

        if os.path.exists(checkpoint_path):
            os.unlink(checkpoint_path)
    return finalize_pca_stats(state, k, mean_center, mesh, n_true, solver=solver)


def finalize_pca_stats(
    state: gram_ops.Stats,
    k: int,
    mean_center: bool,
    mesh: Mesh,
    n_true: int,
    solver: Optional[str] = None,
) -> PCASolution:
    """(count, colsum, gram) accumulator → PCASolution.

    Shared tail of the streaming fit — also the finalize entry point for
    the data-plane daemon, which accumulates the same state from
    executor-fed Arrow batches."""
    solver = _resolve_solver(solver)
    count, colsum, g = state
    n_cols = int(np.shape(colsum)[0])  # no copy: a D2H here would wait outside the span
    if not 0 < k <= n_cols:
        # require(k > 0 && k <= n) — RapidsRowMatrix.scala:60; without this
        # the top-k slice silently clamps and returns fewer components
        raise ValueError(f"k = {k} out of range (0, n = {n_cols}]")
    with trace_span("eig finalize"):
        if _use_host_finalize(mesh) and solver != "randomized":
            pc, ev, s, mean, _ = _finalize_on_host(count, colsum, g, mean_center, k)
        else:
            finalize_fn = (
                pca_from_gram_randomized if solver == "randomized" else pca_from_gram
            )
            finalize = ledgered_jit(
                "pca.finalize",
                lambda c, cs, gg: finalize_fn(
                    gram_ops.finalize_gram(c, cs, gg, mean_center)[0], k
                )
            )
            with trace_span("finalize.device"):
                pc, ev, s = jax.device_get(finalize(count, colsum, g))
                mean = jax.device_get(colsum / jnp.maximum(count, 1))
    return PCASolution(
        pc=np.asarray(pc, dtype=np.float64),
        explained_variance=np.asarray(ev, dtype=np.float64),
        sigma=np.asarray(s, dtype=np.float64),
        mean=np.asarray(mean, dtype=np.float64),
        n_rows=n_true,
    )


class PCAJob(JobAlgorithm):
    """(count, Σx, XᵀX) folded in one pass; finalize is the eigensolve —
    or, with ``raw_moments``, the moments themselves: a StandardScaler fit
    is a strict subset of these statistics (count, Σx, diag XᵀX), so
    scaler fits ride the pca job protocol."""

    name = "pca"

    def __init__(self, n_cols, mesh, params):
        super().__init__(n_cols, mesh, params)
        self._require_gram_capacity()
        self._update = gram_ops.streaming_update(mesh)

    def zero_state(self):
        return gram_ops.init_stats(self.n_cols)

    def fold(self, state, xs, ms, columns=(), n=0):
        return self._update(state, xs, ms)

    def finalize(self, state, params, rows, iteration):
        if params.get("raw_moments"):
            count, colsum, g = jax.device_get(state)
            return {
                "count": np.asarray([float(count)]),
                "colsum": np.asarray(colsum),
                "gram_diag": np.diagonal(np.asarray(g)).copy(),
            }
        sol = finalize_pca_stats(
            state,
            k=int(params["k"]),
            mean_center=bool(params.get("mean_center", True)),
            mesh=self.mesh,
            n_true=rows,
            solver=params.get("solver"),
        )
        return {
            "pc": sol.pc,
            "explained_variance": sol.explained_variance,
            "sigma": sol.sigma,
            "mean": sol.mean,
        }


# ---------------------------------------------------------------------------
# Estimator / Model (Spark ML contract — reference RapidsPCA.scala)
# ---------------------------------------------------------------------------


class _PCAParams(HasInputCol, HasOutputCol):
    """Params shared by PCA and PCAModel (RapidsPCAParams, RapidsPCA.scala:34-46)."""

    k = ParamDecl(
        "k",
        "number of principal components (> 0)",
        TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )
    meanCentering = ParamDecl(
        "meanCentering",
        "whether to center data before computing the covariance "
        "(fused on-device here; the reference stubs this to ETL)",
        TypeConverters.toBoolean,
    )

    solver = ParamDecl(
        "solver",
        'eigensolver for the finalize: "auto" (config), "full" (exact '
        'eigh), or "randomized" (on-device subspace iteration — the '
        "TPU-fast path for large feature dims with decaying spectra)",
        TypeConverters.toString,
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        # default true — RapidsPCA.scala:45-46
        self.setDefault(
            meanCentering=True,
            inputCol="features",
            outputCol="pca_features",
            solver="auto",
        )

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getMeanCentering(self) -> bool:
        return self.getOrDefault(self.meanCentering)

    def getSolver(self) -> str:
        return self.getOrDefault(self.solver)


class PCA(Estimator, _PCAParams, MLWritable, MLReadable):
    """PCA estimator: ``PCA().setInputCol("features").setK(3).fit(df)``.

    Drop-in shaped for the reference's public API
    (com.nvidia.spark.ml.feature.PCA, PCA.scala:27-37; input is an
    array-of-floats column, README.md:26-37).
    """

    _uid_prefix = "PCA"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setK(self, value: int) -> "PCA":
        return self._set(k=value)

    def setMeanCentering(self, value: bool) -> "PCA":
        return self._set(meanCentering=value)

    def setSolver(self, value: str) -> "PCA":
        return self._set(solver=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "PCAModel":
        x = as_matrix(dataset, self.getInputCol())
        sol = fit_pca(
            x,
            k=self.getK(),
            mean_center=self.getMeanCentering(),
            mesh=self._mesh,
            solver=self.getSolver(),
        )
        model = PCAModel(
            pc=sol.pc,
            explained_variance=sol.explained_variance,
            mean=sol.mean,
        )
        model.uid = self.uid
        # Parent params flow to the model — Model.copy semantics in Spark.
        self._copy_params_to(model)
        return model


class PCAModel(Model, _PCAParams, MLWritable, MLReadable):
    """Fitted PCA model: pc (d, k), explainedVariance (k,).

    (RapidsPCAModel, RapidsPCA.scala:102-166.)
    """

    _uid_prefix = "PCAModel"

    def __init__(
        self,
        pc: Optional[np.ndarray] = None,
        explained_variance: Optional[np.ndarray] = None,
        mean: Optional[np.ndarray] = None,
        uid=None,
    ):
        super().__init__(uid=uid)
        self.pc = None if pc is None else np.asarray(pc)
        self.explainedVariance = (
            None if explained_variance is None else np.asarray(explained_variance)
        )
        self.mean = None if mean is None else np.asarray(mean)
        self._project_cache: dict = {}

    # -- persistence (PCAModelWriter/Reader, RapidsPCA.scala:193-228) ------
    def _model_data(self):
        data = {"pc": self.pc}
        # Omit-when-None (like mean): a legacy-loaded model re-saved with
        # an explainedVariance=None column would reload as a 0-d nan.
        if self.explainedVariance is not None:
            data["explainedVariance"] = self.explainedVariance
        if self.mean is not None:
            data["mean"] = self.mean
        return data

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(
            pc=data["pc"],
            # Tolerate saves without explainedVariance — the reference's
            # reader does the same for pre-Spark-1.6 models
            # (RapidsPCA.scala:209-213); transform needs only pc.
            explained_variance=data.get("explainedVariance"),
            mean=data.get("mean"),
            uid=uid,
        )

    def _copy_extra_state(self, source):
        self.pc = source.pc
        self.explainedVariance = source.explainedVariance
        self.mean = source.mean
        self._project_cache = {}

    # -- transform ---------------------------------------------------------
    def _projector(self):
        """Jitted y = x @ pc with the PC matrix resident on device.

        The reference re-uploads the PC matrix host→device on every batch
        (rapidsml_jni.cu:85, flagged in SURVEY.md §7(d)); keeping it as a
        captured device constant amortizes it to once per compile. The cache
        is keyed by the dtype config so later config changes recompile.
        """
        key = (config.get("compute_dtype"), config.get("accum_dtype"))
        if key not in self._project_cache:
            pc_dev = jnp.asarray(self.pc, dtype=jnp.dtype(key[0]))
            accum = jnp.dtype(key[1])

            from spark_rapids_ml_tpu.ops.gram import mm_precision

            @ledgered_jit("pca.project")
            def project(x):
                with mm_precision(pc_dev.dtype):
                    return jax.lax.dot_general(
                        x.astype(pc_dev.dtype),
                        pc_dev,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=accum,
                    )

            self._project_cache[key] = project
        return self._project_cache[key]

    # Daemon serving contract (serve/daemon.py): wire algo name + role →
    # (param naming the output column, canonical column kind).
    _serve_algo = "pca"
    _serve_outputs = (("output", "outputCol", "vec"),)

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py): the serving jits
        one padded bucket of ``n_rows`` wire-dtype rows dispatches, with
        their abstract arg specs — ``lower().compile()``d when the model
        registers so the first request pays zero compiles. The primed row
        count is what ``run_bucketed`` will actually dispatch for an
        ``n_rows`` batch (its 256-row floor applies), so small serve
        buckets dedupe onto the one real shape instead of compiling
        unreachable executables."""
        if self.pc is None:
            return None
        if int(n_cols) != int(self.pc.shape[0]):
            # Raise, don't degrade: the trace warmup surfaced a wrong
            # width as a shape error too — acking it would pre-mark a
            # shape no real traffic can produce.
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"model's fitted width {int(self.pc.shape[0])}"
            )
        from spark_rapids_ml_tpu.parallel.sharding import bucket_rows

        return [(
            self._projector(),
            (jax.ShapeDtypeStruct(
                (bucket_rows(int(n_rows)), int(self.pc.shape[0])),
                jnp.dtype(dtype),
            ),),
        )]

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed transform of a bare (n, d) matrix on device — the
        serving surface the data-plane daemon's ``transform`` op calls
        (the accelerator-resident columnar UDF of the reference,
        RapidsPCA.scala:128-161 → rapidsml_jni.cu:75-107)."""
        if self.pc is None:
            raise RuntimeError("PCAModel has no principal components (unfitted?)")
        from spark_rapids_ml_tpu.parallel.sharding import run_bucketed

        with trace_span("pca transform"):
            return {"output": run_bucketed(self._projector(), x)}

    def _transform(self, dataset):
        x = as_matrix(dataset, self.getInputCol())
        y = self.transform_matrix(x)["output"]
        return with_column(dataset, self.getOutputCol(), y)

    def setOutputCol(self, value: str) -> "PCAModel":
        return self._set(outputCol=value)
