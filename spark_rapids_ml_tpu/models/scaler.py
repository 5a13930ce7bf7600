"""StandardScaler — feature standardization, Spark ML semantics.

The reference leaves mean-centering to "an ETL preprocess upstream"
(RapidsRowMatrix.scala:111-117, the stubbed ``meanCentering`` branch —
SURVEY.md §2.4); this estimator IS that preprocess, done properly on
device: one sharded pass accumulates count/Σx/Σx² with a ``psum`` over
ICI, the model then standardizes batches with a fused elementwise kernel
(XLA fuses (x − μ)·s into the surrounding graph).

Spark parity (``org.apache.spark.ml.feature.StandardScaler``):
``withStd`` defaults true, ``withMean`` defaults false (dense-shift
safety), std is the UNBIASED sample standard deviation (ddof=1), and
zero-variance features scale by 0 exactly like MLlib's
``StandardScalerModel`` (their transformed value is 0/constant, never
NaN).
"""

from __future__ import annotations

import functools
from typing import Optional

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
    TypeConverters,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.sharding import shard_rows
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


@functools.lru_cache(maxsize=32)
def _moments_fn(mesh: Mesh, ad: str):
    accum = jnp.dtype(ad)

    def shard(x, mask):
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        with mm_precision(accum):
            xc = x.astype(accum) * mask.astype(accum)[:, None]
            n = mr.reduce_sum(
                jnp.sum(mask.astype(jnp.int32)).astype(accum), DATA_AXIS
            )
            s1 = mr.reduce_sum(jnp.sum(xc, axis=0), DATA_AXIS)
            s2 = mr.reduce_sum(jnp.sum(jnp.square(xc), axis=0), DATA_AXIS)
            return n, s1, s2

    f = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
    )
    return ledgered_jit("scaler.stats", f)


class _ScalerParams(HasInputCol, HasOutputCol):
    withMean = ParamDecl(
        "withMean", "center features to zero mean", TypeConverters.toBoolean
    )
    withStd = ParamDecl(
        "withStd", "scale features to unit standard deviation", TypeConverters.toBoolean
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(
            withMean=False, withStd=True, inputCol="features",
            outputCol="scaled_features",
        )

    def getWithMean(self) -> bool:
        return self.getOrDefault(self.withMean)

    def getWithStd(self) -> bool:
        return self.getOrDefault(self.withStd)


class StandardScaler(Estimator, _ScalerParams, MLWritable, MLReadable):
    """fit() computes per-feature mean/std in one sharded device pass."""

    _uid_prefix = "StandardScaler"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setWithMean(self, value: bool) -> "StandardScaler":
        return self._set(withMean=value)

    def setWithStd(self, value: bool) -> "StandardScaler":
        return self._set(withStd=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "StandardScalerModel":
        x = as_matrix(dataset, self.getInputCol())
        mesh = self._mesh or default_mesh()
        with trace_span("scaler fit"):
            xs, mask, n_true = shard_rows(np.asarray(x, np.float32), mesh)
            n, s1, s2 = jax.device_get(
                _moments_fn(mesh, config.get("accum_dtype"))(xs, mask)
            )
        n = float(n)
        mean = np.asarray(s1, np.float64) / n
        # Unbiased sample variance, numerically floored at 0 (the
        # Σx² − n·μ² form can go -eps for constant features).
        var = (np.asarray(s2, np.float64) - n * mean * mean) / max(n - 1.0, 1.0)
        std = np.sqrt(np.maximum(var, 0.0))
        model = StandardScalerModel(mean=mean, std=std)
        model.uid = self.uid
        self._copy_params_to(model)
        return model


class StandardScalerModel(Model, _ScalerParams, MLWritable, MLReadable):
    _uid_prefix = "StandardScalerModel"

    def __init__(self, mean: Optional[np.ndarray] = None,
                 std: Optional[np.ndarray] = None, uid=None):
        super().__init__(uid=uid)
        self.mean = None if mean is None else np.asarray(mean, np.float64)
        self.std = None if std is None else np.asarray(std, np.float64)

    def _model_data(self):
        return {"mean": self.mean, "std": self.std}

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(mean=data["mean"], std=data["std"], uid=uid)

    def _copy_extra_state(self, source):
        self.mean = source.mean
        self.std = source.std

    # Daemon serving contract (serve/daemon.py). withMean/withStd ride the
    # registration params so the served copy scales identically — they are
    # the only params that change the served output (_serve_params).
    _serve_algo = "scaler"
    _serve_outputs = (("output", "outputCol", "vec"),)
    _serve_params = ("withMean", "withStd")

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py): the scaler is host
        elementwise — nothing compiles, so the plan is trivially
        complete (an empty list, not None: AOT "succeeds" with zero
        executables rather than degrading to trace warmup). A wrong
        ``n_cols`` still raises — the ack must not bless a width the
        transform will reject."""
        if self.mean is not None:
            d = int(np.asarray(self.mean).shape[0])
            if int(n_cols) != d:
                raise ValueError(
                    f"warmup n_cols={int(n_cols)} does not match the "
                    f"model's fitted width {d}"
                )
        return []

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed transform of a bare matrix (host elementwise — the
        op is bandwidth-trivial relative to any model GEMM)."""
        with trace_span("scaler transform"):
            x = np.asarray(x).astype(np.float64)
            if self.getWithMean():
                x = x - self.mean[None, :]
            if self.getWithStd():
                # MLlib convention: zero-variance features multiply by 0.
                inv = np.where(self.std > 0, 1.0 / np.where(self.std > 0, self.std, 1.0), 0.0)
                x = x * inv[None, :]
            return {"output": x.astype(np.float32)}

    def _transform(self, dataset):
        x = as_matrix(dataset, self.getInputCol())
        return with_column(
            dataset, self.getOutputCol(), self.transform_matrix(x)["output"]
        )
