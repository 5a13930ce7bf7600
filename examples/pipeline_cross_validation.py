"""Pipeline + CrossValidator: scale → reduce → regress, tuned end to end.

Runs on whatever backend is available (TPU if attached, else CPU; for a
virtual multi-device mesh run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).
"""

import os
import sys

if __package__ in (None, ""):  # runnable without installation
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from spark_rapids_ml_tpu import (
    CrossValidator,
    LinearRegression,
    PCA,
    ParamGridBuilder,
    Pipeline,
    RegressionEvaluator,
    StandardScaler,
)
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

ensure_compile_cache()  # one rule for where compiled programs are kept

rng = np.random.default_rng(0)
n, d = 20_000, 64
x = (rng.normal(size=(n, d)) * rng.uniform(0.5, 20.0, size=d)).astype(np.float32)
# Signal lives in the top principal directions, so the 48-component
# projection preserves it and the tuned ridge recovers a near-noise rmse.
xs = (x - x.mean(0)) / x.std(0)
u, s, vt = np.linalg.svd(xs, full_matrices=False)
w = vt[:16].T @ rng.normal(size=(16,))
y = xs @ w + 1.5 + 0.05 * rng.normal(size=n)
ds = {"features": x, "label": y}

# A pipeline: standardize, project to principal components, regress on them.
pipe = Pipeline(stages=[
    StandardScaler().setWithMean(True).setOutputCol("scaled"),
    PCA().setInputCol("scaled").setK(48).setOutputCol("pca"),
    LinearRegression().setFeaturesCol("pca"),
])

# Tune the ridge strength by 3-fold cross-validation on rmse.
lr = pipe.getStages()[2]
grid = ParamGridBuilder().addGrid(lr.getParam("regParam"), [0.0, 1e-3, 1e-1]).build()
cv = CrossValidator(
    estimator=pipe,
    estimatorParamMaps=grid,
    evaluator=RegressionEvaluator(),  # rmse, lower is better
    numFolds=3,
    seed=0,
)
cvm = cv.fit(ds)
print("avg rmse per candidate:", np.round(cvm.avgMetrics, 4))
pred = cvm.transform(ds)["prediction"]
print("refit-on-full rmse:", round(float(np.sqrt(np.mean((pred - y) ** 2))), 4))
