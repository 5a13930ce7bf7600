"""Minimal end-to-end PCA: fit, transform, persist, reload.

Runs on whatever backend is available (TPU if attached, else CPU; for a
virtual multi-device mesh run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).
"""

import os
import sys

if __package__ in (None, ""):  # runnable without installation
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile

import numpy as np

import spark_rapids_ml_tpu as srml
from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

ensure_compile_cache()  # one rule for where compiled programs are kept

rng = np.random.default_rng(0)
x = (rng.normal(size=(100_000, 256)) * np.logspace(0, -2, 256)).astype(np.float32)

model = srml.PCA().setInputCol("features").setOutputCol("pca").setK(8).fit(
    {"features": x}
)
out = model.transform({"features": x})["pca"]
print("components:", model.pc.shape, "explained:", model.explainedVariance[:4])

path = tempfile.mkdtemp() + "/pca_model"
model.save(path)
reloaded = srml.PCAModel.load(path)
assert np.allclose(reloaded.transform({"features": x})["pca"], out)
print("persistence round-trip OK ->", path)
