"""spark_rapids_ml_tpu — TPU-native distributed ML acceleration framework.

A brand-new framework providing the capabilities of NVIDIA's
spark-rapids-ml (Scala/JNI era — drop-in Spark ML estimators accelerated by a
native math core; reference: /root/reference) re-designed TPU-first:

* The cuBLAS/cuSOLVER/RAFT JNI library (reference ``native/src/rapidsml_jni.cu``)
  becomes XLA-compiled JAX kernels (``ops/``) with Pallas where fusion matters.
* The per-partition Gram matrix + JVM ``RDD.reduce`` combine (reference
  ``RapidsRowMatrix.scala:122-139``) becomes ``shard_map`` + ``jax.lax.psum``
  over ICI/DCN (``parallel/``).
* The cuDF LIST-column data plane (reference ``ColumnarRdd``) becomes an
  Arrow columnar bridge with an optional native C++ fast path (``bridge/``).
* The Spark ML Estimator/Model/Params contract (reference
  ``RapidsPCA.scala``) is reproduced in ``core/params.py`` so estimators are
  drop-in shaped: ``PCA().setInputCol(...).setK(3).fit(df)``.

Model families (per BASELINE.json north-star configs): PCA, KMeans,
LinearRegression, LogisticRegression, (approx-)KNN.
"""

__version__ = "0.1.0"

from spark_rapids_ml_tpu import config as config

# Re-export the user-facing estimator namespace, mirroring the reference's
# thin `com.nvidia.spark.ml.feature.PCA` shim (reference PCA.scala:27-37).
from spark_rapids_ml_tpu.models.pca import PCA, PCAModel
from spark_rapids_ml_tpu.models.kmeans import KMeans, KMeansModel
from spark_rapids_ml_tpu.models.linear_regression import (
    LinearRegression,
    LinearRegressionModel,
)
from spark_rapids_ml_tpu.models.logistic_regression import (
    LogisticRegression,
    LogisticRegressionModel,
)
from spark_rapids_ml_tpu.models.knn import (
    NearestNeighbors,
    NearestNeighborsModel,
    ApproximateNearestNeighbors,
    ApproximateNearestNeighborsModel,
)
from spark_rapids_ml_tpu.models.random_forest import (
    RandomForestClassifier,
    RandomForestClassificationModel,
    RandomForestRegressor,
    RandomForestRegressionModel,
)
from spark_rapids_ml_tpu.models.scaler import StandardScaler, StandardScalerModel
from spark_rapids_ml_tpu.pipeline import Pipeline, PipelineModel
from spark_rapids_ml_tpu.tuning import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)
from spark_rapids_ml_tpu.evaluation import (
    BinaryClassificationEvaluator,
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)

__all__ = [
    "PCA",
    "PCAModel",
    "KMeans",
    "KMeansModel",
    "LinearRegression",
    "LinearRegressionModel",
    "LogisticRegression",
    "LogisticRegressionModel",
    "NearestNeighbors",
    "NearestNeighborsModel",
    "ApproximateNearestNeighbors",
    "ApproximateNearestNeighborsModel",
    "RandomForestClassifier",
    "RandomForestClassificationModel",
    "RandomForestRegressor",
    "RandomForestRegressionModel",
    "StandardScaler",
    "StandardScalerModel",
    "Pipeline",
    "PipelineModel",
    "ParamGridBuilder",
    "CrossValidator",
    "CrossValidatorModel",
    "TrainValidationSplit",
    "TrainValidationSplitModel",
    "RegressionEvaluator",
    "BinaryClassificationEvaluator",
    "MulticlassClassificationEvaluator",
    "config",
    "__version__",
]
