"""The daemon's job algorithms by wire name: one ``JobAlgorithm`` subclass
beside its streaming functions (models/job_protocol.py), one entry here."""

from __future__ import annotations

from typing import Dict, Type

from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.models.kmeans import KMeansJob
from spark_rapids_ml_tpu.models.knn import KnnRowsJob
from spark_rapids_ml_tpu.models.linear_regression import LinearRegressionJob
from spark_rapids_ml_tpu.models.logistic_regression import LogisticRegressionJob
from spark_rapids_ml_tpu.models.pca import PCAJob
from spark_rapids_ml_tpu.models.random_forest import RandomForestJob

#: In the order the ``unknown algo`` refusal lists them.
JOB_ALGORITHMS: Dict[str, Type[JobAlgorithm]] = {
    "pca": PCAJob,
    "linreg": LinearRegressionJob,
    "kmeans": KMeansJob,
    "logreg": LogisticRegressionJob,
    "rf": RandomForestJob,
    "knn": KnnRowsJob,
}


def job_algorithm(algo: str) -> Type[JobAlgorithm]:
    """The class behind a wire ``algo``, or the refusal that names them all."""
    if algo not in JOB_ALGORITHMS:
        raise ValueError(f"unknown algo {algo!r} ({'|'.join(JOB_ALGORITHMS)})")
    return JOB_ALGORITHMS[algo]
