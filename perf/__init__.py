"""The benchmark of spark_rapids_ml_tpu: harness, cells and yardstick.

Everything here is read by `python3 perf/run.py`; see perf/README.md.
"""
