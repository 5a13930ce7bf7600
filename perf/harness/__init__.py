"""Harness of the benchmark: loaders, clocks, reductions, the comparison
that decides `correct`. Later PRs add files beside it and edit none."""
