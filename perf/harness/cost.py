"""Operations and bytes a kernel's algorithm needs, from its shapes, and
its share of the roofline. Kept with the benchmark so that no PR that
claims a gain can change the yardstick. XLA's cost analysis is not used:
it sees nothing inside a `tpu_custom_call`."""

from __future__ import annotations

from typing import Dict, Tuple

from perf.harness import layout


def pca_fold(n_rows: int, d: int) -> Tuple[float, float]:
    """One fold of `n_rows` float32 rows into (count, colsum, Gram):
    x^T x is 2·n·d² operations, the column sums n·d; the rows are read
    once (4 bytes each element) and the float32 Gram is read and written."""
    return 2.0 * n_rows * d * d + n_rows * d, 4.0 * n_rows * d + 2.0 * 4.0 * d * d


def fold_cost(config: Dict, rows_per_chip: int, root: str = layout.REPO_ROOT
              ) -> Tuple[float, float]:
    """(operations, bytes) of one chip's rows of one fold, by the function
    `fold(config, rows_per_chip)` of `<root>/perf/costs/<algo>.py` — found
    by the configuration's `algo` the way a reader is found by its metric's
    name, so a later PR brings a second algorithm's cost as a file. `root`
    is the tree the run is of (`obs.root`)."""
    try:
        module = layout.load_module(root, "costs", config["algo"])
    except layout.LayoutError as e:
        raise KeyError(f"no fold cost function for algo {config['algo']!r}: {e}") from e
    return module.fold(config, rows_per_chip)


def roofline(flops: float, nbytes: float, seconds: float, peaks: Dict) -> Dict:
    """The least time the chip could take — the larger of operations over
    peak FLOP/s and bytes over peak bytes/s — over the measured time."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_compute, t_memory)
    return {
        "share": least / seconds,
        "bound": "compute" if t_compute >= t_memory else "memory",
        "least_s": least,
    }
