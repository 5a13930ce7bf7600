"""The control of `correct` at a size a test run can hold: the plain
reference in the nearest precision below the configuration's bfloat16
(`perf/reference/control.py`), put in the program's place, has to come out
NOT correct under the configuration's own tolerances — by
`explained_variance_rel`, the one number whose control reading does not
shrink with the rows (PERF.md §2 has the readings on the chip at the cells'
own size)."""

import pytest

from perf.harness import agree, data, layout
from perf.reference import control
from perf.reference import pca as ref_pca

D, K, ROWS, RING = 512, 32, 4096, 4


@pytest.fixture(scope="module")
def tolerances():
    bench = layout.load_benchmark(layout.REPO_ROOT)
    return layout.load_config(layout.REPO_ROOT, bench, "pca_d2048_k32")["tolerances"]


@pytest.mark.parametrize("seed", [3, 2147483659, 3000000019])
def test_the_reference_in_float8_is_not_correct(tolerances, seed):
    spec = data.pca_spec(seed, D, K)
    ring = [data.device_rows(spec, seed, i, ROWS) for i in range(RING)]
    ref = ref_pca.fit(ring, [2] * RING, K)
    again = ref_pca.fit(ring, [2] * RING, K)
    again["rows"] = int(again["rows"])
    assert agree.check_pca_fit(again, ref, tolerances, D, K) == []  # the reference itself is
    model = control.fit(ring, [2] * RING, K)
    model["rows"] = int(model["rows"])
    problems = agree.check_pca_fit(model, ref, tolerances, D, K)
    assert any("explained_variance" in p for p in problems), problems
    compared = agree.compared_pca([{"model": model}], tolerances, 2 * RING * ROWS)
    value, limit = compared["explained_variance_rel"]
    assert value > 3 * limit and compared["rows_not_folded"] == [0.0, 0.0]


def test_the_control_rounds_to_what_float8_e4m3_holds():
    """Three bits of mantissa, round to nearest even, and nothing else: a
    value float8_e4m3fn holds comes back as it went in."""
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.0625, 1.1875, -2.75, 0.4375, 24.0], jnp.float32)
    assert control.lower(x).tolist() == [1.0, 1.0, 1.25, -2.75, 0.4375, 24.0]
