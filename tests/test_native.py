"""Native columnar library tests (libsrml_tpu.so via ctypes).

Builds the library with `make -C native` if missing; skips if no toolchain.
Every native function is differential-tested against its NumPy equivalent.
"""

import os
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SO = os.path.join(REPO, "native", "build", "libsrml_tpu.so")


@pytest.fixture(scope="session")
def native_lib():
    from spark_rapids_ml_tpu.bridge import native

    if not os.path.exists(SO):
        try:
            subprocess.run(
                ["make", "-C", os.path.join(REPO, "native")],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (subprocess.SubprocessError, FileNotFoundError) as e:
            pytest.skip(f"cannot build native library: {e}")
        # An earlier test of this worker may have asked for the library
        # before it was built; get_lib() latches that miss for the process.
        native._lib_tried = False
    lib = native.get_lib()
    if lib is None:
        pytest.skip("native library failed to load")
    return native


def test_abi_version(native_lib):
    assert native_lib.get_lib().srml_abi_version() == 1


def test_flatten_f64(native_lib, rng):
    n, d = 1000, 17
    values = rng.normal(size=n * d)
    offsets = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    out = native_lib.flatten_ragged(values, offsets, d)
    np.testing.assert_array_equal(out, values.reshape(n, d))


def test_flatten_f32(native_lib, rng):
    n, d = 64, 5
    values = rng.normal(size=n * d).astype(np.float32)
    offsets = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    out = native_lib.flatten_ragged(values, offsets, d)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, values.reshape(n, d))


def test_flatten_with_nonzero_start(native_lib, rng):
    # Offsets not starting at 0 (sliced window into the child buffer).
    d = 4
    values = rng.normal(size=40)
    offsets = np.array([8, 12, 16, 20], dtype=np.int64)  # 3 rows
    out = native_lib.flatten_ragged(values, offsets, d)
    np.testing.assert_array_equal(out, values[8:20].reshape(3, d))


def test_flatten_ragged_rejected(native_lib):
    values = np.arange(7, dtype=np.float64)
    offsets = np.array([0, 3, 7], dtype=np.int64)  # widths 3, 4
    assert native_lib.flatten_ragged(values, offsets, 3) is None


def test_cast_f64_to_f32(native_lib, rng):
    x = rng.normal(size=(501, 33))
    out = native_lib.cast_f64_to_f32(x)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, x.astype(np.float32))


def test_concat_chunks(native_lib, rng):
    chunks = [rng.normal(size=(n, 6)) for n in (10, 1, 300)]
    out = native_lib.concat_chunks_f64(chunks)
    np.testing.assert_array_equal(out, np.concatenate(chunks))


def test_concat_chunks_mismatched_width(native_lib, rng):
    assert (
        native_lib.concat_chunks_f64(
            [rng.normal(size=(3, 4)), rng.normal(size=(3, 5))]
        )
        is None
    )


def test_sharding_uses_native_cast(native_lib, mesh8, rng):
    # End-to-end: shard_rows with dtype float32 on float64 input.
    from spark_rapids_ml_tpu.parallel.sharding import shard_rows

    x = rng.normal(size=(100, 8))
    xs, mask, n = shard_rows(x, mesh8, dtype=np.float32)
    assert n == 100
    got = np.asarray(xs)[:100]
    np.testing.assert_array_equal(got, x.astype(np.float32))
