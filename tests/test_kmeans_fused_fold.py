"""The streaming KMeans fold through the fused Lloyd-step kernel.

`models/kmeans.py` `_stream_shard_fn` reads a batch once where the kernel's
gate holds (TPU backend, lane-aligned d, VMEM budget): `lloyd_step_pallas`
takes float32 rows, casts each tile to the compute dtype in VMEM, and gives
sums, counts AND the cost. Here the kernel runs in interpret mode on the
CPU and is held to the XLA body of the same shard function — the path a CPU
takes and the one the kernel replaces on the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import kmeans as km
from spark_rapids_ml_tpu.ops import pallas_kernels as pk
from spark_rapids_ml_tpu.parallel.mesh import make_mesh
from spark_rapids_ml_tpu.utils import metrics

D, K = 256, 100  # the benchmark configuration's widths (kmeans_d256_k100)
CD, AD = "bfloat16", "float32"  # the `auto` profile on the chip


def _blobs(seed: int, n: int, d: int = D, k: int = K):
    """Separated blobs (nearest centres ~8 sigma apart): no row sits within
    rounding of a boundary, so two float32 orders of one bfloat16 product
    give the same argmin."""
    rng = np.random.default_rng(seed)
    centers = (rng.normal(size=(k, d)) * 3).astype(np.float32)
    lab = rng.integers(0, k, size=n)
    x = (centers[lab] + 0.5 * rng.normal(size=(n, d))).astype(np.float32)
    start = (centers + 0.2 * rng.normal(size=(k, d))).astype(np.float32)
    return x, start


def _zero(k: int = K, d: int = D):
    return km.stream_zero_state(k, d, AD)


def _xla_fold(mesh, x, mask, start, k: int = K):
    """The XLA body of the shard function: use_pallas off."""
    f = km._stream_shard_fn(mesh, k, CD, AD, False)
    return [np.asarray(a) for a in f(*_zero(k, x.shape[1]), jnp.asarray(start),
                                     jnp.asarray(x), jnp.asarray(mask))]


def _cpad(start, k: int = K):
    return km._pad_centers(jnp.asarray(start), pk._ceil_to(k, 128), jnp.bfloat16)


def _kernel(x, start, n_valid, block_n, k: int = K):
    sums, counts, cost = pk.lloyd_step_pallas(
        jnp.asarray(x), _cpad(start, k), n_valid, k=k, block_n=block_n, interpret=True)
    return np.asarray(sums)[:k], np.asarray(counts)[:k], float(cost)


def _assert_same_stats(got, want, rows):
    """counts exactly; sums and cost to float32 accumulation order (the
    products are of the same bfloat16 operands on both sides)."""
    np.testing.assert_array_equal(got[1], want[1])
    scale = np.abs(want[0]).max()
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=2e-6 * scale)
    # per row ‖x‖² + ‖c‖² − 2x·c cancels ~2,500 down to ~64 in float32
    np.testing.assert_allclose(got[2], want[2], rtol=0, atol=1e-3 * max(rows, 1))


@pytest.mark.parametrize("seed,n,block_n", [(0, 4096, 1024), (1, 2048, 2048), (2, 3072, 512)])
def test_kernel_matches_the_xla_body_on_float32_rows(mesh1, seed, n, block_n):
    x, start = _blobs(seed, n)
    assert x.dtype == np.float32  # cast to bfloat16 inside the kernel, not before it
    want = _xla_fold(mesh1, x, np.ones(n, np.float32), start)
    assert want[1].sum() == n and want[2] > 0
    _assert_same_stats(_kernel(x, start, n, block_n), want, n)


def test_without_the_cost_the_kernel_gives_the_same_sums_and_counts(mesh1):
    """The in-memory fit's call (`_lloyd_fn`): no third output is computed."""
    x, start = _blobs(7, 2048)
    sums, counts, cost = pk.lloyd_step_pallas(
        jnp.asarray(x), _cpad(start), 1999, k=K, block_n=512, with_cost=False,
        interpret=True)
    assert cost is None
    want = _kernel(x, start, 1999, 512)
    np.testing.assert_array_equal(np.asarray(sums)[:K], want[0])
    np.testing.assert_array_equal(np.asarray(counts)[:K], want[1])


@pytest.mark.parametrize("n_valid", [0, 1, 700, 1024, 1025, 2047, 2048])
def test_rows_at_or_past_n_valid_are_left_out_of_sums_counts_and_cost(mesh1, n_valid):
    """mid-block, on a block's edge, whole blocks past it, none, all."""
    n, block_n = 2048, 512
    x, start = _blobs(3, n)
    x[n_valid:] = 7.0  # padding that would show in every statistic
    mask = (np.arange(n) < n_valid).astype(np.float32)
    want = _xla_fold(mesh1, x, mask, start)
    got = _kernel(x, start, n_valid, block_n)
    assert got[1].sum() == n_valid
    _assert_same_stats(got, want, n_valid)
    if n_valid == 0:
        assert got[2] == 0.0 and not got[0].any()


def test_cost_is_clipped_at_zero_row_by_row(mesh1):
    """Rows that ARE their bfloat16 centre, at a large norm: the Gram trick
    leaves float32 noise of either sign, `sq_euclidean` clips each distance
    before the sum, and so does the kernel — the sum without the clip has
    the other sign."""
    rng = np.random.default_rng(4)
    centers = (rng.normal(size=(K, D)) * 50).astype(np.float32)
    cb = jnp.asarray(centers).astype(jnp.bfloat16)
    x = np.tile(np.asarray(cb.astype(jnp.float32)), (6, 1))[:512]
    xy = jax.lax.dot_general(
        jnp.asarray(x).astype(jnp.bfloat16), cb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    norms = jnp.sum(jnp.square(cb.astype(jnp.float32)), axis=1)
    unclipped = float(jnp.sum(jnp.min(
        jnp.sum(jnp.square(jnp.asarray(x)), axis=1)[:, None] + norms[None, :] - 2.0 * xy,
        axis=1)))
    want = _xla_fold(mesh1, x, np.ones(512, np.float32), centers)
    _, counts, cost = _kernel(x, centers, 512, 256)
    assert counts.sum() == 512
    assert unclipped < -1.0 and want[2] > 1.0  # the case bites
    np.testing.assert_allclose(cost, want[2], rtol=0, atol=0.5)


@pytest.fixture
def fused_on_cpu(monkeypatch):
    """Steer the fold onto the kernel here: the gate is told the backend is
    a TPU and the kernel runs in interpret mode. The program has no option
    for this (ROADMAP D5): the test does it."""
    monkeypatch.setattr(config, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(
        pk, "lloyd_step_pallas",
        functools.partial(pk.lloyd_step_pallas, interpret=True))
    caches = (km._stream_shard_fn, km._stream_step_cached, km._stream_group_cached)
    for c in caches:
        c.cache_clear()
    yield
    for c in caches:
        c.cache_clear()


@pytest.mark.parametrize("n_valid", [8192, 5000, 4096, 2048, 100])
def test_a_prefix_mask_on_four_shards_gives_the_one_device_fold(
        fused_on_cpu, mesh1, devices, n_valid):
    """Padding at the batch's tail (`_Job._bucket`, `shard_rows`) is a prefix
    of every contiguous shard — full, part, or none of it — and the fused
    path's per-shard row count then stands for the mask."""
    n = 8192  # 2,048 rows a shard
    mesh4 = make_mesh(data=4, model=1, devices=devices[:4])
    x, start = _blobs(5, n)
    x[n_valid:] = 7.0
    mask = (np.arange(n) < n_valid).astype(np.float32)
    assert km._pallas_step_applicable(n // 4, K, D, CD, True, 4)
    f = km._stream_shard_fn(mesh4, K, CD, AD, True)
    got = [np.asarray(a) for a in f(*_zero(), jnp.asarray(start), jnp.asarray(x),
                                    jnp.asarray(mask))]
    assert got[1].sum() == n_valid
    _assert_same_stats(got, _xla_fold(mesh1, x, mask, start), n_valid)


def _path_counts():
    c = metrics.counter("srml_kmeans_fold_path_total")
    return {p: c.value(path=p) for p in ("fused", "xla")}


@pytest.mark.parametrize("d,tpu,path", [
    (256, True, "fused"),   # the gate holds
    (200, True, "xla"),     # d off the 128-lane grid
    (256, False, "xla"),    # the CPU backend, as every other test runs
])
def test_gate_and_counter(fused_on_cpu, monkeypatch, mesh1, d, tpu, path):
    """`update` and `update_group` take the kernel or the XLA body by
    platform and shape, count one dispatch each under that path, and give
    the same statistics either way."""
    if not tpu:
        monkeypatch.setattr(config, "backend_is_tpu", lambda: False)
    n = 2048
    x, start = _blobs(6, n, d=d)
    mask = (np.arange(n) < 1500).astype(np.float32)
    assert km._pallas_step_applicable(n, K, d, CD, True, 4) is (path == "fused")
    with config.option("use_pallas", True), config.option("compute_dtype", CD), \
            config.option("accum_dtype", AD):
        update = km._stream_step_fn(mesh1, K, CD, AD)
        group = km._stream_group_fn(mesh1, K, CD, AD)
    args = (jnp.asarray(start), jnp.asarray(x), jnp.asarray(mask))
    before = _path_counts()
    one = [np.asarray(a) for a in update(_zero(K, d), *args)]
    mid = _path_counts()
    two = [np.asarray(a) for a in group(_zero(K, d), args[0], (args[1],) * 2,
                                        (args[2],) * 2)]
    after = _path_counts()
    other = "xla" if path == "fused" else "fused"
    assert mid[path] - before[path] == 1 and after[path] - mid[path] == 1
    assert after[other] == before[other]
    want = _xla_fold(mesh1, x, mask, start)
    _assert_same_stats(one, want, 1500)
    _assert_same_stats([a / 2 for a in two], want, 1500)


def test_the_snapshot_of_use_pallas_keys_the_built_programs(mesh1):
    """`use_pallas` is read when the fold is built, not inside a trace: two
    settings are two cached programs under one public signature."""
    with config.option("use_pallas", False):
        off = km._stream_step_fn(mesh1, K, CD, AD)
    with config.option("use_pallas", True):
        on = km._stream_step_fn(mesh1, K, CD, AD)
        assert km._stream_step_fn(mesh1, K, CD, AD) is on
    assert on is not off
    assert on.name == off.name == "kmeans.streaming_update"
    assert km._stream_group_fn(mesh1, K, CD, AD).name == "kmeans.streaming_update_group"
    assert km._stream_group_fn(mesh1, K, CD, AD).__name__ == "update_group"


@pytest.mark.parametrize("x_itemsize,want", [(None, 16384), (2, 16384), (4, 8192)])
def test_block_reckoning_counts_a_float32_tile_at_four_bytes(x_itemsize, want):
    """d = 512, bfloat16 compute: 3,072 bytes a row with x in the compute
    dtype, 6,144 with a float32 tile and its cast copy, under 64 MiB."""
    assert km._lloyd_block_n(65536, 512, 128, 2, x_itemsize) == want
