"""The streaming Newton fold through the one-read kernel.

`models/logistic_regression.py` `_stream_grad_hess_shard_fn` reads a batch
once where the kernel's gate holds (TPU backend, float32 rows and
accumulate, whole row blocks, a width off the 128-lane grid — which the
chip keeps rows minor — whose lane-padded Hessian fits VMEM):
`newton_fold_pallas` takes the batch TRANSPOSED — as the chip keeps an
(n, 3000) float32 array — and gives logits, gradient, loss, border and row
count in float32 from each float32 tile, the Hessian from the tile cast to
bfloat16 in VMEM. Here the kernel runs in interpret mode on the CPU and is
held to the XLA body of the same shard function — the path a CPU takes and
the one the kernel replaces on the chip — and, in one test, compiled for a
described v5e at the benchmark's width.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import logistic_regression as lg
from spark_rapids_ml_tpu.ops import pallas_kernels as pk
from spark_rapids_ml_tpu.parallel.mesh import make_mesh
from spark_rapids_ml_tpu.utils import metrics

AD = "float32"  # the `auto` profile's accumulator on the chip
STATE = ("gw", "gb", "hww", "hwb", "hbb", "loss", "n")


def _batch(seed: int, n: int, d: int):
    """float32 rows, 0/1 labels, a mask with HOLES (not a prefix), a
    non-zero iterate and a non-zero running state."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = (0.1 * rng.normal(size=d)).astype(np.float32)
    b = np.float32(0.3)
    y = (rng.random(n) < 0.5).astype(np.float32)
    mask = (rng.random(n) < 0.8).astype(np.float32)
    state = tuple(
        rng.normal(size=z.shape).astype(np.float32) for z in lg.stream_zero_state(d, AD))
    return state, w, b, x, y, mask


def _args(state, w, b, x, y, mask):
    return tuple(jnp.asarray(a) for a in (*state, w, b, x, y, mask))


def _xla_fold(mesh, *batch):
    """The XLA body of the shard function: use_pallas off."""
    return [np.asarray(a) for a in lg._stream_grad_hess_shard_fn(mesh, AD, False)(*_args(*batch))]


def _assert_same_stats(got, want, state):
    """The batch's ADDITION to each leaf, against the XLA body's: the
    gradient, its intercept, the loss and n to the order of float32
    additions; the Hessian and its border to one bfloat16 product's (the
    CPU's XLA body multiplies in float32, the kernel as the chip does)."""
    for name, g, w_, s in zip(STATE, got, want, state):
        g, w_, s = (np.asarray(a, np.float64) for a in (g, w_, s))
        scale = max(np.abs(w_ - s).max(), 1e-30)
        tol = {"hww": 4e-3, "hwb": 1e-5}.get(name, 2e-6)
        assert np.abs(g - w_).max() <= tol * scale, (name, np.abs(g - w_).max() / scale)
    # rows: whole numbers added to a state that need not be one
    np.testing.assert_allclose(got[6], want[6], rtol=1e-6)


@pytest.fixture
def fused_on_cpu(monkeypatch):
    """Steer the fold onto the kernel here: the gate is told the backend is
    a TPU and the kernel runs in interpret mode. The program has no option
    for this (ROADMAP D5): the test does it."""
    monkeypatch.setattr(config, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(
        pk, "newton_fold_pallas",
        functools.partial(pk.newton_fold_pallas, interpret=True))
    caches = (lg._stream_grad_hess_shard_fn, lg._stream_grad_hess_cached,
              lg._stream_grad_hess_group_cached)
    for c in caches:
        c.cache_clear()
    yield
    for c in caches:
        c.cache_clear()


def _kernel(state, w, b, x, y, mask, **kw):
    out = pk.newton_fold_pallas(
        jnp.asarray(x).T, jnp.asarray(y), jnp.asarray(mask), jnp.asarray(w), b,
        interpret=True, **kw)
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("d,block_n", [(200, 256), (203, 128), (384, 512), (128, 1024)])
def test_kernel_matches_the_xla_body_on_float32_rows(mesh1, d, block_n):
    """Widths off the lane grid (200: whole sublane tiles past d; 203: a
    tile that straddles d) and on it; the kernel's sums are the batch's
    own, its Hessian lane-padded with zeros."""
    n = 1024
    batch = _batch(d, n, d)
    zero = tuple(np.zeros_like(s) for s in batch[0])
    want = _xla_fold(mesh1, zero, *batch[1:])
    got = _kernel(*batch, block_n=block_n)
    dp = pk._ceil_to(d, 128)
    assert got[2].shape == (dp, dp)
    assert not got[2][d:].any() and not got[2][:, d:].any()
    got[2] = got[2][:d, :d]
    _assert_same_stats(got, want, zero)
    assert got[6] == batch[5].sum() and 0 < got[6] < n  # the mask has holes


def test_kernel_seeded_adds_to_the_running_hessian_alone(mesh1):
    d, n = 200, 512
    batch = _batch(1, n, d)
    plain = _kernel(*batch, block_n=256)
    seed = np.zeros((256, 256), np.float32)
    seed[:d, :d] = batch[0][2]
    seeded = _kernel(*batch, block_n=256, hww=jnp.asarray(seed))
    np.testing.assert_allclose(seeded[2], seed + plain[2], rtol=0, atol=1e-4)
    assert not seeded[2][d:].any() and not seeded[2][:, d:].any()
    for i in (0, 1, 3, 4, 5, 6):
        np.testing.assert_array_equal(seeded[i], plain[i])


@pytest.mark.parametrize("what,kw,match", [
    ("rows", {"block_n": 384}, "not divisible"),
    ("block", {"block_n": 64}, "multiple of 128"),
    ("dtype", {}, "float32 rows"),
    ("seed", {"hww": np.zeros((200, 200), np.float32)}, "lane-padded"),
    ("budget", {}, "VMEM budget"),
])
def test_kernel_refuses_what_it_cannot_fold(what, kw, match):
    d = 4200 if what == "budget" else 200
    state, w, b, x, y, mask = _batch(2, 512, d)
    if what == "dtype":
        x = x.astype(np.float64)
    with pytest.raises(ValueError, match=match):
        _kernel(state, w, b, x, y, mask, **kw)


@pytest.mark.parametrize("d,n,want", [
    (3000, 65536, 256), (3072, 65536, 256), (200, 65536, 2048), (4096, 65536, 256),
    (3000, 1536, 256), (200, 1536, 512), (1024, 512, 512),
])
def test_row_block_from_the_padded_width_and_the_rows(d, n, want):
    """`gram_colsum_block_n`'s rule on ceil(d / 128) · 128: a float32 tile
    within 4 MiB, a power of two that divides the rows."""
    assert pk.newton_fold_block_n(d, n) == want


@pytest.mark.parametrize("d", [200, 203])
def test_fused_body_matches_the_xla_body_from_a_running_state(fused_on_cpu, mesh1, d):
    n = 1024
    batch = _batch(10 + d, n, d)
    assert lg._fused_newton_fold_applicable((n, d), np.float32, AD, True)
    got = [np.asarray(a) for a in
           lg._stream_grad_hess_shard_fn(mesh1, AD, True)(*_args(*batch))]
    assert [g.shape for g in got] == [s.shape for s in batch[0]]
    _assert_same_stats(got, _xla_fold(mesh1, *batch), batch[0])


@pytest.mark.parametrize("d", [200, 203])
def test_a_group_of_three_is_three_calls_bit_for_bit(fused_on_cpu, mesh1, d):
    """The group pads the running Hessian once and slices it once; a call
    pads and slices every time. A zero pad and its slice are exact."""
    n = 512
    batches = [_batch(20 + i, n, d) for i in range(3)]
    state, w, b = batches[0][:3]
    with config.option("use_pallas", True):
        update = lg._stream_grad_hess_fn(mesh1, AD)
        group = lg._stream_grad_hess_group_fn(mesh1, AD)
    one = tuple(jnp.asarray(s) for s in state)
    for bt in batches:
        one = update(one, jnp.asarray(w), b, *(jnp.asarray(a) for a in bt[3:]))
    xs, ys, ms = (tuple(jnp.asarray(bt[i]) for bt in batches) for i in (3, 4, 5))
    three = group(tuple(jnp.asarray(s) for s in state), jnp.asarray(w), b, xs, ys, ms)
    for name, a, c in zip(STATE, one, three):
        assert a.shape == c.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c), err_msg=name)


@pytest.mark.parametrize("d", [200, 203])
def test_four_shards_give_the_one_device_fold(fused_on_cpu, mesh1, devices, d):
    """On four data devices the kernel is unseeded and the Hessian meets
    the state after the psum; the mask's holes fall in every shard."""
    n = 2048  # 512 rows a shard
    mesh4 = make_mesh(data=4, model=1, devices=devices[:4])
    batch = _batch(30 + d, n, d)
    assert lg._fused_newton_fold_applicable((n // 4, d), np.float32, AD, True)
    got = [np.asarray(a) for a in
           lg._stream_grad_hess_shard_fn(mesh4, AD, True)(*_args(*batch))]
    _assert_same_stats(got, _xla_fold(mesh1, *batch), batch[0])


def _path_counts():
    c = metrics.counter("srml_logreg_fold_path_total")
    return {p: c.value(path=p) for p in ("fused", "xla")}


@pytest.mark.parametrize("tpu,d,n,dtype,path", [
    (True, 200, 512, np.float32, "fused"),   # the gate holds: 200 < 256, rows minor on the chip
    (True, 203, 1024, np.float32, "fused"),  # 208 < 256
    (True, 384, 1024, np.float32, "xla"),    # on the lane grid: row-major on the chip
    (True, 1020, 512, np.float32, "xla"),    # 1024 = 1024: a tie, row-major
    (False, 200, 512, np.float32, "xla"),    # the CPU backend, as every other test runs
    (True, 4200, 512, np.float32, "xla"),    # (4224, 4224) float32 is over 64 MiB
    (True, 200, 500, np.float32, "xla"),     # rows no multiple of 512
    (True, 200, 512, np.float64, "xla"),     # rows not float32
])
def test_gate_and_counter(fused_on_cpu, monkeypatch, mesh1, tpu, d, n, dtype, path):
    """`update` and `update_group` take the kernel or the XLA body by
    platform, shape and dtype, count one dispatch each under that path,
    and give the same statistics either way."""
    if not tpu:
        monkeypatch.setattr(config, "backend_is_tpu", lambda: False)
    assert lg._fused_newton_fold_applicable((n, d), dtype, AD, True) is (path == "fused")
    assert not lg._fused_newton_fold_applicable((n, d), dtype, "float64", True)
    assert not lg._fused_newton_fold_applicable((n, d), dtype, AD, False)
    if d > 4096:
        return  # the gate's answer is the test: no (4200, 4200) fold on this CPU
    state, w, b, x, y, mask = _batch(40, n, d)
    x = x.astype(dtype)
    with config.option("use_pallas", True):
        update = lg._stream_grad_hess_fn(mesh1, AD)
        group = lg._stream_grad_hess_group_fn(mesh1, AD)
    it = (jnp.asarray(w), b)
    cols = tuple(jnp.asarray(a) for a in (x, y, mask))
    before = _path_counts()
    one = update(tuple(jnp.asarray(s) for s in state), *it, *cols)
    mid = _path_counts()
    two = group(tuple(jnp.asarray(s) for s in state), *it,
                *((c,) * 2 for c in cols))
    after = _path_counts()
    other = "xla" if path == "fused" else "fused"
    assert mid[path] - before[path] == 1 and after[path] - mid[path] == 1
    assert after[other] == before[other]
    want = _xla_fold(mesh1, state, w, b, x.astype(np.float32), y, mask)
    _assert_same_stats([np.asarray(a) for a in one], want, state)
    twice = [2 * wa - s for wa, s in zip(want, state)]
    _assert_same_stats([np.asarray(a) for a in two], twice, state)


def test_the_snapshot_of_use_pallas_keys_the_built_programs(mesh1):
    """`use_pallas` is read when the fold is built, not inside a trace: two
    settings are two cached programs under one public signature."""
    with config.option("use_pallas", False):
        off = lg._stream_grad_hess_fn(mesh1, AD)
        goff = lg._stream_grad_hess_group_fn(mesh1, AD)
    with config.option("use_pallas", True):
        on = lg._stream_grad_hess_fn(mesh1, AD)
        gon = lg._stream_grad_hess_group_fn(mesh1, AD)
        assert lg._stream_grad_hess_fn(mesh1, AD) is on
        assert lg._stream_grad_hess_group_fn(mesh1, AD) is gon
    assert on is not off and gon is not goff
    assert on.name == off.name == "logreg.streaming_update"
    assert gon.name == goff.name == "logreg.streaming_update_group"
    assert gon.__name__ == "update_group"  # the configuration's `fold_program`: jit_update_group


# ---------------------------------------------------------------------------
# Compiled for a described v5e, at the benchmark's width (no chip attached)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A v5e the TPU compiler describes without one attached; its library is
    loaded inside the test's own process, here and nowhere at import."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_cached_batch_is_read_as_it_lies_at_d_3000(one_chip):
    """The chip keeps a (65536, 3000) float32 batch with its rows minor, so
    the kernel's `x.T` is a bitcast: the program compiled for a v5e holds
    no copy of the batch — no temporary at all beside the donated Hessian —
    and the Mosaic kernel is in it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    n, d, dp = 65536, 3000, 3072

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fold(x, y, mask, w, b, hww):
        return pk.newton_fold_pallas(x.T, y, mask, w, b, hww=hww)

    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # the chip's programs are built without x64 (conftest turns it on
        # for the float64 parity profile; Mosaic takes no int64 index)
        with jax.enable_x64(False):
            compiled = jax.jit(fold, donate_argnums=(5,)).lower(
                s(n, d), s(n), s(n), s(d), s(), s(dp, dp)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.alias_size_in_bytes == 4 * dp * dp
    # 786,432,000 bytes of rows: the unpadded batch, not 805 MB of padded lanes
    assert mem.argument_size_in_bytes < 4 * n * d + 4 * dp * dp + 2**20
    assert "tpu_custom_call" in compiled.as_text()
