"""The forest's histogram fold through the kernel that makes the bin one-hot
in VMEM.

`ops/histogram.py` `hist_update_group_fn` contracts a chunk's int8 operand
with `hist_onehot_matmul_pallas` where `_fused_hist_fold_applicable` holds
(TPU backend, bfloat16 compute under float32 accumulate, a bin count on the
128-lane grid, chunk rows in multiples of 512) and with `jax.nn.one_hot` and
XLA's product everywhere else. Here the kernel runs in interpret mode on the
CPU and is held to the XLA body of the same fold — the path a CPU takes and
the one the kernel replaces on the chip: int32 products are the same whole
numbers, so the count channel is equal cell for cell; the fused body walks a
batch in one chunk where the XLA body walks it in several, so a label
statistic's digits carry one scale a batch, not one a chunk, and agree to the
accumulator's own rounding. One test compiles the kernel for a described v5e
at the benchmark's deepest shape.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import random_forest as rf
from spark_rapids_ml_tpu.ops import histogram as hist_ops
from spark_rapids_ml_tpu.ops import pallas_kernels as pk
from spark_rapids_ml_tpu.utils import metrics

D = 11  # off the 8-feature tile: the last feature block reaches back
BINS = 128
REG = {"num_trees": 3, "max_depth": 3, "max_bins": BINS, "n_classes": 0, "seed": 2}
CLF = {"num_trees": 2, "max_depth": 3, "max_bins": BINS, "n_classes": 3, "seed": 7}


@pytest.fixture
def fused_on_cpu(monkeypatch):
    """Steer the fold onto the kernel here: the gate is told the backend is
    a TPU and the kernel runs in interpret mode. The program has no option
    for this (ROADMAP D5): the test does it."""
    monkeypatch.setattr(config, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(
        pk, "hist_onehot_matmul_pallas",
        functools.partial(pk.hist_onehot_matmul_pallas, interpret=True))
    hist_ops.hist_update_group_fn.cache_clear()
    yield
    hist_ops.hist_update_group_fn.cache_clear()


@pytest.fixture
def chip_profile():
    """bfloat16 compute under a float32 accumulator: the chip's `auto`."""
    with config.option("accum_dtype", "float32"), config.option(
            "compute_dtype", "bfloat16"):
        yield


def _rows(seed, n, classes=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, D)).astype(np.float32)
    if classes:
        y = (np.abs(x[:, 0] + x[:, 1]) * 2 // 1 % classes).astype(np.float64)
    else:
        y = x @ rng.normal(size=D) * 50.0
    return x, y


def _path_counts():
    c = metrics.counter("srml_forest_fold_path_total")
    return {p: c.value(path=p) for p in ("fused", "xla")}


def _level(params, x, y, mesh, depth, use_pallas):
    """The in-memory fit's passes up to `depth` under one body of the fold:
    (every closed level's complete histogram, the halved fold of the last
    level — seeded from its parent — where depth >= 1)."""
    spec = rf.forest_spec_from_params(params, D)
    tables = rf.init_forest_arrays(
        spec, hist_ops.quantile_bin_edges(x, spec.max_bins))
    keys = rf.row_identity_keys(None, 0, len(x))
    placed = rf._place_batch(x, y, np.ones(len(x), np.float32), keys, mesh)
    run = tuple((a,) for a in placed)
    whole, halved = [], None
    with config.option("use_pallas", use_pallas):
        for level in range(depth + 1):
            state, _ = rf.open_pass(tables, spec, D)
            hist = rf.accumulate_histogram(
                state, tables, *run, spec, mesh, n_valid=len(x))
            if level:
                state, signs = rf.open_pass(tables, spec, D, parent=parent)
                assert signs is not None
                halved = np.asarray(rf.accumulate_histogram(
                    state, tables, *run, spec, mesh, n_valid=len(x), signs=signs))
            whole.append(np.asarray(hist))
            parent = jnp.asarray(whole[-1])
            if level < depth:
                rf.grow_level(tables, hist, spec)
    return whole, halved


@pytest.mark.parametrize("tpu,compute,bins,rows,fused", [
    (True, "bfloat16", 128, 65536, True),    # the cell's shape on the chip
    (True, "bfloat16", 256, 512, True),      # two lane tiles of bins, one row tile
    (True, "bfloat16", 128, 98304, True),    # longer than a chunk: 65,536-row chunks
    (False, "bfloat16", 128, 65536, False),  # the CPU backend, as every other test runs
    (True, "float32", 128, 65536, False),    # float32 compute: XLA's product in float32
    (True, "bfloat16", 32, 65536, False),    # Spark's default maxBins: off the lane grid
    (True, "bfloat16", 128, 500, False),     # chunk rows no multiple of 512
    (True, "bfloat16", 128, 0, False),
])
def test_the_gate_by_platform_dtypes_and_shapes(monkeypatch, tpu, compute, bins, rows, fused):
    monkeypatch.setattr(config, "backend_is_tpu", lambda: tpu)
    narrow = jnp.finfo(compute).nmant < jnp.finfo("float32").nmant
    operand = jnp.int8 if narrow else jnp.dtype(compute)
    assert hist_ops._fused_hist_fold_applicable(rows, operand, bins, True) is fused
    assert not hist_ops._fused_hist_fold_applicable(rows, operand, bins, False)
    with config.option("use_pallas", False):
        assert not hist_ops._fused_hist_fold_applicable(rows, operand, bins)


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_a_whole_and_a_halved_level_through_both_bodies(
        fused_on_cpu, chip_profile, devices, params, n_dev):
    """Depths 0-2 folded whole and depths 1-2 by halves, on one device (the
    batch joins the donated frontier tensor) and on four (per-shard
    partials under `map_fn`): count channels equal cell for cell, label
    channels within 1e-6 of the level's largest cell; and one dispatch
    counted under each body's path."""
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=n_dev, model=1, devices=devices[:n_dev])
    x, y = _rows(5, n=512 * n_dev, classes=params["n_classes"])
    before = _path_counts()
    fused, fused_half = _level(params, x, y, mesh, 2, use_pallas=True)
    mid = _path_counts()
    xla, xla_half = _level(params, x, y, mesh, 2, use_pallas=False)
    after = _path_counts()
    assert mid["fused"] - before["fused"] == 5 and mid["xla"] == before["xla"]
    assert after["xla"] - mid["xla"] == 5 and after["fused"] == mid["fused"]
    counts = slice(None) if params["n_classes"] else slice(0, 1)
    for got, want in zip(fused + [fused_half], xla + [xla_half]):
        assert got.shape == want.shape and want[..., 0].sum() > 0
        np.testing.assert_array_equal(got[..., counts], want[..., counts])
        scale = np.abs(want).max(axis=(0, 1, 2, 3))
        assert np.all(np.abs(got - want).max(axis=(0, 1, 2, 3)) <= 1e-6 * scale)
    # a halved level brought to the whole frontier's IS the level folded whole
    np.testing.assert_array_equal(fused_half[..., counts], fused[2][..., counts])


def test_a_batch_longer_than_a_chunk_and_a_ragged_one(fused_on_cpu, chip_profile, mesh1,
                                                       monkeypatch):
    """The fused body walks a shard in `_FUSED_CHUNK_ROWS` chunks, the tail
    padded with masked rows (1,280 rows: three chunks of 512); a shard
    shorter than a chunk that is no whole row tiles takes the XLA body —
    all give the XLA body's counts."""
    monkeypatch.setattr(hist_ops, "_FUSED_CHUNK_ROWS", 512)
    hist_ops.hist_update_group_fn.cache_clear()
    for n, path in ((1536, "fused"), (1280, "fused"), (300, "xla")):
        x, y = _rows(n, n=n)
        before = _path_counts()
        got, _ = _level(REG, x, y, mesh1, 1, use_pallas=True)
        assert _path_counts()[path] - before[path] == 3  # depth 0; depth 1 whole, halved
        want, _ = _level(REG, x, y, mesh1, 1, use_pallas=False)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[..., 0], w[..., 0])
            assert np.abs(g - w).max() <= 1e-6 * np.abs(w).max()


def test_use_pallas_keys_the_built_programs(mesh1):
    """The caller's snapshot of `use_pallas` is an argument of the cached
    builder: two settings are two programs under one ledger name and one
    trace name (the configuration's `fold_program`)."""
    build = functools.partial(
        hist_ops.hist_update_group_fn, mesh1, 3, BINS, 1, 0, True, 2, "float32", "bfloat16")
    on, off = build(use_pallas=True), build(use_pallas=False)
    assert on is not off and build(use_pallas=True) is on and build(use_pallas=False) is off
    assert on.name == off.name == "histogram.update_group"
    assert on.__name__ == off.__name__ == "hist_update_group"


# ---------------------------------------------------------------------------
# Compiled for a described v5e, at the benchmark's shapes (no chip attached)
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


@pytest.mark.parametrize("m", [210, 3360])
def test_the_kernel_compiles_for_a_v5e_at_the_cells_heights(one_chip, m):
    """The root's 210 rows of left operand (padded to int8's 32-row tile)
    and the halved depth 5's 3,360 against a 65,536-row chunk's bin ids:
    Mosaic takes int8 x int8 into int32 with both operands contracting
    their lanes, the product is the program's only output and nothing the
    size of a one-hot (4.6 MB a row of the block) is in it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    c, db = 65536, 16

    def product(lhs, bins_t):
        return pk.hist_onehot_matmul_pallas(lhs, bins_t, n_bins=BINS)

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            compiled = jax.jit(product).lower(
                jax.ShapeDtypeStruct((m, c), jnp.int8, sharding=one_chip),
                jax.ShapeDtypeStruct((db, c), jnp.int32, sharding=one_chip),
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    # the product, in whole 8-row tiles of the chip's layout
    assert 4 * m * db * BINS <= mem.output_size_in_bytes < 4 * (m + 8) * db * BINS
    # the padded operand and the padded product at most: no one-hot
    assert mem.temp_size_in_bytes <= (m + 32) * (c + 4 * db * BINS)
