"""The pass cache for the forest job (ISSUE 36; docs/protocol.md "rescan"):
a forest job keeps the batches its fold placed on the device — rows, mask,
the label column AND the bag keys — and every depth after the first is
folded from there, a run of batches a program.

The invariant is `tests/test_pass_cache.py`'s: **the cache changes the
transport of a pass, never its result.** The labels here are whole numbers,
so every statistic is exact in the accumulation dtype and the order of the
accumulator's additions cannot show: a cached fit is the re-fed fit and the
single-daemon oracle bit for bit (tests/test_forest.py's convention).

The fold walks a batch in row chunks inside its program and the scorer goes
tree by tree (ops/histogram.py); what they replaced — the whole batch in one
contraction, the whole frontier scored at once — stands here as the oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import random_forest as rf
from spark_rapids_ml_tpu.models.jobs import job_algorithm
from spark_rapids_ml_tpu.ops import histogram as hist_ops
from spark_rapids_ml_tpu.ops.histogram import OPEN
from spark_rapids_ml_tpu.serve import DataPlaneDaemon, protocol
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils import xprof

D = 6
REG = {"num_trees": 5, "max_depth": 3, "max_bins": 16, "n_classes": 0, "seed": 2}
CLF = {"num_trees": 4, "max_depth": 3, "max_bins": 16, "n_classes": 3, "seed": 7}
# 5 batches, the last ragged: 300 rows pad to 512, 117 to 128
BATCHES = [(0, 300), (300, 600), (600, 900), (900, 1200), (1200, 1317)]
ROWS = BATCHES[-1][1]


def _job(mesh, cache_mb, params, d=D):
    with config.option("daemon_pass_cache_mb", cache_mb):
        return _Job("rf", d, mesh, params)


def _rows(seed, n=ROWS, d=D, classes=0):
    """Whole-number rows and labels: every histogram statistic is exact."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-20, 21, size=(n, d)).astype(np.float32)
    if classes:
        y = (np.abs(x[:, 0] + x[:, 1]) // 7 % classes).astype(np.float64)
    else:
        y = (x @ rng.integers(-3, 4, size=d)).astype(np.float64)
    return x, y


def _start(params, x, d=D):
    spec = rf.forest_spec_from_params(params, d)
    return rf.init_forest_arrays(spec, hist_ops.quantile_bin_edges(x, spec.max_bins))


def _hist(job):
    return np.asarray(jax.device_get(job.peek_pass_state()[0]))


def _counter(name, **labels):
    return sum(
        s["value"] for s in (metrics_mod.snapshot().get(name) or {}).get("samples", [])
        if all(s["labels"].get(k) == v for k, v in labels.items()))


def _phase_count(phase):
    return sum(
        s["count"] for s in (metrics_mod.snapshot().get(
            "srml_phase_duration_seconds") or {}).get("samples", [])
        if s["labels"].get("phase") == phase)


def test_the_forest_job_may_keep_its_pass_whatever_its_params(mesh8):
    cls = job_algorithm("rf")
    assert cls.cacheable and cls.boundary_span == "forest.boundary"
    assert cls.cacheable_for(REG) and cls.cacheable_for(CLF) and cls.cacheable_for({})
    assert _job(mesh8, 16, REG)._cache_budget == 16 << 20
    assert _job(mesh8, 0, REG)._cache_budget == 0


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
def test_a_cached_forest_fit_is_bit_equal_to_the_refed_fit_of_direct_feeds(mesh8, params):
    x, y = _rows(3, classes=params["n_classes"])
    fed, cached = _job(mesh8, 0, params), _job(mesh8, 16, params)
    for job in (fed, cached):
        job.set_iterate(_start(params, x), 0)
    for it in range(params["max_depth"] + 1):
        for lo, hi in BATCHES:
            fed.fold(x[lo:hi], y[lo:hi], pass_id=it)
        if it == 0:
            for lo, hi in BATCHES:
                cached.fold(x[lo:hi], y[lo:hi], pass_id=it)
            assert cached.cache_ack() == {"cached": True, "cached_rows": ROWS}
            # the cached batch is what the fold placed: rows, mask, labels, bag keys
            assert [len(b) for b in cached._cache.batches] == [4] * 5
            xs, ms, ys, ks = cached._cache.batches[4]
            assert (xs.shape, ms.shape, ys.shape, ks.shape) == ((128, D), (128,), (128,), (128,))
            np.testing.assert_array_equal(np.asarray(ys)[:117], y[1200:])
            np.testing.assert_array_equal(
                np.asarray(ks)[:117], rf.row_identity_keys(None, 1200, 117))
            assert np.asarray(ms).sum() == 117
        else:
            assert cached.rescan(it) == {
                "pass_rows": ROWS, "cached_rows": ROWS, "cached_batches": 5}
        np.testing.assert_array_equal(_hist(fed), _hist(cached))
        info = cached.step({})
        assert fed.step({}) == info
        if info["open_nodes"] == 0:
            break
    assert it >= 2 and fed.cache_ack() == {} and fed.pass_cache_bytes == 0
    got, want = cached.get_iterate()[0], fed.get_iterate()[0]
    assert int(got["depth"][0]) == it + 1
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
def test_a_cached_partitioned_forest_fit_is_the_in_memory_oracle(mesh8, params):
    """One partition, so that the daemon's (partition, offset) keys are the
    in-memory fit's: the cached daemon fit equals `fit_random_forest_*`."""
    x, y = _rows(5, classes=params["n_classes"])
    job = _job(mesh8, 16, params)
    with config.option("forest_seed_sample_rows", ROWS):
        if params["n_classes"]:
            want = rf.fit_random_forest_classifier(
                x, y, n_classes=3, num_trees=4, max_depth=3, max_bins=16, seed=7, mesh=mesh8)
        else:
            want = rf.fit_random_forest_regressor(
                x, y, num_trees=5, max_depth=3, max_bins=16, seed=2, mesh=mesh8)
    job.set_iterate(_start(params, x), 0)
    for lo, hi in BATCHES:
        job.fold(x[lo:hi], y[lo:hi], partition=0, pass_id=0)
    job.commit(0, pass_id=0)
    it = 0
    while job.step({})["open_nodes"]:
        it += 1
        assert job.rescan(it)["pass_rows"] == ROWS
    assert it >= 2
    got = job.finalize({})
    for key in ("feature", "threshold", "value", "bin_edges"):
        np.testing.assert_array_equal(got[key], want.arrays[key], err_msg=key)


def test_a_rescan_folds_runs_of_eight_batches_in_one_ledgered_program_and_books_true_rows(
        mesh8):
    x, y = _rows(19, 19 * 60)
    fed, cached = _job(mesh8, 0, REG), _job(mesh8, 16, REG)
    for job in (fed, cached):
        job.set_iterate(_start(REG, x), 0)
        for i in range(19):
            job.fold(x[i * 60:(i + 1) * 60], y[i * 60:(i + 1) * 60], pass_id=0)
        job.step({})
    for i in range(19):
        fed.fold(x[i * 60:(i + 1) * 60], y[i * 60:(i + 1) * 60], pass_id=1)
    name = "histogram.update_group"
    calls = xprof.snapshot()[name]["calls"]
    rows = _counter("srml_forest_hist_rows_total", role="regressor")
    cached.rescan(1)
    assert xprof.snapshot()[name]["calls"] - calls == 3  # 8 + 8 + 3 batches
    # the true rows, not the padded 64 a batch: what the masks count
    assert _counter("srml_forest_hist_rows_total", role="regressor") - rows == 19 * 60
    np.testing.assert_array_equal(_hist(fed), _hist(cached))


def test_fold_group_over_a_run_is_fold_batch_by_batch(mesh8):
    """Real-valued labels: the run's program adds in the calls' order, so
    even sums that round are the same bits."""
    x, _ = _rows(23)
    y = np.random.default_rng(23).normal(size=ROWS)
    job = _job(mesh8, 16, REG)
    job.set_iterate(_start(REG, x), 0)
    for lo, hi in BATCHES[:4]:
        job.fold(x[lo:hi], y[lo:hi], pass_id=0)
    one_by_one = _hist(job)
    algo = job.algorithm
    xs, ms, ys, ks = zip(*job._cache.batches)
    grouped = np.asarray(algo.fold_group(algo.zero_state(), xs, ms, (ys, ks)))
    np.testing.assert_array_equal(grouped, one_by_one)
    assert grouped[..., 0].sum() > 0 and np.abs(grouped[..., 1]).sum() > 0


# ---- what the chunked fold and the tree-by-tree scorer replaced, as oracles ----


def _whole_batch_histogram(edges, feature, threshold, x, y, mask, keys, spec, depth):
    """The fold as it stood before ISSUE 36: the whole batch in one
    contraction (`sb` is rows x d x B x S — 4.6 MB a row at the suite's width)."""
    W = 1 << depth
    bins = hist_ops.bin_matrix(jnp.asarray(x, edges.dtype), edges)
    idx, alive = hist_ops.descend_to_frontier(bins, feature, threshold, depth)
    node_f = jnp.take_along_axis(feature, idx, axis=1)
    w = (alive & (node_f == OPEN) & (jnp.asarray(mask) > 0)[None, :]).astype(edges.dtype)
    w = w * hist_ops.bootstrap_weights(keys, spec.num_trees, spec.seed).astype(edges.dtype)
    pos = jnp.clip(idx - (W - 1), 0, W - 1)
    node_oh = jax.nn.one_hot(pos, W, dtype=edges.dtype) * w[:, :, None]
    bin_oh = jax.nn.one_hot(bins, spec.max_bins, dtype=edges.dtype)
    if spec.n_classes:
        stat = jax.nn.one_hot(jnp.asarray(y, jnp.int32), spec.n_classes, dtype=edges.dtype)
    else:
        ya = jnp.asarray(y, edges.dtype)
        stat = jnp.stack([jnp.ones_like(ya), ya, ya * ya], axis=1)
    sb = bin_oh[:, :, :, None] * stat[:, None, None, :]
    return jnp.einsum("tnw,ndbs->twdbs", node_oh, sb)


def _whole_frontier_scorer(hist, spec, depth):
    """`best_splits_fn`'s scorer as it stood before ISSUE 36: every tree at
    once, each intermediate the size of the frontier tensor."""
    T, W, d, B, S = hist.shape
    cum = jnp.cumsum(hist, axis=3)
    tot = cum[:, :, 0, B - 1, :]
    left = cum[:, :, :, : B - 1, :]
    right = tot[:, :, None, None, :] - left
    if spec.n_classes > 0:
        n_l, n_r = jnp.sum(left, axis=-1), jnp.sum(right, axis=-1)
        g_l, g_r = jnp.sum(left * left, axis=-1), jnp.sum(right * right, axis=-1)
        g_t, n_t = jnp.sum(tot * tot, axis=-1), jnp.sum(tot, axis=-1)
    else:
        n_l, n_r = left[..., 0], right[..., 0]
        g_l, g_r = left[..., 1] * left[..., 1], right[..., 1] * right[..., 1]
        g_t, n_t = tot[..., 1] * tot[..., 1], tot[..., 0]
    score = g_l / jnp.maximum(n_l, 1) + g_r / jnp.maximum(n_r, 1)
    score = score - (g_t / jnp.maximum(n_t, 1))[:, :, None, None]
    mi = jnp.asarray(float(spec.min_instances), hist.dtype)
    mask = hist_ops.feature_subset_mask(T, W, depth, d, spec.subset_m, spec.seed)
    score = jnp.where((n_l >= mi) & (n_r >= mi) & mask[:, :, :, None], score, -jnp.inf)
    flat = score.reshape(T, W, d * (B - 1))
    best = jnp.argmax(flat, axis=-1)
    best_score = jnp.take_along_axis(flat, best[:, :, None], -1)[..., 0]
    bf, bb = (best // (B - 1)).astype(jnp.int32), (best % (B - 1)).astype(jnp.int32)

    def pick(a):
        return jnp.take_along_axis(
            jnp.take_along_axis(a, bf[:, :, None, None, None], axis=2),
            bb[:, :, None, None, None], axis=3)[:, :, 0, 0, :]

    return best_score, bf, bb, pick(left), pick(right), tot


def _grown(params, x, y, mesh, depth):
    """(spec, tables at `depth`, the placed batch) of an in-memory fit cut short."""
    spec = rf.forest_spec_from_params(params, x.shape[1])
    tables = _start(params, x, x.shape[1])
    keys = rf.row_identity_keys(None, 0, len(x))
    placed = rf._place_batch(x, y, np.ones(len(x), np.float32), keys, mesh)
    for _ in range(depth):
        hist = hist_ops.zero_hist(
            spec.num_trees, int(tables["depth"][0]), x.shape[1], spec.max_bins,
            spec.n_stats, config.get("accum_dtype"))
        xs, ys, ms, ks = placed
        hist = rf.accumulate_histogram(
            hist, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh, n_valid=len(x))
        rf.grow_level(tables, hist, spec)
    return spec, tables, placed, keys


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
@pytest.mark.parametrize("chunk,frontier", [
    (64, "small"), (96, "small"), (4096, "small"), (64, "large"), (96, "large")])
def test_the_chunked_fold_equals_the_whole_batch_contraction(
        mesh8, monkeypatch, params, chunk, frontier):
    """Chunks of 64 (whole), of 96 (a ragged tail in every shard) and one
    chunk a shard, through both of the fold's regimes — a small frontier
    (short chunks, the one-hot generated inside the contraction) and a
    large one (long chunks, the one-hot written out first): count channel
    and whole-number sums bit-equal to the contraction over the whole
    batch; with real labels, to the order of a float64 accumulator's
    additions."""
    if frontier == "small":
        monkeypatch.setattr(hist_ops, "_SHORT_CHUNK_ROWS", chunk)
    else:
        monkeypatch.setattr(hist_ops, "FOLD_CHUNK_ROWS", chunk)
        monkeypatch.setattr(hist_ops, "_SMALL_FRONTIER_BYTES", 0)
        monkeypatch.setattr(hist_ops, "_MATERIALIZE_FROM_ROWS", 0)
    hist_ops.hist_update_group_fn.cache_clear()
    try:
        x, y = _rows(29, n=1600, classes=params["n_classes"])
        spec, tables, placed, keys = _grown(params, x, y, mesh8, depth=2)
        accum = jnp.dtype(config.get("accum_dtype"))
        args = (jnp.asarray(tables["bin_edges"], accum), jnp.asarray(tables["feature"]),
                jnp.asarray(tables["threshold"]))
        want = np.asarray(_whole_batch_histogram(
            *args, x, y, np.ones(len(x)), keys, spec, depth=2))
        xs, ys, ms, ks = placed
        zero = hist_ops.zero_hist(spec.num_trees, 2, D, spec.max_bins, spec.n_stats, accum)
        got = np.asarray(rf.accumulate_histogram(
            zero, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=len(x)))
        assert got.shape == want.shape and want[..., 0].sum() > 0
        np.testing.assert_array_equal(got, want)
        if not params["n_classes"]:
            real = np.random.default_rng(31).normal(size=len(x))
            want = np.asarray(_whole_batch_histogram(
                *args, x, real, np.ones(len(x)), keys, spec, depth=2))
            zero = hist_ops.zero_hist(spec.num_trees, 2, D, spec.max_bins, 3, accum)
            ys = rf._place_batch(x, real, np.ones(len(x), np.float32), keys, mesh8)[1]
            got = np.asarray(rf.accumulate_histogram(
                zero, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=len(x)))
            np.testing.assert_array_equal(got[..., 0], want[..., 0])  # counts: exact
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-12 * np.abs(want).max())
    finally:
        hist_ops.hist_update_group_fn.cache_clear()


def test_label_statistics_travel_as_whole_number_digits_where_the_compute_dtype_is_narrow(
        mesh8):
    """bfloat16 compute under a float32 accumulator (the chip's profile):
    the contraction's operands are int8 — three balanced base-256 digits of
    a chunk's terms scaled by a power of two — so the fold is the float32
    fold to the accumulator's own rounding, not one of labels rounded to 8
    bits; whole-number labels travel exactly."""
    x, _ = _rows(37, n=800)
    real = np.random.default_rng(37).normal(size=800) * 100.0
    whole = np.round(real / 10.0)  # sums of squares stay under 2^24: exact in float32
    spec, tables, placed, keys = _grown(REG, x, real, mesh8, depth=1)
    xs, _, ms, ks = placed
    for y, exact in ((real, False), (whole, True)):
        ys = rf._place_batch(x, y, np.ones(800, np.float32), keys, mesh8)[1]
        out = {}
        for compute in ("float32", "bfloat16"):
            with config.option("accum_dtype", "float32"), config.option(
                    "compute_dtype", compute):
                zero = hist_ops.zero_hist(spec.num_trees, 1, D, spec.max_bins, 3, "float32")
                out[compute] = np.asarray(rf.accumulate_histogram(
                    zero, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=800))
        np.testing.assert_array_equal(out["bfloat16"][..., 0], out["float32"][..., 0])
        if exact:
            np.testing.assert_array_equal(out["bfloat16"], out["float32"])
        else:
            scale = np.abs(out["float32"]).max(axis=(0, 1, 2, 3))
            err = np.abs(out["bfloat16"] - out["float32"]).max(axis=(0, 1, 2, 3))
            assert np.all(err <= 2e-6 * scale), (err, scale)  # labels in bfloat16: ~4e-3
    parts, unit = hist_ops._digits(jnp.asarray(real, jnp.float32))
    back = sum(np.asarray(p, np.float64) * 256.0 ** k for k, p in enumerate(parts)) * float(unit)
    assert all(p.dtype == jnp.int8 for p in parts) and len(parts) == 3
    assert np.abs(back - real.astype(np.float32)).max() <= 2.0 ** -22 * np.abs(real).max()


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("whole_up_to", [0, 1 << 30], ids=["tree_by_tree", "small_frontier"])
def test_the_tree_by_tree_scorer_equals_the_whole_frontier_scorer_on_every_output(
        mesh8, monkeypatch, params, depth, whole_up_to):
    """Both of the scorer's ways through a frontier — a tree at a time (a
    wide or deep one) and all trees at once (a small one) — against the
    scorer as it stood."""
    monkeypatch.setattr(hist_ops, "_SCORE_BLOCK_BYTES", whole_up_to)
    hist_ops.best_splits_fn.cache_clear()
    params = dict(params, subset="onethird")
    x, y = _rows(41, n=900, classes=params["n_classes"])
    spec, tables, placed, _ = _grown(params, x, y, mesh8, depth)
    xs, ys, ms, ks = placed
    hist = rf.accumulate_histogram(
        hist_ops.zero_hist(spec.num_trees, depth, D, spec.max_bins, spec.n_stats,
                           config.get("accum_dtype")),
        tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=900)
    scorer = hist_ops.best_splits_fn(
        spec.num_trees, depth, spec.n_classes, spec.subset_m, spec.seed,
        spec.min_instances, config.get("accum_dtype"))
    got = [np.asarray(a) for a in scorer(hist, hist_ops.feature_subset_mask(
        spec.num_trees, 1 << depth, depth, D, spec.subset_m, spec.seed))]
    want = [np.asarray(a) for a in _whole_frontier_scorer(hist, spec, depth)]
    assert spec.subset_m == 2 and np.isfinite(want[0]).any()
    for name, a, b in zip(("score", "feature", "bin", "left", "right", "total"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    hist_ops.best_splits_fn.cache_clear()


def test_the_boundary_and_the_scorer_are_spans_and_the_gate_still_refuses_at_the_boundary(
        mesh8):
    x, y = _rows(43)
    job = _job(mesh8, 16, REG)
    job.set_iterate(_start(REG, x), 0)
    before = {p: _phase_count(p) for p in ("forest.boundary", "forest.score")}
    job.fold(x, y, pass_id=0)
    info = job.step({})
    assert list(info) == ["iteration", "depth", "open_nodes", "splits", "pass_rows"]
    for phase, count in before.items():
        assert _phase_count(phase) == count + 1, phase
    # 100 trees x 6 x 256 x 3 float64: 3.5 MiB at depth 0, 7 at depth 1. A
    # budget between them is refused where the next frontier would be
    # allocated — the step — and not in the middle of the pass after it
    big = dict(REG, num_trees=100, max_bins=256)
    with config.option("forest_hist_budget_mb", 4):
        gated = _job(mesh8, 16, big)
        gated.set_iterate(_start(big, x), 0)
        gated.fold(x, y, pass_id=0)
        with pytest.raises(rf.ForestCapacityError, match="depth-1 frontier histogram"):
            gated.step({})
        with pytest.raises(rf.ForestCapacityError, match="depth-0"):
            _job(mesh8, 16, dict(big, num_trees=200))


def test_a_restored_forest_job_has_no_cached_pass(mesh8):
    x, y = _rows(47)
    job = _job(mesh8, 16, REG)
    job.set_iterate(_start(REG, x), 0)
    job.fold(x, y, partition=0, pass_id=0)
    job.commit(0, pass_id=0)
    with pytest.raises(protocol.NoCachedPass, match="still open"):
        job.rescan(0)
    job.step({})
    restored = _job(mesh8, 16, REG)
    restored.set_iterate(job.durable_arrays(), 1)
    with pytest.raises(protocol.NoCachedPass, match="keeps none"):
        restored.rescan(1)
    restored.fold(x, y, partition=0, pass_id=1)
    restored.commit(0, pass_id=1)
    assert job.rescan(1)["pass_rows"] == ROWS
    np.testing.assert_array_equal(_hist(job), _hist(restored))


# ---------------- through a real daemon, client and spark/estimator.py -------


def _fit(x, y, classes=0):
    from sparksim import simdf_from_numpy
    from spark_rapids_ml_tpu.spark.estimator import (
        SparkRandomForestClassifier,
        SparkRandomForestRegressor,
    )

    df = simdf_from_numpy(x, n_partitions=3, label=y, concurrency=1)
    est = SparkRandomForestClassifier() if classes else SparkRandomForestRegressor()
    return est.setNumTrees(5).setMaxDepth(3).setMaxBins(16).setSeed(2).fit(df)


@pytest.mark.parametrize("classes", [0, 3], ids=["regressor", "classifier"])
def test_spark_forest_rows_cross_the_wire_once_and_the_model_is_the_key_off_model(
        mesh8, monkeypatch, classes):
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    x, y = _rows(53, n=900, classes=classes)
    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
        metrics_mod.reset()
        off = _fit(x, y, classes)
        assert _counter("srml_daemon_pass_rows_total") == 0
        assert _counter("srml_daemon_requests_total", op="rescan") == 0

        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            on = _fit(x, y, classes)
    depths = int(_counter("srml_daemon_passes_total"))
    assert depths >= 3
    # rows crossed the wire at depth 0 only; every later depth came from the cache
    assert _counter("srml_daemon_pass_rows_total", source="wire") == len(x)
    assert _counter("srml_daemon_pass_rows_total", source="cache") == (depths - 1) * len(x)
    assert _counter("srml_daemon_passes_total", source="wire") == 1
    assert _counter("srml_daemon_passes_total", source="cache") == depths - 1
    for key in off.arrays:
        np.testing.assert_array_equal(on.arrays[key], off.arrays[key], err_msg=key)


def test_spark_forest_a_daemon_without_its_cached_pass_gets_the_pass_refed(
        mesh8, monkeypatch):
    """`rescan` answers NoCachedPass once (the cache lost, not over budget):
    that depth is fed — which refills the cache — the later depths are
    scanned again, and the model is the key-off model. No recovery budget."""
    from sparksim import SimDataFrame
    from spark_rapids_ml_tpu.spark import estimator as spark_est

    spark_est.register_dataframe_type(SimDataFrame)
    monkeypatch.delenv("SRML_FIT_RECOVERY_ATTEMPTS", raising=False)
    x, y = _rows(59, n=900)
    real, calls = _Job.rescan, []

    def losing(job, *args, **kwargs):
        calls.append(job)
        if len(calls) == 1:
            job._drop_cache()
            job._cache_ok = True
        return real(job, *args, **kwargs)

    with DataPlaneDaemon(host="127.0.0.1", port=0, mesh=mesh8) as daemon:
        monkeypatch.setenv("SRML_DAEMON_ADDRESS", "%s:%d" % daemon.address)
        monkeypatch.delenv("SRML_DAEMON_PASS_CACHE_MB", raising=False)
        off = _fit(x, y)
        monkeypatch.setattr(_Job, "rescan", losing)
        monkeypatch.setenv("SRML_DAEMON_PASS_CACHE_MB", "16")
        metrics_mod.reset()
        with config.option("daemon_pass_cache_mb", 16):
            on = _fit(x, y)
    depths = int(_counter("srml_daemon_passes_total"))
    assert len(calls) == depths - 1  # depth 1 asked and refused, then depths 2.. scanned
    assert _counter("srml_daemon_pass_rows_total", source="wire") == 2 * len(x)
    assert _counter("srml_daemon_passes_total", source="cache") == depths - 2
    for key in off.arrays:
        np.testing.assert_array_equal(on.arrays[key], off.arrays[key], err_msg=key)
