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
        monkeypatch.setattr(hist_ops, "_SHORT_CHUNKS_BELOW_ROWS", 0)
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


# ---- ISSUE 37: one child of every split is folded, its sibling is the parent less it ----


def _signs(job):
    return job.algorithm._signs


def _frontier_nodes(how):
    return _counter("srml_forest_frontier_nodes_total", how=how)


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
@pytest.mark.parametrize("transport", ["direct", "staged", "cached"])
def test_a_seeded_pass_holds_the_direct_folds_histogram_at_every_depth(
        mesh8, params, transport):
    """`whole` is handed its iterate before every pass (so it holds no
    parent and folds the whole frontier, as the job did before ISSUE 37);
    `halved` steps on its own: from depth 1 on its state starts from the
    parent's histogram and its folds — direct feeds, stages merged at their
    commit, a rescan's runs, the last batch ragged — contract one child of
    every pair. Whole-number rows: before every `step` the two states are
    the same bits, and so are the tables after it."""
    params = dict(params, max_depth=4)
    x, y = _rows(61, classes=params["n_classes"])
    whole, halved = _job(mesh8, 0, params), _job(mesh8, 16, params)
    for job in (whole, halved):
        job.set_iterate(_start(params, x), 0)
    folded = derived = 0

    def feed(job, it):
        # a row's bag is keyed by its (partition, offset): both jobs feed alike
        for i, (lo, hi) in enumerate(BATCHES):
            job.fold(x[lo:hi], y[lo:hi], pass_id=it,
                     partition=i % 2 if transport == "staged" else None)
        if transport == "staged":
            for part in (1, 0):
                job.commit(part, pass_id=it)

    for it in range(params["max_depth"] + 1):
        feed(whole, it)
        if transport == "cached" and it > 0:
            halved.rescan(it)
        else:
            feed(halved, it)
        signs = _signs(halved)
        assert _signs(whole) is None and (signs is None) == (it == 0)
        if it:
            assert signs.shape == (params["num_trees"], 1 << (it - 1), 2)
            folded += int((signs > 0).sum())
            derived += int((signs < 0).sum())
        np.testing.assert_array_equal(_hist(halved), _hist(whole))
        info = halved.step({})
        assert whole.step({}) == info
        got, want = halved.get_iterate()[0], whole.get_iterate()[0]
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)
        if info["open_nodes"] == 0:
            break
        whole.set_iterate(want, it + 1)  # an installed iterate: no parent
    assert it >= 3 and derived > 0 and folded >= derived


def _doctored(params, x, y, mesh):
    """Tables at depth 2 with every case of a pair in them, and the
    complete depth-1 histogram they descend from: tree 0 has a parent that
    did not split (closed, its children closed) beside a split whose
    children tie in their counts; tree 1 a pair with one OPEN child."""
    spec, tables, placed, keys = _grown(params, x, y, mesh, depth=1)
    xs, ys, ms, ks = placed
    parent = rf.accumulate_histogram(
        hist_ops.zero_hist(spec.num_trees, 1, x.shape[1], spec.max_bins, spec.n_stats,
                           config.get("accum_dtype")),
        tables, (xs,), (ys,), (ms,), (ks,), spec, mesh, n_valid=len(x))
    rf.grow_level(tables, parent, spec)
    feat, val = tables["feature"], tables["value"]
    assert (feat[:2, 1:3] >= 0).all() and (feat[:2, 3:7] == OPEN).all()
    feat[0, 1], feat[0, 3:5] = rf.LEAF, rf.LEAF  # the parent that did not split
    val[0, 5] = val[0, 6]  # a tie: the left child folds
    feat[1, 4] = rf.LEAF  # one OPEN child: it folds, nothing is derived
    return spec, tables, placed, parent


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
def test_which_child_folds_and_the_halved_fold_over_every_case_of_a_pair(mesh8, params):
    x, y = _rows(67, classes=params["n_classes"])
    spec, tables, placed, parent = _doctored(params, x, y, mesh8)
    signs = rf.pair_signs(tables, spec)
    assert signs.shape == (spec.num_trees, 2, 2) and signs.dtype == np.int8
    np.testing.assert_array_equal(signs[0], [[0, 0], [1, -1]])
    np.testing.assert_array_equal(signs[1, 0], [1, 0])
    count = tables["value"][:, 3:7].sum(-1) if spec.n_classes else tables["value"][:, 3:7, 0]
    for t in range(2, spec.num_trees):
        for pair in range(2):
            left, right = count[t, 2 * pair], count[t, 2 * pair + 1]
            assert list(signs[t, pair]) == ([-1, 1] if right < left else [1, -1])
    xs, ys, ms, ks = placed
    accum = config.get("accum_dtype")
    want = np.asarray(rf.accumulate_histogram(
        hist_ops.zero_hist(spec.num_trees, 2, D, spec.max_bins, spec.n_stats, accum),
        tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=len(x)))
    state, got_signs = rf.open_pass(tables, spec, D, parent=parent)
    np.testing.assert_array_equal(got_signs, signs)
    seeded = np.asarray(state)
    # the seed: the parent's histogram where a child will be derived, zeros elsewhere
    np.testing.assert_array_equal(seeded[0, 3], np.asarray(parent)[0, 1])
    assert not seeded[0, :3].any() and not seeded[1, :2].any()
    got = np.asarray(rf.accumulate_histogram(
        state, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=len(x), signs=signs))
    assert want[0, 2:].any() and want[1, 0].any() and not want[0, :2].any()
    np.testing.assert_array_equal(got, want)
    # without a parent, at depth 0 or where nothing is open the pass opens as it did
    assert rf.open_pass(tables, spec, D)[1] is None
    tables["feature"][:, 3:7] = rf.LEAF
    assert rf.open_pass(tables, spec, D, parent=parent) == ((), None)


@pytest.mark.parametrize("d,block", [(40, 12), (7, 4)], ids=["whole_tiles", "ragged"])
def test_a_chunk_folded_in_feature_blocks_joins_each_block_where_it_belongs(
        mesh8, monkeypatch, d, block):
    """Several feature blocks a chunk, the last reaching back over the one
    before it — at a width in whole tiles of eight (blocks of 16 at offsets
    0, 16, 24) and at one that is not (blocks of 4 at 0, 3): the whole
    frontier's fold and the halved one, against the whole-batch oracle."""
    x, y = _rows(97, n=1600, d=d)
    monkeypatch.setattr(hist_ops, "_ONEHOT_BLOCK_BYTES", 200 * REG["max_bins"] * 8 * block)
    hist_ops.hist_update_group_fn.cache_clear()
    try:
        spec, tables, placed, keys = _grown(REG, x, y, mesh8, depth=1)
        xs, ys, ms, ks = placed
        accum = jnp.dtype(config.get("accum_dtype"))

        def fold(state, signs=None):
            return rf.accumulate_histogram(
                state, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh8, n_valid=len(x),
                signs=signs)

        parent = fold(hist_ops.zero_hist(spec.num_trees, 1, d, spec.max_bins, 3, accum))
        rf.grow_level(tables, parent, spec)
        want = np.asarray(_whole_batch_histogram(
            jnp.asarray(tables["bin_edges"], accum), jnp.asarray(tables["feature"]),
            jnp.asarray(tables["threshold"]), x, y, np.ones(len(x)), keys, spec, depth=2))
        assert want[..., 0].sum() > 0
        whole = fold(hist_ops.zero_hist(spec.num_trees, 2, d, spec.max_bins, 3, accum))
        np.testing.assert_array_equal(np.asarray(whole), want)
        state, signs = rf.open_pass(tables, spec, d, parent=parent)
        assert (signs < 0).any()
        np.testing.assert_array_equal(np.asarray(fold(state, signs)), want)
    finally:
        hist_ops.hist_update_group_fn.cache_clear()


def test_fold_group_over_a_run_is_fold_batch_by_batch_in_a_seeded_pass(mesh8):
    """Real-valued labels, as the depth-0 test above: the run's program
    adds — and subtracts — in the calls' order."""
    x, _ = _rows(71)
    y = np.random.default_rng(71).normal(size=ROWS)
    job = _job(mesh8, 16, REG)
    job.set_iterate(_start(REG, x), 0)
    for lo, hi in BATCHES:
        job.fold(x[lo:hi], y[lo:hi], pass_id=0)
    parent = job.peek_pass_state()[0]
    job.step({})
    assert _signs(job) is not None
    for lo, hi in BATCHES[:4]:
        job.fold(x[lo:hi], y[lo:hi], pass_id=1)
    one_by_one = _hist(job)
    algo = job.algorithm
    xs, ms, ys, ks = zip(*job._cache.batches[:4])
    state, _ = rf.open_pass(algo.tables, algo.spec, D, parent=parent)
    grouped = np.asarray(algo.fold_group(state, xs, ms, (ys, ks)))
    np.testing.assert_array_equal(grouped, one_by_one)


def test_a_restored_job_folds_one_level_whole_and_hands_its_parent_on_again(mesh8):
    """A snapshot holds the iterate, not the parent: the restored job's
    first pass folds the whole frontier, its `step` keeps that histogram,
    and the fit ends in the uninterrupted fit's tables."""
    x, y = _rows(73)
    params = dict(REG, max_depth=4)
    job = _job(mesh8, 16, params)
    job.set_iterate(_start(params, x), 0)
    for lo, hi in BATCHES:
        job.fold(x[lo:hi], y[lo:hi], pass_id=0)
    for it in (1, 2):
        job.step({})
        job.rescan(it)
    assert _signs(job) is not None
    restored = _job(mesh8, 16, params)
    restored.set_iterate(job.durable_arrays(), 2)  # what a boundary snapshot holds
    modes = []
    for it in range(2, 5):
        for lo, hi in BATCHES:
            restored.fold(x[lo:hi], y[lo:hi], pass_id=it)
        modes.append(_signs(restored) is not None)
        np.testing.assert_array_equal(_hist(restored), _hist(job))
        info = restored.step({})
        assert job.step({}) == info
        if info["open_nodes"] == 0:
            break
        job.rescan(it + 1)
    assert modes[:2] == [False, True]
    got, want = restored.get_iterate()[0], job.get_iterate()[0]
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@pytest.mark.parametrize("merge", ["merge_remote", "merge_mesh"])
def test_a_job_whose_state_took_a_merge_hands_no_parent_on(mesh8, merge):
    """The parent must be of exactly the rows the next pass folds here: a
    state that took another daemon's is not, and a peer that held rows once
    may hold none in one pass and some in the next — so from the first
    merge until an iterate is installed the job folds whole frontiers."""
    x, y = _rows(79)
    params = dict(REG, max_depth=6)
    primary, peer, alone = (_job(mesh8, 0, params) for _ in range(3))
    start = _start(params, x)
    for job in (primary, peer, alone):
        job.set_iterate(start, 0)
    for it in range(3):
        primary.fold(x[:700], y[:700], partition=0, pass_id=it)
        primary.commit(0, pass_id=it)
        for part, rows in enumerate((slice(0, 700), slice(700, None))):
            alone.fold(x[rows], y[rows], partition=part, pass_id=it)
            alone.commit(part, pass_id=it)
        if it < 2:  # in the last pass the peer holds no row: nothing is merged
            peer.fold(x[700:], y[700:], partition=1, pass_id=it)
            peer.commit(1, pass_id=it)
            if merge == "merge_remote":
                arrays, meta = peer.export_state()
                primary.merge_remote(arrays, meta["pass_rows"], merge_id=f"m{it}")
            else:
                state, rows, _, _ = peer.peek_pass_state()
                primary.merge_mesh([("peer", state, rows)], reduce_id=f"r{it}")
        else:
            primary.fold(x[700:], y[700:], partition=1, pass_id=it)
            primary.commit(1, pass_id=it)
        assert _signs(primary) is None and (_signs(alone) is None) == (it == 0)
        np.testing.assert_array_equal(_hist(primary), _hist(alone))
        assert primary.step({}) == alone.step({})
        peer.set_iterate(primary.get_iterate()[0], it + 1)
    # an installed iterate opens a new account: the next own step hands a parent on
    primary.set_iterate(primary.get_iterate()[0], 3)
    for it in (3, 4):
        for job in (primary, alone):
            for part, rows in enumerate((slice(0, 700), slice(700, None))):
                job.fold(x[rows], y[rows], partition=part, pass_id=it)
                job.commit(part, pass_id=it)
        assert (_signs(primary) is None) == (it == 3)
        np.testing.assert_array_equal(_hist(primary), _hist(alone))
        if it == 3:
            assert primary.step({}) == alone.step({})


@pytest.mark.parametrize("params", [REG, CLF], ids=["regressor", "classifier"])
def test_folded_and_derived_nodes_add_to_the_open_nodes_of_every_level(mesh8, params):
    x, y = _rows(83, classes=params["n_classes"])
    before = {how: _frontier_nodes(how) for how in ("folded", "derived")}
    job = _job(mesh8, 16, params)
    job.set_iterate(_start(params, x), 0)
    job.fold(x, y, pass_id=0)
    open_nodes, derived = params["num_trees"], 0  # the roots
    it = 0
    while True:
        info = job.step({})
        if info["open_nodes"] == 0:
            break
        it += 1
        open_nodes += info["open_nodes"]
        derived += int((_signs(job) < 0).sum())
        job.rescan(it)
    got = {how: _frontier_nodes(how) - before[how] for how in before}
    assert derived > 0 and got["derived"] == derived
    assert got["folded"] + got["derived"] == open_nodes
    # the in-memory fit takes the same path: the same nodes, the same way
    before = {how: _frontier_nodes(how) for how in before}
    with config.option("forest_seed_sample_rows", ROWS):
        fit = (rf.fit_random_forest_classifier if params["n_classes"]
               else rf.fit_random_forest_regressor)
        fit(x, y, **{k: v for k, v in params.items() if k != "n_classes"}, mesh=mesh8)
    assert {how: _frontier_nodes(how) - before[how] for how in before} == got


def test_derived_label_sums_stay_within_float32s_rounding_of_a_float64_histogram(mesh8):
    """Real labels in float32, four levels deep: a derived child's Σy and
    Σy² are the parent's less the folded child's — one more rounding a
    chunk and level — and stay within 1e-6 of a float64 histogram of the
    same rows (what the chip's `hist_rel` is predicted from); the count
    channel is exact."""
    params = dict(REG, max_depth=5, num_trees=3, bootstrap=False)
    rng = np.random.default_rng(89)
    n = 4096
    x = rng.normal(size=(n, D)).astype(np.float32)
    y = (3.0 * x[:, 0] - 2.0 * x[:, 1] + x[:, 2] * x[:, 3] + 0.3 * rng.normal(size=n) + 5.0)
    with config.option("accum_dtype", "float32"), config.option("compute_dtype", "float32"):
        job = _job(mesh8, 16, params)
        job.set_iterate(_start(params, x), 0)
        job.fold(x, y, pass_id=0)
        for it in range(1, 5):
            job.step({})
            job.rescan(it)
        signs, got = _signs(job), _hist(job)
        tables = job.get_iterate()[0]
    assert got.dtype == np.float32 and signs.shape == (3, 8, 2) and (signs < 0).sum() >= 8
    # float64 on the host: bin ids by the stated rule, every row walked to depth 4
    edges = tables["bin_edges"].astype(np.float32)
    bins = (x[:, :, None] > edges[None]).sum(-1)
    want = np.zeros(got.shape, np.float64)
    for t in range(3):
        node = np.zeros(n, np.int64)
        for _ in range(4):
            f = tables["feature"][t, node]
            right = bins[np.arange(n), np.clip(f, 0, D - 1)] > tables["threshold"][t, node]
            node = np.where(f >= 0, 2 * node + 1 + right, -1 - n)
        ok = node >= 0
        ok[ok] = tables["feature"][t, node[ok]] == OPEN
        for f in range(D):
            for s, v in enumerate((np.ones(n), y, y * y)):
                np.add.at(want[t, :, f, :, s], (node[ok] - 15, bins[ok, f]), v[ok])
    np.testing.assert_array_equal(got[..., 0], want[..., 0])
    is_derived = (signs < 0).reshape(3, 16)
    for s in (1, 2):
        a, b = got[..., s][is_derived], want[..., s][is_derived]
        assert b.any() and np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b), s


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
