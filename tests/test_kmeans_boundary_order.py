"""The KMeans pass boundary is one program: `step` dispatches the Lloyd
update and the next pass's zero state together (`kmeans.lloyd_boundary`),
starts both scalars' copies to the host, and waits for nothing; the daemon's
`.read` turns them into floats. The work is the eager rule's: the centers
bit for bit, `moved2` to float32 rounding, `cost` the fold's own array, and
the next pass folds from the made-ahead zeros as from `stream_zero_state`.
Empty clusters keep their centroid, as in Spark."""

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import kmeans as km
from spark_rapids_ml_tpu.serve import daemon
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils import xprof

D = 6
K = 5
ROWS = 700
BOUNDARY = "kmeans.lloyd_boundary"


def _cached_job(mesh, seed=3):
    """A KMeans job that holds its first pass in its pass cache, fed in
    two batches. Its last two starting centers lie far from every row, so
    their clusters stay empty pass after pass."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, D)).astype(np.float32)
    x[: ROWS // 2] += 4.0
    start = np.concatenate([x[:3], np.full((2, D), 1e3, np.float32) + np.arange(2)[:, None]])
    with config.option("daemon_pass_cache_mb", 8):
        job = _Job("kmeans", D, mesh, {"k": K})
    job.set_iterate({"centers": start}, 0)
    for lo, hi in ((0, 400), (400, ROWS)):
        job.fold(x[lo:hi], None, pass_id=0)
    assert job.cache_ack() == {"cached": True, "cached_rows": ROWS}
    return job


def _fold_cached_pass(job, state):
    """The job's cached pass folded into `state` as `rescan` folds it."""
    for group in daemon._rescan_groups(job._cache.batches):
        xs, ms = zip(*group)
        state = job.algorithm.fold_group(state, xs, ms)
    return state


def _next_state(made):
    samples = (metrics_mod.snapshot().get("srml_kmeans_next_state_total") or {}).get(
        "samples", [])
    return sum(s["value"] for s in samples if s["labels"].get("made") == made)


def _boundary_calls():
    return xprof.snapshot().get(BOUNDARY, {}).get("calls", 0)


def test_every_boundary_is_the_eager_rule_with_empty_clusters_kept(mesh8):
    job = _cached_job(mesh8)
    algo = job.algorithm
    for it in range(4):
        if it:
            job.rescan(job.iteration)
        state = job.peek_pass_state()[0]
        sums, counts, cost = (np.asarray(a) for a in state)
        assert (counts[-2:] == 0).all() and (counts[:-2] > 0).all()
        want_centers, want_moved2 = km.apply_lloyd_update(state[0], state[1], algo.centers)
        info = job.step({})
        np.testing.assert_array_equal(np.asarray(algo.centers), np.asarray(want_centers))
        np.testing.assert_allclose(info["moved2"], float(want_moved2), rtol=1e-6)
        assert info["cost"] == float(cost)
        assert type(info["moved2"]) is float and type(info["cost"]) is float
    np.testing.assert_array_equal(np.asarray(algo.centers)[-2:],
                                  np.full((2, D), 1e3) + np.arange(2)[:, None])
    assert (info["iteration"], info["pass_rows"]) == (4, ROWS)


def test_the_step_hands_back_device_scalars_and_the_folds_own_cost(mesh8):
    job = _cached_job(mesh8)
    algo = job.algorithm
    state = job.peek_pass_state()[0]
    fields = algo.step(state, {})
    assert set(fields) == {"moved2", "cost"}
    assert all(isinstance(v, jax.Array) and v.shape == () for v in fields.values())
    assert fields["cost"] is state[2]
    algo.next_pass_state()


def test_the_next_pass_folds_from_the_made_ahead_state_as_from_zeros(mesh8):
    job = _cached_job(mesh8)
    algo = job.algorithm
    kept = job.peek_pass_state()[0]
    kept_values = [np.asarray(a).copy() for a in kept]
    algo.step(kept, {})
    ahead = algo.next_pass_state()
    zeros = km.stream_zero_state(K, D, algo.accum)
    assert [(a.shape, a.dtype) for a in ahead] == [(z.shape, z.dtype) for z in zeros]
    assert all(not np.any(np.asarray(a)) for a in ahead)
    for got, want in zip(_fold_cached_pass(job, ahead), _fold_cached_pass(job, zeros)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # nothing donated the finished pass's state: a caller that kept it reads it
    for was, leaf in zip(kept_values, kept):
        np.testing.assert_array_equal(np.asarray(leaf), was)


def test_a_step_is_one_boundary_dispatch_and_its_state_is_made_ahead(mesh8):
    job = _cached_job(mesh8)
    calls, ahead, on_demand = _boundary_calls(), _next_state("ahead"), _next_state("on_demand")
    for it in range(3):
        if it:
            job.rescan(job.iteration)
        job.step({})
        assert _boundary_calls() - calls == it + 1
    assert _next_state("ahead") - ahead == 3
    assert _next_state("on_demand") == on_demand


def test_a_state_asked_with_no_step_before_it_is_made_on_demand(mesh8):
    job = _cached_job(mesh8)
    algo = job.algorithm
    ahead, on_demand = _next_state("ahead"), _next_state("on_demand")
    made = algo.next_pass_state()
    assert (_next_state("ahead"), _next_state("on_demand")) == (ahead, on_demand + 1)
    assert all(not np.any(np.asarray(a)) for a in made)
    algo.step(job.peek_pass_state()[0], {})
    algo.next_pass_state()
    algo.next_pass_state()  # the stash went with the first
    assert (_next_state("ahead"), _next_state("on_demand")) == (ahead + 1, on_demand + 2)


@pytest.mark.parametrize("tol", [0.0, 1e-3])
def test_the_stream_fit_takes_the_same_boundary(mesh8, tol):
    rng = np.random.default_rng(9)
    x = rng.normal(size=(900, D)).astype(np.float32)
    x[:450] -= 3.0
    batches = lambda: iter(np.array_split(x, 3))  # noqa: E731
    calls = _boundary_calls()
    sol = km.fit_kmeans_stream(batches, k=3, n_cols=D, max_iter=5, tol=tol, seed=2,
                               mesh=mesh8)
    assert _boundary_calls() - calls == sol.n_iter
    # the same fit with the eager rule between host scans
    centers = km._kmeans_plus_plus(x[:65536], 3, np.random.default_rng(2))
    centers = jax.numpy.asarray(centers, config.get("accum_dtype"))
    update = km._stream_step_fn(mesh8, 3, config.get("compute_dtype"), config.get("accum_dtype"))

    def scan(c):
        state = km.stream_zero_state(3, D, config.get("accum_dtype"))
        for b in batches():
            xs, ms, _ = km.shard_rows(b, mesh8, dtype=np.float32)
            state = update(state, c, xs, ms)
        return state

    for _ in range(sol.n_iter):
        sums, counts, _ = scan(centers)
        centers, _ = km.apply_lloyd_update(sums, counts, centers)
    np.testing.assert_array_equal(sol.centers, np.asarray(centers, np.float64))
    assert sol.cost == float(scan(centers)[2])
