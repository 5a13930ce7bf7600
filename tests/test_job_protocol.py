"""The seam between a daemon job and its algorithm (PR 30).

`serve/daemon.py` `_Job` owns transport, staging, fencing, the pass cache
and durability; what depends on the algorithm is behind one
`models/job_protocol.py` `JobAlgorithm` object, by wire name from
`models/jobs.py`. Held here: (a) what every algorithm of the table owes
the job, (b) that a new algorithm is one class and one table entry — a toy
one runs through a real `_Job` with no edit to the daemon, (c) that the
job does not switch on `algo` again, and that the arrows between the
layers point one way."""

import ast
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.models import random_forest as rf_mod
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.models.jobs import JOB_ALGORITHMS, job_algorithm
from spark_rapids_ml_tpu.serve import daemon as daemon_mod
from spark_rapids_ml_tpu.serve.daemon import _Job, _new_job, _RowsJob
from spark_rapids_ml_tpu.tools import analyze

PKG = Path(daemon_mod.__file__).resolve().parent.parent
D, K, N = 6, 3, 203  # N: ragged against every bucket and the 8-way mesh

#: Creation params by wire name (the rest take none). rf: no bootstrap —
#: a row's bag is keyed by (partition, offset), so a row keeps its weight
#: however the rows are cut into stages only without it.
PARAMS = {
    "kmeans": {"k": K},
    "logreg": {"n_classes": 3},
    "rf": {"num_trees": 2, "max_depth": 2, "max_bins": 8, "n_classes": 2,
           "bootstrap": False},
}
ALGOS = list(JOB_ALGORITHMS)
MERGEABLE = [a for a in ALGOS if JOB_ALGORITHMS[a].mergeable]
ITERATIVE = [a for a in ALGOS if JOB_ALGORITHMS[a].iterative]


def _rows(rng):
    x = rng.normal(size=(N, D)).astype(np.float32)
    y = {
        "linreg": x @ rng.normal(size=D),
        "logreg": rng.integers(0, 3, size=N).astype(np.float64),
        "rf": rng.integers(0, 2, size=N).astype(np.float64),
    }
    return x, y


def _start(algo, x):
    """An iterate to install before the first fold, where one is needed."""
    if algo == "kmeans":
        return {"centers": x[:K].astype(np.float64)}
    if algo == "rf":
        spec = rf_mod.forest_spec_from_params(PARAMS["rf"], D)
        edges = np.quantile(x, np.linspace(0, 1, spec.max_bins + 1)[1:-1], axis=0).T
        return rf_mod.init_forest_arrays(spec, edges)
    return None


def _leaves(job):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(jax.device_get(job.state))]


# -- (a) what every algorithm of the table owes the job ---------------------


def test_the_table_names_its_classes_and_refuses_the_rest(mesh8):
    for name, cls in JOB_ALGORITHMS.items():
        assert issubclass(cls, JobAlgorithm) and cls.name == name
        assert job_algorithm(name) is cls
    with pytest.raises(ValueError, match=re.escape(
            "unknown algo 'bogus' (pca|linreg|kmeans|logreg|rf|knn)")):
        _Job("bogus", D, mesh8)


@pytest.mark.parametrize("algo", MERGEABLE)
def test_two_halves_staged_and_merged_equal_one_fold_of_the_whole(algo, mesh8, rng):
    x, labels = _rows(rng)
    y = labels.get(algo)
    cuts = {"halves": [(0, 101), (101, N)], "whole": [(0, N)]}
    states = {}
    for how, parts in cuts.items():
        job = _new_job(algo, D, mesh8, PARAMS.get(algo))
        assert type(job) is _Job
        start = _start(algo, x)
        if start is not None:
            job.set_iterate(start, 0)
        for pid, (lo, hi) in enumerate(parts):
            job.fold(x[lo:hi], None if y is None else y[lo:hi], partition=pid,
                     pass_id=0 if job.algorithm.iterative else None)
            assert job.commit(pid) == hi
        assert job.pass_rows == N and not job.staged
        states[how] = _leaves(job)
    assert len(states["halves"]) == len(states["whole"]) > 0
    for a, b in zip(states["halves"], states["whole"]):
        np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-9)
        assert np.any(b != 0)


#: A mis-shaped iterate and the refusal it meets today.
BAD_ITERATE = {
    "kmeans": ({"centers": np.zeros((K + 1, D))},
               f"centers shape ({K + 1}, {D}) != ({K}, {D})"),
    "logreg": ({"w": np.zeros((D, 2)), "b": np.zeros(3)},
               f"coefficients shape ({D}, 2) != ({D}, 3) (n_cols={D}, n_classes=3)"),
    "rf": ({**_start("rf", np.zeros((4, D), np.float32)), "feature": np.zeros((2, 3))},
           "forest iterate array 'feature' shape (2, 3) != (2, 7)"),
}


@pytest.mark.parametrize("algo", ITERATIVE)
def test_installing_the_iterate_a_job_gives_is_the_identity(algo, mesh8, rng):
    x, _ = _rows(rng)
    algorithm = job_algorithm(algo)(D, mesh8, PARAMS[algo])
    start = _start(algo, x)
    if start is not None:
        assert not algorithm.installed
        algorithm.install_iterate(start)
    assert algorithm.installed
    first = algorithm.iterate_arrays()
    algorithm.install_iterate(first)
    again = algorithm.iterate_arrays()
    assert sorted(first) == sorted(again)
    for key in first:
        np.testing.assert_array_equal(first[key], again[key])
    bad, refusal = BAD_ITERATE[algo]
    with pytest.raises(ValueError, match=re.escape(refusal)):
        algorithm.install_iterate(bad)
    # ... and through the job, which keeps what it had
    job = _Job(algo, D, mesh8, PARAMS[algo])
    job.set_iterate(first, 4)
    with pytest.raises(ValueError, match=re.escape(refusal)):
        job.set_iterate(bad, 5)
    assert job.iteration == 4
    for key, value in job.get_iterate()[0].items():
        np.testing.assert_array_equal(value, first[key])


@pytest.mark.parametrize("algo", [a for a in ALGOS if a not in ITERATIVE])
def test_a_single_pass_job_has_no_iterate_and_no_boundary(algo, mesh8):
    job = _new_job(algo, D, mesh8, PARAMS.get(algo))
    for call, text in (
        (lambda: job.get_iterate(), "is single-pass; it has no iterate"),
        (lambda: job.set_iterate({}, 1), "is single-pass; set_iterate not applicable"),
        (lambda: job.step({}), "is single-pass; step not applicable"),
    ):
        with pytest.raises(ValueError, match=re.escape(f"algo {algo!r} {text}")):
            call()
    assert job.durable_arrays() == {}
    with pytest.raises(ValueError, match="seed only applies to kmeans jobs"):
        job.seed_centers(np.zeros((8, D), np.float32))


@pytest.mark.parametrize("algo", [a for a in ALGOS if a not in MERGEABLE])
def test_rows_that_are_the_state_get_the_row_store_job(algo, mesh8, rng):
    x, _ = _rows(rng)
    job = _new_job(algo, D, mesh8, {})
    assert type(job) is _RowsJob and job.state == []
    job.fold(x[:50], None, partition=1)
    job.fold(x[50:80], None, partition=0)
    job.fold(x[80:], None)
    assert (job.rows, job.staged_bytes) == (N - 80, 80 * D * 4)
    job.commit(1), job.commit(0)
    assert job.rows == N and job.staged_bytes == 0
    # direct rows first, then partition-major whatever the commit order
    np.testing.assert_array_equal(
        job.sample_rows(N), np.concatenate([x[80:], x[50:80], x[:50]]))
    for refused in (job.export_state, job.peek_pass_state,
                    lambda: job.merge_remote({}, 0)):
        with pytest.raises(ValueError, match="knn job"):
            refused()


def test_the_analyzer_knows_the_members_the_protocol_marks_as_dispatching():
    marked = {
        name for name, member in vars(JobAlgorithm).items()
        if callable(member) and not name.startswith("_")
        and "*dispatches*" in (member.__doc__ or "")
    }
    assert marked == set(analyze._ALGORITHM_DISPATCH_MEMBERS)


# -- (b) a seventh algorithm is one class and one table entry ----------------


class ColumnSums(JobAlgorithm):
    """(count, Σx): the smallest algorithm the protocol can hold."""

    name = "colsum"

    def zero_state(self):
        return jnp.zeros((), self.accum), jnp.zeros((self.n_cols,), self.accum)

    def fold(self, state, xs, ms, columns=(), n=0):
        count, colsum = state
        masked = xs.astype(self.accum) * ms.astype(self.accum)[:, None]
        return count + ms.astype(self.accum).sum(), colsum + masked.sum(axis=0)

    def finalize(self, state, params, rows, iteration):
        count, colsum = jax.device_get(state)
        return {"count": np.asarray([float(count)]), "colsum": np.asarray(colsum),
                "rows": np.asarray([rows])}


def test_a_toy_algorithm_runs_through_a_real_job_with_the_daemon_unedited(
        mesh8, rng, monkeypatch):
    monkeypatch.setitem(JOB_ALGORITHMS, "colsum", ColumnSums)
    x, _ = _rows(rng)
    here, there = (_new_job("colsum", D, mesh8, {}) for _ in range(2))
    assert type(here) is _Job and here._cache_budget == 0
    here.fold(x[:60], None, partition=0, feed_id="f0")
    here.fold(x[:60], None, partition=0, feed_id="f0")  # a replay: not folded
    here.fold(x[60:90], None, partition=0, attempt=1)   # a losing attempt
    assert here.rows == 0
    assert here.commit(0) == 60 and not here.staged
    here.fold(x[60:120], None)
    there.fold(x[120:], None, partition=3)
    there.commit(3)
    arrays, meta = there.export_state()
    assert (meta["algo"], meta["rows"], meta["committed"]) == ("colsum", N - 120, {"3": N - 120})
    assert here.merge_remote(arrays, meta["rows"], merge_id="m") == N
    assert here.merge_remote(arrays, meta["rows"], merge_id="m") == N  # replayed
    out = here.finalize({}, drop=True)
    assert out["count"][0] == N and out["rows"][0] == N
    np.testing.assert_allclose(out["colsum"], x.astype(np.float64).sum(axis=0), rtol=1e-9)
    with pytest.raises(KeyError, match="finalized/dropped"):
        here.fold(x[:4], None)
    with pytest.raises(ValueError, match=r"unknown algo 'colsums' \(pca\|.*\|knn\|colsum\)"):
        _new_job("colsums", D, mesh8, {})


@pytest.mark.parametrize("algo", ITERATIVE)
def test_a_job_asks_its_algorithm_for_the_next_passs_state_and_tells_it_of_a_merge(
        algo, mesh8, rng):
    """The two hooks of ISSUE 37, through a real job: both merges say
    `state_merged` once the add applied — not for a replay — and `step`
    takes the next pass's state from `next_pass_state`: by default
    `zero_state()`, zeros of the state's own shapes."""
    x, labels = _rows(rng)
    y = labels.get(algo)
    here, there = (_new_job(algo, D, mesh8, PARAMS.get(algo)) for _ in range(2))
    calls = []
    for name in ("next_pass_state", "state_merged"):
        real = getattr(here.algorithm, name)
        setattr(here.algorithm, name,
                lambda real=real, name=name: calls.append(name) or real())
    start = _start(algo, x)
    for job, rows in ((here, slice(0, 101)), (there, slice(101, N))):
        if start is not None:
            job.set_iterate(start, 0)
        job.fold(x[rows], None if y is None else y[rows], pass_id=0)
    arrays, meta = there.export_state()
    here.merge_remote(arrays, meta["rows"], merge_id="m")
    here.merge_remote(arrays, meta["rows"], merge_id="m")  # replayed: not applied
    assert calls == ["state_merged"]
    state, rows, _, _ = there.peek_pass_state()
    here.merge_mesh([("peer", state, rows)], reduce_id="r")
    here.merge_mesh([("peer", state, rows)], reduce_id="r")
    assert calls == ["state_merged"] * 2
    here.step({})
    assert calls == ["state_merged"] * 2 + ["next_pass_state"]
    zeros = jax.tree_util.tree_leaves(here.algorithm.zero_state())
    assert len(zeros) == len(_leaves(here))
    for got, zero in zip(_leaves(here), zeros):  # a merged forest hands no parent on
        np.testing.assert_array_equal(got, np.asarray(zero))


# -- (c) the structure that keeps it so -------------------------------------


def _algo_comparisons(node):
    """Comparisons of something named `algo` with a string constant."""
    found = []
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Compare):
            continue
        sides = [sub.left, *sub.comparators]
        names = any(analyze.terminal_name(s) in ("algo", "req_algo") for s in sides)
        strings = any(
            isinstance(c, ast.Constant) and isinstance(c.value, str)
            for s in sides for c in ast.walk(s)
        )
        if names and strings:
            found.append(sub.lineno)
    return found


def _daemon_tree():
    return ast.parse((PKG / "serve" / "daemon.py").read_text())


@pytest.mark.parametrize("cls", ["_Job", "_RowsJob"])
def test_a_job_compares_algo_with_no_string(cls):
    """46 such comparisons in `_Job` at the parent of PR 30."""
    node = next(n for n in ast.walk(_daemon_tree())
                if isinstance(n, ast.ClassDef) and n.name == cls)
    assert _algo_comparisons(node) == []


@pytest.mark.parametrize(
    "fn", ["_op_feed", "_feed_validated", "_attach_durability", "_restore_job"])
def test_the_daemons_job_side_ops_ask_the_table_not_a_tuple_of_names(fn):
    node = next(n for n in ast.walk(_daemon_tree())
                if isinstance(n, ast.FunctionDef) and n.name == fn)
    assert _algo_comparisons(node) == []
    assert "n_classes" not in ast.unparse(node)


def test_no_attribute_is_guessed_because_it_exists_on_one_branch_only():
    assert 'getattr(self, "n_classes"' not in (PKG / "serve" / "daemon.py").read_text()


def test_the_arrows_point_one_way():
    """`models/*`, `ops/*` import nothing from `serve/`, and the device
    lock is taken in `serve/` only."""
    for layer in ("models", "ops"):
        for path in sorted((PKG / layer).glob("*.py")):
            text = path.read_text()
            assert not re.search(r"^\s*(from|import) spark_rapids_ml_tpu\.serve\b|"
                                 r"^\s*from spark_rapids_ml_tpu import .*\bserve\b",
                                 text, re.M), path
            assert "with _DEVICE_LOCK" not in text, path
    takers = {p.parent.name for p in sorted(PKG.rglob("*.py"))
              if "with _DEVICE_LOCK" in p.read_text() and p.parent.name != "tools"}
    assert takers == {"serve"}
