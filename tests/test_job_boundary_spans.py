"""The pass boundary, opened (ISSUE 38): inside the span an iterative
algorithm names (`boundary_span`) the job's `step` opens four children
`<boundary_span>.update|state|read|snapshot` — for every algorithm, by the
job — in all of `trace_span`'s sinks, and the split reorders nothing of what
`step` did: `algorithm.step` → `next_pass_state` in one hold of the device
lock → the fields' scalars to the host → the snapshot."""

import contextlib

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import random_forest as rf
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops import histogram as hist_ops
from spark_rapids_ml_tpu.serve import daemon
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import journal, metrics

PARTS = ["update", "state", "read", "snapshot"]
D = 6
FOREST = {"num_trees": 3, "max_depth": 2, "max_bins": 8, "n_classes": 0, "seed": 2}


def _rows(seed, n=300):
    rng = np.random.default_rng(seed)
    x = rng.integers(-20, 21, size=(n, D)).astype(np.float32)
    return x, (x @ rng.integers(-3, 4, size=D)).astype(np.float64)


def _kmeans(mesh):
    x, _ = _rows(3)
    job = _Job("kmeans", D, mesh, {"k": 4})
    job.set_iterate({"centers": x[:4].copy()}, 0)
    job.fold(x, None, pass_id=0)
    return job, {}, {"moved2", "cost"}


def _logreg(mesh):
    x, y = _rows(5)
    job = _Job("logreg", D, mesh, {})
    job.set_iterate({"w": np.full(D, 0.01), "b": np.asarray([0.05])}, 0)
    job.fold(x / 20.0, (y > 0).astype(np.float64), pass_id=0)
    return job, {"reg": 1e-3, "fit_intercept": True}, {"delta", "loss"}


def _logreg_mn(mesh):
    x, y = _rows(5)
    job = _Job("logreg", D, mesh, {"n_classes": 3})
    job.set_iterate({"w": np.full((D, 3), 0.01), "b": np.asarray([0.05, 0.0, -0.05])}, 0)
    job.fold(x / 20.0, np.digitize(y, [-10.0, 10.0]).astype(np.float64), pass_id=0)
    return job, {"reg": 1e-3, "fit_intercept": True}, {"delta", "loss"}


def _forest(mesh):
    x, y = _rows(7)
    with config.option("daemon_pass_cache_mb", 16):
        job = _Job("rf", D, mesh, FOREST)
    spec = rf.forest_spec_from_params(FOREST, D)
    job.set_iterate(rf.init_forest_arrays(
        spec, hist_ops.quantile_bin_edges(x, spec.max_bins)), 0)
    job.fold(x, y, pass_id=0)
    return job, {}, {"depth", "open_nodes", "splits"}


JOBS = {"kmeans": (_kmeans, "lloyd.boundary", []),
        "logreg": (_logreg, "newton.boundary", ["newton.solve"]),
        "logreg_mn": (_logreg_mn, "softmax.boundary", ["softmax.solve"]),
        "rf": (_forest, "forest.boundary", ["forest.score"])}


@pytest.fixture()
def ring():
    """Arms the journal's ring; gives the sequence number it starts after."""
    journal.ring_arm(256)
    try:
        yield journal.last_seq()
    finally:
        journal.ring_disarm()


def _phases(since):
    events, _ = journal.tail(since)
    return [e for e in events if e["event"] == "phase"]


def _count(phase):
    samples = metrics.snapshot().get("srml_phase_duration_seconds", {}).get("samples", [])
    return sum(s["count"] for s in samples if s["labels"].get("phase") == phase)


@pytest.mark.parametrize("algo", list(JOBS))
def test_one_step_emits_the_boundary_and_exactly_its_four_children(mesh8, ring, algo):
    make, span, inside_update = JOBS[algo]
    job, params, fields = make(mesh8)
    assert job.algorithm.boundary_span == span
    names = [span] + [f"{span}.{part}" for part in PARTS]
    before = {name: _count(name) for name in names}
    since = journal.last_seq()
    info = job.step(params)
    assert fields <= set(info) and all(not isinstance(v, jax.Array) for v in info.values())
    events = _phases(max(ring, since))
    (parent,) = [e for e in events if e["name"] == span]
    children = [e for e in events if e["parent_id"] == parent["span_id"]]
    # the job names them, `<boundary_span>.<part>`, in the order they ran
    assert [e["name"] for e in children] == names[1:]
    assert {e["run_id"] for e in children} == {parent["run_id"]}
    assert sum(e["duration_s"] for e in children) <= parent["duration_s"]
    for e in children:
        assert e["ts"] >= parent["ts"] - 1e-3
        assert e["ts"] + e["duration_s"] <= parent["ts"] + parent["duration_s"] + 1e-3
    # what the algorithm spans itself (the solve, the scorer) runs under `.update`
    update = children[0]
    assert [e["name"] for e in events if e["parent_id"] == update["span_id"]] == inside_update
    # and each is one sample of the phase histogram, journal or not
    assert {name: _count(name) - n for name, n in before.items()} == {
        name: 1 for name in names}


def test_an_algorithm_that_names_no_boundary_opens_no_span(mesh8, ring, monkeypatch):
    job, params, _ = _kmeans(mesh8)
    monkeypatch.setattr(job.algorithm, "boundary_span", None)
    before = _count("lloyd.boundary")
    info = job.step(params)
    assert isinstance(info["moved2"], float) and info["iteration"] == 1
    assert [e["name"] for e in _phases(ring) if "boundary" in e["name"]] == []
    assert _count("lloyd.boundary") == before and _count("None.update") == 0


class _Scalar(jax.Array):
    """A field `step` must read with `float()`: says when it was read."""

    def __init__(self, log, name, value):
        self.log, self.name, self.value = log, name, value

    def __float__(self):
        self.log.append(f"float({self.name})")
        return self.value


class _Recording(JobAlgorithm):
    """Says what the job asked of it, in order; dispatches nothing."""

    name = "recording"
    iterative = True
    boundary_span = "recording.boundary"
    log = None

    def zero_state(self):
        return "zeros"

    def step(self, state, params):
        self.log.append(f"algorithm.step({state})")
        return {"moved2": _Scalar(self.log, "moved2", 0.25),
                "cost": _Scalar(self.log, "cost", 8.0), "plain": 3}

    def next_pass_state(self):
        self.log.append("algorithm.next_pass_state")
        return "opened"


class _LoggedLock:
    def __init__(self, log):
        self.log = log

    def __enter__(self):
        self.log.append("device lock taken")

    def __exit__(self, *exc):
        self.log.append("device lock released")


@pytest.mark.parametrize("span", ["recording.boundary", None], ids=["spanned", "unspanned"])
def test_the_split_leaves_the_order_of_what_step_does_as_it_was(mesh8, monkeypatch, span):
    log = []
    real_span = daemon.trace_span

    @contextlib.contextmanager
    def logged_span(name, **kw):
        log.append(f"> {name}")
        with real_span(name, **kw) as timer:
            yield timer
        log.append(f"< {name}")

    monkeypatch.setattr(_Recording, "log", log)
    monkeypatch.setattr(_Recording, "boundary_span", span)
    monkeypatch.setattr(daemon, "job_algorithm", lambda algo: _Recording)
    monkeypatch.setattr(daemon, "trace_span", logged_span)
    job = _Job("recording", D, mesh8, {})
    monkeypatch.setattr(daemon, "_DEVICE_LOCK", _LoggedLock(log))
    job.snapshot_cb = lambda j: log.append(f"snapshot(state={j.state})")
    job.pass_rows = 7
    info = job.step({})
    assert info == {"iteration": 1, "moved2": 0.25, "cost": 8.0, "plain": 3, "pass_rows": 7}
    assert job.state == "opened" and job.pass_rows == 0
    did = ["device lock taken", "algorithm.step(zeros)", "algorithm.next_pass_state",
           "device lock released", "float(moved2)", "float(cost)", "snapshot(state=opened)"]
    # what the job does, in the parent commit's order, spans or none
    assert [line for line in log if line[0] not in "<>"] == did
    if span is None:
        assert log == did
        return
    assert log == [
        "> recording.boundary",
        "device lock taken",  # ONE hold around the update and the next pass's state
        "> recording.boundary.update", "algorithm.step(zeros)", "< recording.boundary.update",
        "> recording.boundary.state", "algorithm.next_pass_state", "< recording.boundary.state",
        "device lock released",
        "> recording.boundary.read", "float(moved2)", "float(cost)", "< recording.boundary.read",
        "> recording.boundary.snapshot", "snapshot(state=opened)",
        "< recording.boundary.snapshot",
        "< recording.boundary"]


def test_a_span_whose_body_raises_still_lands_in_every_sink(ring):
    """`trace_span` is an object since PR 38 (no generator a span): what the
    generator's `finally` did, `__exit__` does — the histogram, the journal
    line under its parent, the exception handed on, the thread's span stack
    left as it was found."""
    from spark_rapids_ml_tpu.utils.profiling import trace_span

    before, stack = _count("probe.raises"), journal.current()
    with pytest.raises(KeyError, match="inside"):
        with trace_span("probe.outer"):
            with trace_span("probe.raises") as timer:
                raise KeyError("inside")
    assert timer.elapsed is not None and _count("probe.raises") == before + 1
    events = {e["name"]: e for e in _phases(ring)}
    assert events["probe.raises"]["parent_id"] == events["probe.outer"]["span_id"]
    assert events["probe.raises"]["duration_s"] <= events["probe.outer"]["duration_s"]
    assert journal.current() == stack


def test_a_journal_that_cannot_open_its_span_leaves_no_annotation_open(ring, monkeypatch):
    """`__enter__` enters the profiler's annotation, then the journal's span:
    if the second raises, the first is exited before the error goes on — what
    the nested `with`s of the generator version did."""
    from spark_rapids_ml_tpu.utils import profiling

    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(f"> {self.name}")

        def __exit__(self, exc_type, exc, tb):
            log.append(f"< {self.name} ({exc_type.__name__ if exc_type else None})")

    @contextlib.contextmanager
    def broken(name):
        raise OSError("journal full")
        yield

    monkeypatch.setattr(profiling.jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(profiling.journal, "span", broken)
    before, stack = _count("probe.unopened"), journal.current()
    with pytest.raises(OSError, match="journal full"):
        with profiling.trace_span("probe.unopened"):
            log.append("body")
    assert log == ["> probe.unopened", "< probe.unopened (OSError)"]
    assert _count("probe.unopened") == before and journal.current() == stack
