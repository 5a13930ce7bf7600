"""The finalize, opened: `eig finalize` and its child spans in all three
sinks of `trace_span` — the journal (ring), the phase histogram and the
profile a `jax.profiler` trace records — and the host path's results held
to the way they were computed before it solved for k components only."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models.pca import finalize_pca_stats, fit_pca
from spark_rapids_ml_tpu.parallel.mesh import make_mesh
from spark_rapids_ml_tpu.utils import journal, metrics

HOST_CHILDREN = ["finalize.wait", "finalize.fetch", "finalize.center",
                 "finalize.lapack", "finalize.post"]
D, K, ROWS = 48, 5, 300


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(devices=jax.devices()[:1])


@pytest.fixture()
def state(rng):
    """A float32 (count, colsum, gram) on the device, as a fold leaves it."""
    x = (rng.normal(size=(ROWS, D)) * np.linspace(3.0, 0.5, D)).astype(np.float32)
    return (jnp.float32(ROWS), jnp.asarray(x.sum(axis=0)), jnp.asarray(x.T @ x))


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


def test_host_finalize_emits_the_parent_and_exactly_its_five_children(state, mesh1, ring):
    with config.option("finalize", "host"):
        finalize_pca_stats(state, K, True, mesh1, ROWS)
    events = _phases(ring)
    parent = [e for e in events if e["name"] == "eig finalize"]
    assert len(parent) == 1
    parent = parent[0]
    children = [e for e in events if e["name"] != "eig finalize"]
    assert [e["name"] for e in children] == HOST_CHILDREN  # in the order they ran
    assert {e["run_id"] for e in events} == {parent["run_id"]}
    assert all(e["parent_id"] == parent["span_id"] for e in children)
    # each child lies inside the parent on the journal's clock (ts is
    # time.time(), the duration perf_counter: allow the clocks 1 ms)
    for e in children:
        assert e["ts"] >= parent["ts"] - 1e-3
        assert e["ts"] + e["duration_s"] <= parent["ts"] + parent["duration_s"] + 1e-3
    assert sum(e["duration_s"] for e in children) <= parent["duration_s"]


def test_fit_pca_gets_the_same_children_from_the_same_helper(rng, mesh1, ring):
    x = rng.normal(size=(64, 8)).astype(np.float32)
    with config.option("finalize", "host"):
        fit_pca(x, 2, mesh=mesh1)
    events = _phases(ring)
    parent = [e for e in events if e["name"] == "eig finalize"][0]
    assert [e["name"] for e in events if e["parent_id"] == parent["span_id"]] == HOST_CHILDREN


@pytest.mark.parametrize("solver", ["full", "randomized"])
def test_device_finalize_emits_one_child(state, mesh1, ring, solver):
    with config.option("finalize", "device"):
        finalize_pca_stats(state, K, True, mesh1, ROWS, solver=solver)
    events = _phases(ring)
    parent = [e for e in events if e["name"] == "eig finalize"][0]
    children = [e for e in events if e["parent_id"] == parent["span_id"]]
    assert [e["name"] for e in children] == ["finalize.device"]
    assert not any(e["name"] in HOST_CHILDREN for e in events)


def test_without_a_ring_or_a_path_no_event_is_made_but_the_histogram_counts(
        state, mesh1, monkeypatch):
    monkeypatch.setattr(journal, "_ring_arms", 0)  # whatever an earlier test left armed
    seq = journal.last_seq()
    before = {name: _count(name) for name in HOST_CHILDREN + ["eig finalize"]}
    with config.option("finalize", "host"), config.option("run_journal", None):
        assert not journal.active()
        finalize_pca_stats(state, K, True, mesh1, ROWS)
    assert journal.tail() == ([], seq)  # nothing buffered, no sequence number spent
    assert {name: _count(name) - n for name, n in before.items()} == {
        name: 1 for name in before}


@pytest.mark.parametrize("mean_center", [True, False])
def test_host_path_outputs_agree_with_the_old_computation(state, mesh1, mean_center):
    """The old computation: an outer product subtracted out of place, the
    full-spectrum `eigh`, sign flip over all d columns, then the slice. An
    in-place rank-1 update may round the last bit otherwise (FMA) and another
    LAPACK route does: float64 rounding is what may differ, nothing more."""
    count, colsum, gram = state
    with config.option("finalize", "host"):
        sol = finalize_pca_stats(state, K, mean_center, mesh1, ROWS)
    n = max(float(np.asarray(count)), 1.0)
    cs = np.asarray(colsum, dtype=np.float64)
    g = np.asarray(gram, dtype=np.float64)
    mean = cs / n
    if mean_center:
        g = g - np.outer(mean, cs)
    w, v = np.linalg.eigh(g)
    w, v = w[::-1], v[:, ::-1]
    idx = np.argmax(np.abs(v), axis=0)
    v = v * np.where(v[idx, np.arange(D)] < 0, -1.0, 1.0)
    s = np.sqrt(np.clip(w, 0, None))
    ev = s / s.sum()
    assert sol.pc.shape == (D, K) and sol.pc.dtype == np.float64
    # same sign, so the plain dot product: 1 - cos <= 1e-12 a component
    assert np.all(np.sum(sol.pc * v[:, :K], axis=0) >= 1 - 1e-12)
    assert np.allclose(sol.sigma, s, rtol=0, atol=1e-12 * s[0]) and sol.sigma.shape == (D,)
    assert np.allclose(sol.explained_variance, ev[:K], rtol=1e-12, atol=0)
    assert np.allclose(sol.mean, mean, rtol=1e-12, atol=0)
    assert sol.n_rows == ROWS


def test_finalize_does_not_copy_the_state_outside_its_span(mesh1, ring):
    """A host copy made before `eig finalize` opens would wait for the
    owed folds outside every span: the width is read from the shape."""

    class NoCopy:
        shape = (D,)

        def __array__(self, *a, **k):
            raise AssertionError("the column sums were copied to the host")

    with pytest.raises(ValueError, match="out of range"):
        finalize_pca_stats((None, NoCopy(), None), D + 1, True, mesh1, ROWS)
    assert _phases(ring) == []


def test_the_spans_are_in_a_recorded_profile_on_the_journals_clock(state, mesh1, ring,
                                                                   tmp_path):
    """`trace_span` opens its TraceAnnotation whatever config `tracing`
    says, so a profile holds the program's spans on a host plane; and the
    journal's `ts` is on the profiler's host clock (CLOCK_REALTIME):
    `ts − profile_start_time` is the span's position in the profile."""
    assert not config.get("tracing")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    with jax.profiler.trace(str(tmp_path), profiler_options=options), \
            config.option("finalize", "host"):
        finalize_pca_stats(state, K, True, mesh1, ROWS)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    start_ns, found = None, {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats)["profile_start_time"]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "eig finalize" or ev.name.startswith("finalize."):
                        found[ev.name] = ev
    assert start_ns is not None
    assert set(found) == set(HOST_CHILDREN) | {"eig finalize"}
    in_journal = {e["name"]: e for e in _phases(ring)}
    for name in ("finalize.lapack", "eig finalize"):
        in_profile = (start_ns + found[name].start_ns) * 1e-9
        assert abs(in_profile - in_journal[name]["ts"]) < 5e-3
        assert abs(found[name].duration_ns * 1e-9
                   - in_journal[name]["duration_s"]) < 5e-3
