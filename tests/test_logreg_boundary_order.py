"""The logistic pass boundary dispatches before it reads: `step` queues the
step program and the next pass's zero state behind the pass's folds, waits
for the folds with no transfer, and then reads the loss and the step's
scalars in two transfers. The order is all that changed: the model, the
loss and every step's length are what the order read-then-dispatch gave,
bit for bit — binary Newton, multinomial MM-Newton (three classes) and the
elastic-net proximal-Newton step alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.models import logistic_regression as lg
from spark_rapids_ml_tpu.serve.daemon import _Job
from spark_rapids_ml_tpu.utils import metrics as metrics_mod

D = 12
ROWS = 700
#: job params, step params
KINDS = {
    "binary": ({}, {"reg": 1e-2, "fit_intercept": True}),
    "multinomial": ({"n_classes": 3}, {"reg": 1e-2, "fit_intercept": True}),
    "elastic_net": ({}, {"reg": 0.05, "fit_intercept": True, "elastic_net": 0.5}),
}
STEP_FNS = ("_stream_newton_step_fn", "_stream_multinomial_step_fn",
            "_stream_prox_newton_step_fn")
OBJECTIVES = ("stream_objective", "stream_softmax_objective",
              "stream_elastic_net_objective")
ZERO_STATES = ("stream_zero_state", "stream_softmax_zero_state")


def _cached_job(mesh, kind, seed=4):
    """A logistic job that holds its first pass in its pass cache, fed in
    two batches from a seeded start."""
    job_params, _ = KINDS[kind]
    classes = job_params.get("n_classes", 2)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, D)).astype(np.float32)
    w = rng.normal(size=(D, classes)) * 1.5 / np.sqrt(D)
    y = np.argmax(x @ w + rng.gumbel(size=(ROWS, classes)), axis=1).astype(np.float64)
    with config.option("daemon_pass_cache_mb", 8):
        job = _Job("logreg", D, mesh, job_params)
    if classes > 2:
        start = {"w": rng.normal(size=(D, classes)) * 0.1, "b": rng.normal(size=classes) * 0.05}
    else:
        start = {"w": rng.normal(size=D) * 0.1, "b": np.asarray([0.05])}
    job.set_iterate(start, 0)
    for lo, hi in ((0, 400), (400, ROWS)):
        job.fold(x[lo:hi], y[lo:hi], pass_id=0)
    assert job.cache_ack() == {"cached": True, "cached_rows": ROWS}
    return job


def _parent_boundary(kind, state, w, b, params, ad):
    """The boundary as the order read-then-dispatch computed it, rebuilt
    from the step programs and the eager objective: the loss read first,
    term by term, then the step → (new_w, new_b, fields)."""
    gw, gb, hww, hwb, hbb, lsum, n = state
    reg, fit_intercept = params["reg"], params["fit_intercept"]
    data = float(lsum / jnp.maximum(n, 1.0))
    if kind == "elastic_net":
        alpha = params["elastic_net"]
        l1, l2 = lg.elastic_net_penalties(reg, alpha)
        loss = data + 0.5 * float(l2) * float(jnp.sum(w * w)) + l1 * float(jnp.sum(jnp.abs(w)))
        step = lg._stream_prox_newton_step_fn(reg, alpha, fit_intercept, ad)
        new_w, new_b, delta, products, nonzero = step(gw, gb, hww, hwb, hbb, n, w, b)
        return new_w, new_b, {"loss": loss, "delta": float(delta),
                              "prox_products": int(products), "nonzero": int(nonzero)}
    loss = data + 0.5 * float(reg) * float(jnp.sum(w * w))
    factory = lg._stream_multinomial_step_fn if kind == "multinomial" else lg._stream_newton_step_fn
    new_w, new_b, delta = factory(reg, fit_intercept, ad)(gw, gb, hww, hwb, hbb, n, w, b)
    return new_w, new_b, {"loss": loss, "delta": float(delta)}


def _counter(**labels):
    samples = (metrics_mod.snapshot().get("srml_logreg_next_state_total") or {}).get(
        "samples", [])
    return sum(s["value"] for s in samples
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@pytest.mark.parametrize("kind", list(KINDS))
def test_three_boundaries_are_the_parents_bit_for_bit(mesh8, kind):
    job = _cached_job(mesh8, kind)
    _, params = KINDS[kind]
    algo = job.algorithm
    for it in range(3):
        if it:
            job.rescan(job.iteration)
        state = job.peek_pass_state()[0]
        want_w, want_b, want = _parent_boundary(kind, state, algo.w, algo.b, params,
                                                algo.accum.name)
        info = job.step(params)
        assert {k: info[k] for k in want} == want
        assert all(type(info[k]) is type(v) for k, v in want.items())
        np.testing.assert_array_equal(np.asarray(algo.w), np.asarray(want_w))
        np.testing.assert_array_equal(np.asarray(algo.b), np.asarray(want_b))
    assert {info["iteration"], info["pass_rows"]} == {3, ROWS}


@pytest.fixture
def calls(monkeypatch):
    """Records, in order, the module's step programs called, its objective
    helpers entered, its zero states made, the host's waits, and every
    device→host read: one `device_get` each, and an implicit read of an
    array (`float()`, `np.asarray`) outside one."""
    log = []

    def logged(fn, tag):
        def wrapper(*args, **kwargs):
            log.append(tag)
            return fn(*args, **kwargs)
        return wrapper

    for name in STEP_FNS:
        factory = getattr(lg, name)
        monkeypatch.setattr(lg, name, lambda *a, _f=factory: logged(_f(*a), "step"))
    for name in OBJECTIVES:
        monkeypatch.setattr(lg, name, logged(getattr(lg, name), "objective"))
    for name in ZERO_STATES:
        monkeypatch.setattr(lg, name, logged(getattr(lg, name), "zero_state"))
    monkeypatch.setattr(jax, "block_until_ready",
                        logged(jax.block_until_ready, "wait"))

    inside = []
    real_get, real_value = jax.device_get, ArrayImpl._value

    def device_get(tree):
        log.append("read")
        inside.append(True)
        try:
            return real_get(tree)
        finally:
            inside.pop()

    def value(self):
        if not inside:
            log.append("implicit read")
        return real_value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(value))
    return log


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_step_is_dispatched_before_the_first_read_and_two_transfers_follow(
        mesh8, calls, kind):
    job = _cached_job(mesh8, kind)
    _, params = KINDS[kind]
    for it in range(2):
        if it:
            job.rescan(job.iteration)
        calls.clear()
        job.step(params)
        # the step and the next pass's zeros queue behind the folds; the
        # host then waits with no transfer, and reads twice: the loss's
        # terms, the step's scalars
        assert calls == ["step", "zero_state", "wait", "objective", "read", "read"]


@pytest.mark.parametrize("kind", ["binary", "multinomial"])
def test_the_next_pass_state_is_made_ahead_once_and_then_on_demand(mesh8, kind):
    job = _cached_job(mesh8, kind)
    _, params = KINDS[kind]
    algo = job.algorithm
    zeros = algo.zero_state()
    kept = job.peek_pass_state()[0]
    kept_values = [np.asarray(a).copy() for a in jax.device_get(kept)]
    ahead, on_demand = _counter(made="ahead"), _counter(made="on_demand")

    algo.step(kept, params)
    stage = algo.zero_state()  # a stage's zeros leave the stash where it is
    first = algo.next_pass_state()
    assert (_counter(made="ahead"), _counter(made="on_demand")) == (ahead + 1, on_demand)
    second = algo.next_pass_state()
    assert (_counter(made="ahead"), _counter(made="on_demand")) == (ahead + 1, on_demand + 1)
    for made in (stage, first, second):
        assert [(a.shape, a.dtype) for a in made] == [(z.shape, z.dtype) for z in zeros]
        assert all(not np.any(np.asarray(a)) for a in made)
        assert all(not a.committed for a in made)
    pointers = [{a.unsafe_buffer_pointer() for a in made} for made in (stage, first, second)]
    assert all(len(p) == len(zeros) for p in pointers)  # no leaf shares a buffer
    assert not (pointers[0] & pointers[1] or pointers[0] & pointers[2]
                or pointers[1] & pointers[2])
    # nothing donated the finished pass's state: a caller that kept it reads it
    for was, leaf in zip(kept_values, kept):
        np.testing.assert_array_equal(np.asarray(leaf), was)


@pytest.mark.parametrize("kind", list(KINDS))
def test_every_boundary_of_a_job_opens_its_next_pass_from_the_state_made_ahead(mesh8, kind):
    job = _cached_job(mesh8, kind)
    _, params = KINDS[kind]
    ahead, on_demand = _counter(made="ahead"), _counter(made="on_demand")
    for it in range(3):
        if it:
            job.rescan(job.iteration)
        job.step(params)
    assert _counter(made="ahead") - ahead == 3
    assert _counter(made="on_demand") == on_demand
