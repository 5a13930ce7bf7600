"""Elastic-net logistic fits whose rows and labels stay on the chip: the
daemon's own job object, every proximal-Newton pass after the first from its
pass cache.

The generator drives `serve/daemon.py` `_Job("logreg", d, mesh, {...})` in
process — the object every wire op calls — and not the TCP wire, as
`newton_cached` does for the L2 fit and for its reason: 6.4 GB through a
Python `recv` would put tens of seconds of host-clock noise into `setup_s`,
and in the window no op carries a row.

*Set-up.* The seeded rows and their labels are made on the device batch by
batch (`harness/logreg_en_data.py`: the logistic deployment's rows, w* on a
tenth of the columns), fetched, the labels validated as the daemon's `feed`
op validates them, and fed through `_Job.fold` as partitioned feeds, then
`commit`ted; the job keeps what its fold placed — rows, mask, label column —
as its cached pass. The dense start iterate comes from the seed and is
installed with `set_iterate`. One whole fit is the warm-up; the iterate its first
boundary leaves is read to the host for the comparison.

*Window.* Fits back to back, closed loop at the device's pace. A fit =
`set_iterate(start)` → `max_iter` × (`rescan` → `step` with the
configuration's `reg` and `elastic_net`) → (w, b) read to the host; no
further scan. The fold is `newton_cached`'s (`logreg.streaming_update_group`,
the configuration's `fold_program`); the boundary is the proximal-Newton
step (`logreg.prox_newton_step`, the configuration's `prox_program`), whose
inner solve runs to a tolerance: 0.8–2.1 ms of a ~66 ms pass on a v5e, so
the passes are nearly equal work and the median-pass metrics read them.
`obs.passes` gets `max_iter` entries a fit, each from before `rescan` until
`step` has returned; `obs.notes["prox_steps"]` holds every step's host
interval and its products with the Hessian, and `obs.notes["traced"]` the
interval the profiler traced (monotonic clock), for `prox_step_roofline`.

*Outside the window.* The job is dropped (its cache freed), the same
batches are made again on the device and the plain reference
(`reference/logreg_en.py`) runs over them: its first pass from the common
start and the exact first boundary from it (against the warm-up's first
step: where the support moves and the inner solve works hardest), and its
last boundary from the iterate each fit's last pass started at (read
between the fit's last two passes: teacher forcing);
`harness/agree_logreg_en.py` compares every fit.

A program whose logistic estimator has no `elasticNetParam` cannot run this
cell: the generator says so before a byte of data is made.
"""

from __future__ import annotations

import time

import numpy as np

from perf.harness import layout, stats, trace

#: the ledger's names of the fold `rescan` dispatches and of the boundary's
#: step (`models/logistic_regression.py`)
FOLD_FN = "logreg.streaming_update_group"
STEP_FN = "logreg.prox_newton_step"
#: the leaves of a pass's state, in the program's order
STATE = ("gw", "gb", "hww", "hwb", "hbb", "loss", "n")


def _same_as(held, arrays):
    """`held` where every leaf of `arrays` equals it bit for bit, else
    `arrays`: the fits of a window start alike, so their first passes are
    kept once."""
    if held is not None and all(np.array_equal(held[k], arrays[k]) for k in arrays):
        return held
    return arrays


def run(ctx):
    cfg = ctx.config
    # Before a byte of data is made: a program without the elastic-net
    # logistic fit cannot run this cell, and says so at once.
    try:
        from spark_rapids_ml_tpu.models.jobs import job_algorithm
        from spark_rapids_ml_tpu.models.logistic_regression import LogisticRegression

        algorithm = job_algorithm("logreg")
    except (ImportError, ValueError) as e:
        raise RuntimeError(f"this program has no table of job algorithms with "
                           f"'logreg' in it ({e}): the cell prox_newton_cached cannot "
                           "run on it") from e
    if not LogisticRegression().hasParam("elasticNetParam"):
        raise RuntimeError(
            "models/logistic_regression.py `LogisticRegression` has no "
            "`elasticNetParam`: this program fits no elastic-net logistic model, "
            "the cell prox_newton_cached cannot run on it")
    job_params = {"n_classes": 2}
    if not getattr(algorithm, "cacheable_for", lambda _: False)(job_params):
        raise RuntimeError("the binary logistic job keeps no pass cache: the cell "
                           "prox_newton_cached cannot run on it")

    import jax

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh, make_mesh
    from spark_rapids_ml_tpu.serve.daemon import _Job

    p, obs, say = ctx.params, ctx.obs, ctx.say
    if cfg["algo"] != "logreg_en":
        raise KeyError(f"prox_newton_cached has no fit for algo {cfg['algo']!r}")
    data = layout.load_module(ctx.root, "harness", "logreg_en_data")
    agree = layout.load_module(ctx.root, "harness", "agree_logreg_en")
    reference = layout.load_module(ctx.root, "reference", "logreg_en")

    d, max_iter = cfg["n_cols"], cfg["max_iter"]
    reg, alpha = cfg["reg"], cfg["elastic_net"]
    step_params = {"reg": reg, "elastic_net": alpha, "fit_intercept": cfg["fit_intercept"]}
    rows, n_batches, parts = p["batch_rows"], p["cached_batches"], p["partitions"]
    chips = ctx.cell["chips"]
    if n_batches % parts:
        raise ValueError("cached_batches must be a multiple of partitions")
    mesh = (default_mesh() if len(jax.devices()) == chips
            else make_mesh(devices=jax.devices()[:chips]))
    if mesh.shape[DATA_AXIS] != chips:
        raise RuntimeError(f"mesh {dict(mesh.shape)} does not put {chips} chips on 'data'")
    cached_rows = n_batches * rows
    say(f"mesh {dict(mesh.shape)}; a cached pass: {n_batches} batches x {rows} rows = "
        f"{cached_rows} rows, {cached_rows * d * 4 / chips / 1e9:.2f} GB on each chip; "
        f"a fit: {max_iter} proximal-Newton passes, reg {reg:g}, elastic_net {alpha:g}")

    with config.option("daemon_pass_cache_mb", int(cfg["daemon_pass_cache_mb"])):
        job = _Job("logreg", d, mesh, job_params)  # reads its budget when made
    planted = data.spec(ctx.seed, d)
    start = data.start_iterate(ctx.seed, planted)
    job.set_iterate(start, 0)
    per_part = n_batches // parts
    for i in range(n_batches):
        x, y = (np.asarray(a) for a in data.device_rows(planted, ctx.seed, i, rows))
        algorithm.check_labels(job_params, y)  # as `feed` does before the job sees them
        job.fold(x, y, partition=i // per_part, pass_id=0)
        if (i + 1) % per_part == 0:
            job.commit(i // per_part, pass_id=0)
    del x, y
    ack = job.cache_ack()
    if not ack.get("cached") or ack["cached_rows"] != cached_rows:
        raise RuntimeError(f"the job did not keep the pass it was fed: {ack} "
                           f"(budget {cfg['daemon_pass_cache_mb']} MiB a device)")
    ctx.stage(f"{n_batches} batches and their labels made on the device, fetched, fed and "
              f"committed in {parts} partitions; the job holds {job.pass_cache_bytes} "
              f"bytes a device; w* planted on {len(planted['support'])} columns")

    held = {"pass0": None}
    prox_steps = []

    def one_fit(index: int):
        """→ (passes, model): the timed passes, and what the comparison
        reads — device references until the fit is over, so that no pass
        waits for a copy it does not need."""
        passes, counted, first, infos, first_step = [], [], None, [], None
        with ctx.span("set_iterate"):
            job.set_iterate(start, job.iteration + 1)
        for it in range(max_iter):
            if it == max_iter - 1:
                with ctx.span("model_read"):  # where the last pass starts, between passes
                    before_last = job.get_iterate()[0]
            begin = time.monotonic()
            with ctx.span("rescan"):
                job.rescan(job.iteration)
            state = job.peek_pass_state()[0]
            scanned = time.monotonic()
            with ctx.span("boundary"):
                infos.append(job.step(step_params))
            end = time.monotonic()
            if index < 0 and it == 0:  # the warm-up alone: nothing is read in the window
                iterate = job.get_iterate()[0]
                first_step = {"w": np.asarray(iterate["w"]),
                              "b": float(np.asarray(iterate["b"]).reshape(-1)[0])}
            passes.append({"fit": index, "pass": it, "rows": cached_rows, "start": begin,
                           "scanned": scanned, "end": end})
            prox_steps.append([scanned, end, infos[-1].get("prox_products")])
            counted.append(state[STATE.index("n")])
            first = state if first is None else first
        with ctx.span("model_read"):
            iterate = job.get_iterate()[0]
            held["pass0"] = _same_as(held["pass0"], {
                name: np.asarray(leaf) for name, leaf in zip(STATE, first)})
            model = {
                "w": np.asarray(iterate["w"]),
                "b": float(np.asarray(iterate["b"]).reshape(-1)[0]),
                "loss": float(infos[-1]["loss"]),
                "pass_rows": [float(np.asarray(n)) for n in counted],
                "pass0": held["pass0"],
                "before_last": {"w": np.asarray(before_last["w"]),
                                "b": float(np.asarray(before_last["b"]).reshape(-1)[0])},
                "delta": [float(info["delta"]) for info in infos],
                "nonzero": [info.get("nonzero") for info in infos],
                "products": [info.get("prox_products") for info in infos],
                "first_step": first_step,
            }
        return passes, model

    _, warm = one_fit(-1)  # every program and every argument sharding a fit meets
    obs.spans.clear()
    prox_steps.clear()
    ctx.stage("one whole fit as warm-up; by pass, the step's length, the nonzero "
              "coefficients and the inner solve's products with the Hessian: " + ", ".join(
                  f"{i + 1}: {warm['delta'][i]:.3g} {warm['nonzero'][i]} "
                  f"{warm['products'][i]}" for i in range(max_iter)))

    # The traced part is the window's LAST seconds: stopping the profiler
    # takes host seconds, and so runs on after the window has closed
    # instead of inside it.
    trace_s = min(p["trace_s"], ctx.seconds / 2)
    tracer = trace.TraceWindow(ctx.trace, max(0.0, ctx.seconds - trace_s - 0.5), trace_s,
                               ctx.out_dir)
    begin = ctx.begin_window()
    deadline = obs.window[1]
    ops_per_fit = 1 + 2 * max_iter + 1  # set_iterate, rescans and steps, the read
    with tracer:
        index = 0
        while time.monotonic() < deadline:
            passes, model = one_fit(index)
            obs.attempted += ops_per_fit
            obs.passes += passes
            obs.fits.append({"fit": index, "rows": cached_rows * max_iter,
                             "end": time.monotonic(), "model": model})
            index += 1
    ctx.end_window()
    say(f"window closed after {time.monotonic() - begin:.2f} s: {len(obs.fits)} fits, "
        f"{len(obs.passes)} passes")
    obs.trace = tracer.reduced(obs.spans)
    obs.notes["prox_steps"] = prox_steps
    if tracer.traced is not None:
        obs.notes["traced"] = [t - tracer.wall_minus_mono for t in tracer.traced]
    stats.say_passes(obs.passes, deadline, say, obs.trace)
    # A fold program's rows, as the program counted them: `rescan` folds its
    # cached batches a group a dispatch, and the group is the program's to choose.
    folded = obs.counter_delta("srml_daemon_pass_rows_total", source="cache")
    refed = obs.counter_delta("srml_daemon_pass_rows_total", source="wire")
    dispatched = obs.counter_delta("srml_xla_calls_total", fn=FOLD_FN)
    if dispatched > 0:
        obs.fold_rows_per_chip = int(round(folded / dispatched)) // chips
        say(f"a fold program folds {obs.fold_rows_per_chip * chips} rows "
            f"({obs.fold_rows_per_chip * chips // rows} cached batches a dispatch)")
    if folded + refed > 0:
        say(f"rows folded in the window: {folded:.0f} from the cache, {refed:.0f} from "
            f"the wire ({100.0 * folded / (folded + refed):.6g}% cached)")
    steps = obs.counter_delta("srml_xla_calls_total", fn=STEP_FN)
    if steps > 0:
        say(f"proximal-Newton steps in the window: {steps:.0f}, "
            f"{obs.counter_delta('srml_logreg_prox_iterations_total') / steps:.4g} "
            "products with the Hessian a step")
    if obs.fits:
        last = obs.fits[-1]["model"]
        say("the last fit, by pass: nonzero coefficients " + ", ".join(
            str(n) for n in last["nonzero"]) + "; products " + ", ".join(
            str(n) for n in last["products"]))
        ends = sorted({int(f["model"]["nonzero"][-1]) for f in obs.fits
                       if f["model"]["nonzero"][-1] is not None})
        say(f"nonzero coefficients at the end of a fit: {ends} of {d} "
            f"(w* planted on {len(planted['support'])})")

    # Outside the window: free the program's rows, make them again, and run
    # the plain reference over them: its first pass from the common start,
    # and its last boundary from each iterate a fit's last pass started at.
    job.release()
    del job
    batches = [data.device_rows(planted, ctx.seed, i, rows) for i in range(n_batches)]
    pass0_ref = reference.scan(batches, start["w"], float(start["b"][0]))
    first_ref = reference.first_step(pass0_ref, start, reg, alpha, cfg["fit_intercept"])
    last_refs = {}

    def last_step_of(model):
        before = model["before_last"]
        key = (before["w"].tobytes(), before["b"])
        if key not in last_refs:
            last_refs[key] = reference.last_step(batches, before["w"], before["b"], reg,
                                                 alpha, cfg["fit_intercept"])
        return last_refs[key]

    tol = cfg["tolerances"]
    problems = agree.check_fits(obs.fits, pass0_ref, last_step_of, tol,
                                cfg["support_margin"], cached_rows, say)
    first_compared, first_problems = agree.check_first_step(warm["first_step"], first_ref,
                                                            start, tol)
    problems += first_problems
    say(f"the first boundary from the start: {agree.FIRST} {first_compared[0]:.3e} "
        f"(limit {first_compared[1]}); nonzero coefficients "
        f"{np.count_nonzero(warm['first_step']['w'])}, the reference's "
        f"{np.count_nonzero(first_ref['w'])}")
    del batches
    say(f"the reference's last boundary from {len(last_refs)} distinct iterate(s): "
        "nonzero coefficients " + ", ".join(
            str(int(np.count_nonzero(ref["w"]))) for ref in last_refs.values()))
    if refed:
        problems.append(f"{refed:.0f} rows were fed again inside the window")
    if not any(pa["end"] <= deadline for pa in obs.passes):
        problems.append("no pass completed inside the window")
    obs.compared = {**agree.compared(obs.fits, tol, cached_rows),
                    agree.FIRST: first_compared,
                    "rows_refed_in_window": [float(refed), 0.0]}
    for problem in problems[:20]:
        say(f"  DISAGREES: {problem}")
    if problems:
        obs.correct = False
    for fit in obs.fits:
        fit.pop("model", None)
    return obs
