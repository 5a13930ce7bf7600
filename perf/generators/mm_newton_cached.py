"""Multinomial logistic fits whose rows and class labels stay on the chip:
the daemon's own job object, every MM-Newton pass after the first from its
pass cache.

The generator drives `serve/daemon.py` `_Job("logreg", d, mesh,
{"n_classes": C})` in process — the object every wire op calls — and not
the TCP wire, as `newton_cached` does for the binary job and for its
reason: 6.4 GB through a Python `recv` would put tens of seconds of
host-clock noise into `setup_s`, and in the window no op carries a row.

*Set-up.* The seeded rows and their labels are made on the device batch by
batch (`harness/logreg_mn_data.py`), fetched, the labels validated as the
daemon's `feed` op validates them, and fed through `_Job.fold` as
partitioned feeds, then `commit`ted; the job keeps what its fold placed —
rows, mask, label column — as its cached pass. The start iterate comes from
the seed and is installed with `set_iterate`. One whole fit is the warm-up.

*Window.* Fits back to back, closed loop at the device's pace. A fit =
`set_iterate(start)` → `max_iter` × (`rescan` → `step`) → (W, b) read to the
host; no further scan. A `rescan` folds the cached batches a group a
dispatch (`serve/daemon.py` `_RESCAN_GROUP`), batch by batch inside the
program; a "fold" in this cell's `pass_fold_device_ms` and
`pass_fold_roofline` is one such program, and `obs.fold_rows_per_chip` its
rows. `obs.passes` gets `max_iter` entries a fit, each from before `rescan`
until `step` has returned: the boundary and its C solves are in
`pass_rows_per_s`.

*Outside the window.* The job is dropped (its cache freed), the same
batches are made again on the device and the plain reference runs over them:
its first pass from the common start, and its last pass from the iterate
each fit's last pass started at (read between the fit's last two passes);
`harness/agree_logreg_mn.py` compares every fit.

A program whose multinomial job keeps no pass (`cacheable_for` its params
is False) cannot run this cell: the generator says so before a byte of data
is made.
"""

from __future__ import annotations

import time

import numpy as np

from perf.harness import layout, stats, trace

#: the ledger's name of the program `rescan` dispatches
#: (`models/logistic_regression.py` `_stream_softmax_stats_group_fn`); the
#: configuration's `fold_program` is its name in a trace
FOLD_FN = "logreg.softmax_streaming_update_group"
#: the leaves of a pass's state, in the program's order
STATE = ("gw", "gb", "hw", "hwb", "hbb", "loss", "n")


def _same_as(held, arrays):
    """`held` where every leaf of `arrays` equals it bit for bit, else
    `arrays`: the fits of a window start alike, so their first passes are
    kept once."""
    if held is not None and all(np.array_equal(held[k], arrays[k]) for k in arrays):
        return held
    return arrays


def run(ctx):
    cfg = ctx.config
    # Before a byte of data is made: a program whose logistic job keeps no
    # cached pass for these classes cannot run this cell, and says so at once.
    job_params = {"n_classes": int(cfg["n_classes"])}
    try:
        from spark_rapids_ml_tpu.models.jobs import job_algorithm

        algorithm = job_algorithm("logreg")
    except (ImportError, ValueError) as e:
        raise RuntimeError(f"this program has no table of job algorithms with "
                           f"'logreg' in it ({e}): the cell mm_newton_cached cannot "
                           "run on it") from e
    cacheable_for = getattr(algorithm, "cacheable_for", None)
    if cacheable_for is None or not cacheable_for(job_params):
        raise RuntimeError(
            "models/logistic_regression.py `LogisticRegressionJob` is not "
            f"`cacheable_for` {job_params}: this program keeps no pass cache for "
            "the multinomial job, the cell mm_newton_cached cannot run on it")

    import jax

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh, make_mesh
    from spark_rapids_ml_tpu.serve.daemon import _Job

    p, obs, say = ctx.params, ctx.obs, ctx.say
    if cfg["algo"] != "logreg_mn":
        raise KeyError(f"mm_newton_cached has no fit for algo {cfg['algo']!r}")
    data = layout.load_module(ctx.root, "harness", "logreg_mn_data")
    agree = layout.load_module(ctx.root, "harness", "agree_logreg_mn")
    reference = layout.load_module(ctx.root, "reference", "logreg_mn")

    d, n_classes, max_iter = cfg["n_cols"], job_params["n_classes"], cfg["max_iter"]
    step_params = {"reg": cfg["reg"], "fit_intercept": cfg["fit_intercept"]}
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
        f"a fit: {max_iter} MM-Newton passes over {n_classes} classes, "
        f"reg {cfg['reg']:g}")

    with config.option("daemon_pass_cache_mb", int(cfg["daemon_pass_cache_mb"])):
        job = _Job("logreg", d, mesh, job_params)  # reads its budget when made
    planted = data.spec(ctx.seed, d, n_classes)
    start = data.start_iterate(ctx.seed, planted)
    job.set_iterate(start, 0)
    per_part = n_batches // parts
    shares = np.zeros(n_classes)
    for i in range(n_batches):
        x, y = (np.asarray(a) for a in data.device_rows(planted, ctx.seed, i, rows))
        algorithm.check_labels(job_params, y)  # as `feed` does before the job sees them
        shares += np.bincount(y.astype(np.int64), minlength=n_classes)
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
              "bytes a device; class shares " + ", ".join(
                  f"{s / cached_rows:.4f}" for s in shares))

    held = {"pass0": None}

    def one_fit(index: int):
        """→ (passes, model): the timed passes, and what the comparison
        reads — device references until the fit is over, so that no pass
        waits for a copy it does not need."""
        passes, counted, first, infos = [], [], None, []
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
            passes.append({"fit": index, "pass": it, "rows": cached_rows, "start": begin,
                           "scanned": scanned, "end": time.monotonic()})
            counted.append(state[STATE.index("n")])
            first = state if first is None else first
        with ctx.span("model_read"):
            iterate = job.get_iterate()[0]
            held["pass0"] = _same_as(held["pass0"], {
                name: np.asarray(leaf) for name, leaf in zip(STATE, first)})
            model = {
                "w": np.asarray(iterate["w"]),
                "b": np.asarray(iterate["b"]).reshape(-1),
                "loss": float(infos[-1]["loss"]),
                "pass_rows": [float(np.asarray(n)) for n in counted],
                "pass0": held["pass0"],
                "before_last": {"w": np.asarray(before_last["w"]),
                                "b": np.asarray(before_last["b"]).reshape(-1)},
                "delta": [float(info["delta"]) for info in infos],
            }
        return passes, model

    _, warm = one_fit(-1)  # every program and every argument sharding a fit meets
    obs.spans.clear()
    ctx.stage("one whole fit as warm-up; the MM step's length, by pass: " + ", ".join(
        f"{i + 1}: {warm['delta'][i]:.3g}" for i in range(max_iter)))

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

    # Outside the window: free the program's rows, make them again, and run
    # the plain reference over them: its first pass from the common start,
    # and its last pass from each iterate a fit's last pass started at
    # (teacher forcing; the window's fits reach one and the same, as a rule).
    job.release()
    del job
    batches = [data.device_rows(planted, ctx.seed, i, rows) for i in range(n_batches)]
    pass0_ref = reference.scan(batches, start["w"], start["b"])
    last_refs = {}

    def last_pass_of(model):
        before = model["before_last"]
        key = (before["w"].tobytes(), before["b"].tobytes())
        if key not in last_refs:
            last_refs[key] = reference.one_pass(batches, before, cfg["reg"],
                                                cfg["fit_intercept"])
        return last_refs[key]

    tol = cfg["tolerances"]
    problems = agree.check_fits(obs.fits, pass0_ref, last_pass_of, tol, cached_rows, say)
    del batches
    say(f"the reference's last pass from {len(last_refs)} distinct iterate(s): step "
        + ", ".join(f"{ref['delta']:.4g}" for ref in last_refs.values()))
    if refed:
        problems.append(f"{refed:.0f} rows were fed again inside the window")
    if not any(pa["end"] <= deadline for pa in obs.passes):
        problems.append("no pass completed inside the window")
    obs.compared = {**agree.compared(obs.fits, tol, cached_rows),
                    "rows_refed_in_window": [float(refed), 0.0]}
    for problem in problems[:20]:
        say(f"  DISAGREES: {problem}")
    if problems:
        obs.correct = False
    for fit in obs.fits:
        fit.pop("model", None)
    return obs
