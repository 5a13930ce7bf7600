"""KMeans fits whose rows stay on the chip: the daemon's own job object,
every pass after the first from its pass cache.

The generator drives `serve/daemon.py` `_Job("kmeans", d, mesh, {"k": k})`
in process — the object every wire op calls — and not the TCP wire: 6.4 GB
through a Python `recv` would put tens of seconds of host-clock noise into
`setup_s`, and in the window no op carries a row (PERF.md §4).

*Set-up.* The seeded rows are made on the device batch by batch, fetched,
and fed through `_Job.fold` as partitioned feeds, then `commit`ted (the pad
copy, the put and the staging run as for a Spark task); the job keeps what
it placed as its cached pass. The starting centres come from the seed
(`harness/kmeans_data.start_centres`) and are installed with `set_iterate`.
One whole fit is the warm-up.

*Window.* Fits back to back, closed loop at the device's pace. A fit =
`set_iterate(start)` → `max_iter` × (`rescan` → `step`) → `rescan` for the
cost → the centres and the cost read to the host. A `rescan` folds the cached
batches a group a dispatch (`serve/daemon.py` `_RESCAN_GROUP`), batch by batch
inside the program; a "fold" in this cell's `pass_fold_device_ms` and
`pass_fold_roofline` is one such program, and `obs.fold_rows_per_chip` its
rows. `obs.passes` gets `max_iter` + 1 entries a fit: a Lloyd pass from
before `rescan` until `step` has returned, the cost scan until the cost is
on the host; `scanned` is when `rescan` had returned. `pass_rows_per_s` is
taken over all their seconds, `median_pass_rows_per_s` and `late_pass_share`
(the run's `a pass:` and `late:` lines) stand beside it.

*Outside the window.* The job is dropped (its cache freed), the same rows
are made again on the device and the plain reference runs over them from
the same start; `harness/agree_kmeans.py` compares every fit.
"""

from __future__ import annotations

import time

import numpy as np

from perf.harness import layout, stats, trace

#: the ledger's name of the program `rescan` dispatches (`models/kmeans.py`
#: `_stream_group_fn`); the configuration's `fold_program` is its name in a trace
FOLD_FN = "kmeans.streaming_update_group"


def run(ctx):
    import jax

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh, make_mesh
    from spark_rapids_ml_tpu.serve.daemon import _Job

    # Before a byte of data is made: a program without the pass cache
    # cannot run this cell, and says so at once.
    if not hasattr(_Job, "rescan"):
        raise RuntimeError(
            "serve/daemon.py `_Job` has no `rescan`: this program keeps no pass "
            "cache, the cell lloyd_cached cannot run on it")

    cfg, p, obs, say = ctx.config, ctx.params, ctx.obs, ctx.say
    if cfg["algo"] != "kmeans":
        raise KeyError(f"lloyd_cached has no fit for algo {cfg['algo']!r}")
    data = layout.load_module(ctx.root, "harness", "kmeans_data")
    agree = layout.load_module(ctx.root, "harness", "agree_kmeans")
    reference = layout.load_module(ctx.root, "reference", "kmeans")

    d, k, max_iter = cfg["n_cols"], cfg["k"], cfg["max_iter"]
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
        f"a fit: {max_iter} Lloyd passes and a cost scan, k = {k}")

    with config.option("daemon_pass_cache_mb", int(cfg["daemon_pass_cache_mb"])):
        job = _Job("kmeans", d, mesh, {"k": k})  # reads its budget when made
    planted = data.spec(ctx.seed, d, k)
    start = None
    per_part = n_batches // parts
    for i in range(n_batches):
        x = np.asarray(data.device_rows(planted, ctx.seed, i, rows))
        if start is None:
            start = data.start_centres(ctx.seed, x, k)
            job.set_iterate({"centers": start}, 0)
        job.fold(x, None, partition=i // per_part, pass_id=0)
        if (i + 1) % per_part == 0:
            job.commit(i // per_part, pass_id=0)
    del x
    ack = job.cache_ack()
    if not ack.get("cached") or ack["cached_rows"] != cached_rows:
        raise RuntimeError(f"the job did not keep the pass it was fed: {ack} "
                           f"(budget {cfg['daemon_pass_cache_mb']} MiB a device)")
    ctx.stage(f"{n_batches} batches made on the device, fetched, fed and committed in "
              f"{parts} partitions; the job holds {job.pass_cache_bytes} bytes a device")

    def one_fit(index: int):
        """→ (passes, model): the timed passes, and what the comparison
        reads — device references until the fit is over, so that no pass
        waits for a copy it does not need."""
        passes, counts, first, moved = [], [], None, []
        with ctx.span("set_iterate"):
            job.set_iterate({"centers": start}, job.iteration + 1)
        for it in range(max_iter):
            begin = time.monotonic()
            with ctx.span("rescan"):
                job.rescan(job.iteration)
            state = job.peek_pass_state()[0]
            scanned = time.monotonic()
            with ctx.span("boundary"):
                moved.append(job.step({})["moved2"])
            passes.append({"fit": index, "pass": it, "rows": cached_rows, "start": begin,
                           "scanned": scanned, "end": time.monotonic()})
            counts.append(state[1])
            first = state if first is None else first
        begin = time.monotonic()
        with ctx.span("rescan"):
            job.rescan(job.iteration)
        scanned = time.monotonic()
        with ctx.span("cost_read"):
            state = job.peek_pass_state()[0]
            cost = float(np.asarray(state[2]))
        passes.append({"fit": index, "pass": max_iter, "rows": cached_rows, "start": begin,
                       "scanned": scanned, "end": time.monotonic()})
        with ctx.span("model_read"):
            centers = np.asarray(job.get_iterate()[0]["centers"])
            counts.append(state[1])
            model = {
                "centers": centers, "cost": cost,
                "pass_counts": [float(np.asarray(c, np.float64).sum()) for c in counts],
                "pass0": {"sums": np.asarray(first[0]), "counts": np.asarray(first[1]),
                          "cost": float(np.asarray(first[2]))},
                "moved2": moved,
            }
        return passes, model

    _, warm = one_fit(-1)  # every program and every argument sharding a fit meets
    obs.spans.clear()
    ctx.stage("one whole fit as warm-up; the farthest a centre moved, by pass: " + ", ".join(
        f"{i + 1}: {warm['moved2'][i] ** 0.5:.3g}" for i in sorted({0, max_iter // 2 - 1, max_iter - 1})))

    # The traced part is the window's LAST seconds: stopping the profiler
    # takes the host seconds (against this loop, over 30 of them), and so
    # runs on after the window has closed instead of inside it.
    trace_s = min(p["trace_s"], ctx.seconds / 2)
    tracer = trace.TraceWindow(ctx.trace, max(0.0, ctx.seconds - trace_s - 0.5), trace_s,
                               ctx.out_dir)
    begin = ctx.begin_window()
    deadline = obs.window[1]
    ops_per_fit = 1 + 2 * max_iter + 2  # set_iterate, rescans and steps, the read
    with tracer:
        index = 0
        while time.monotonic() < deadline:
            passes, model = one_fit(index)
            obs.attempted += ops_per_fit
            obs.passes += passes
            obs.fits.append({"fit": index, "rows": cached_rows * (max_iter + 1),
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
    dispatched = obs.counter_delta("srml_xla_calls_total", fn=FOLD_FN)
    if dispatched > 0:
        obs.fold_rows_per_chip = int(round(folded / dispatched)) // chips
        say(f"a fold program folds {obs.fold_rows_per_chip * chips} rows "
            f"({obs.fold_rows_per_chip * chips // rows} cached batches a dispatch)")

    # Outside the window: free the program's rows, make them again, and run
    # the plain reference over them from the same start.
    job.release()
    del job
    batches = [data.device_rows(planted, ctx.seed, i, rows) for i in range(n_batches)]
    ref = reference.fit(batches, start, max_iter, cfg["tol"])
    del batches
    tol = cfg["tolerances"]
    problems = agree.check_fits(obs.fits, ref, tol, cached_rows, say)
    refed = obs.counter_delta("srml_daemon_pass_rows_total", source="wire")
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
