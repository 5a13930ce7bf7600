"""The fold at the device's pace: no daemon, no wire.

The harness calls the same ledgered functions the daemon's `_Job` holds —
`gram.init_stats`, `gram.streaming_update(mesh)`,
`models.pca.finalize_pca_stats` — on device-resident float32 batches of the
shape the daemon would have put: `global_batch_rows` rows, row-sharded over
the `data` axis of `default_mesh()`, with a mask of ones. A ring of
`ring_batches` distinct batches is made on the device from the seed. One
fit is `folds_per_fit` folds, then `block_until_ready` (the end of the
pass), then finalize. Fits run back to back until the window closes.
"""

from __future__ import annotations

import time
import numpy as np

from perf.harness import agree, data, trace
from perf.reference import pca as ref_pca


def run(ctx):
    import jax

    from spark_rapids_ml_tpu.models.pca import finalize_pca_stats
    from spark_rapids_ml_tpu.ops import gram
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh, make_mesh
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    cfg, p, obs, say = ctx.config, ctx.params, ctx.obs, ctx.say
    if cfg["algo"] != "pca":
        raise KeyError(f"fold_resident has no fold for algo {cfg['algo']!r}")
    d, k = cfg["n_cols"], cfg["k"]
    rows, ring_n, folds = p["global_batch_rows"], p["ring_batches"], p["folds_per_fit"]
    chips = ctx.cell["chips"]
    # Off the chip (the tests' rehearsal) the host shows more devices than
    # the cell asks for; on it, require_device has made them equal.
    mesh = (default_mesh() if len(jax.devices()) == chips
            else make_mesh(devices=jax.devices()[:chips]))
    if mesh.shape[DATA_AXIS] != chips:
        raise RuntimeError(f"mesh {dict(mesh.shape)} does not put {chips} chips on 'data'")
    if folds % ring_n:
        raise ValueError("folds_per_fit must be a multiple of ring_batches")
    rows_per_fit = folds * rows
    say(f"mesh {dict(mesh.shape)}; a fit: {folds} folds x {rows} rows = "
        f"{rows_per_fit} rows; ring of {ring_n} batches, "
        f"{ring_n * rows * d * 4 / chips / 1e9:.2f} GB on each chip")

    spec = data.pca_spec(ctx.seed, d, k)
    ring = [data.device_rows(spec, ctx.seed, i, rows, row_sharding(mesh))
            for i in range(ring_n)]
    mask = jax.device_put(np.ones((rows,), np.float32), row_sharding(mesh, 1))
    update = gram.streaming_update(mesh)
    jax.block_until_ready(ring)

    def one_fit(n_folds: int):
        state = gram.init_stats(d)
        with ctx.span("fold_loop"):
            start = time.monotonic()
            for i in range(n_folds):
                state = update(state, ring[i % ring_n], mask)
            jax.block_until_ready(state)
            end = time.monotonic()
        with ctx.span("finalize"):
            t0 = time.monotonic()
            sol = finalize_pca_stats(state, k=k, mean_center=cfg["mean_center"],
                                     mesh=mesh, n_true=n_folds * rows)
            model = {"pc": np.asarray(sol.pc),
                     "explained_variance": np.asarray(sol.explained_variance),
                     "mean": np.asarray(sol.mean)}
            seconds = time.monotonic() - t0
        # the rows the state says it folded (a float32 count of multiples
        # of the fold's rows, 2^14 or more, under 2^27: exact), read after
        # both timed parts
        model["rows"] = int(np.asarray(state[0]))
        return start, end, seconds, model

    ctx.stage(f"ring of {ring_n} batches made on the device")
    one_fit(2)  # a fold onto a fresh state and onto a folded one, a finalize
    obs.spans.clear()

    tracer = trace.TraceWindow(ctx.trace, min(0.5, ctx.seconds / 4),
                               min(p["trace_s"], ctx.seconds / 2), ctx.out_dir)
    begin = ctx.begin_window()
    deadline = obs.window[1]
    with tracer:
        index = 0
        while time.monotonic() < deadline:
            start, end, seconds, model = one_fit(folds)
            obs.attempted += folds + 1
            obs.passes.append({"fit": index, "pass": 0, "rows": rows_per_fit,
                               "start": start, "end": end})
            obs.fits.append({"fit": index, "finalize_s": seconds, "rows": rows_per_fit,
                             "part": 0, "end": time.monotonic(), "model": model})
            index += 1
    ctx.end_window()
    say(f"window closed after {time.monotonic() - begin:.2f} s: {len(obs.fits)} fits")
    obs.trace = tracer.reduced(obs.spans)
    obs.fold_rows_per_chip = rows // chips

    # Outside the window: every fit against the plain reference over the
    # ring, each batch weighted by how often a fit folds it.
    ref = ref_pca.fit(ring, [folds // ring_n] * ring_n, k)
    problems = [f"fit {f['fit']}: the state counts {f['model']['rows']} rows, "
                f"{rows_per_fit} were to be folded" for f in obs.fits
                if f["model"]["rows"] != rows_per_fit]
    problems += agree.check_pca_fits(obs.fits, {0: ref}, cfg["tolerances"], d, k, say)
    if not any(pa["end"] <= deadline for pa in obs.passes):
        problems.append("no pass completed inside the window")
    obs.compared = agree.compared_pca(obs.fits, cfg["tolerances"], rows_per_fit)
    if not agree.summarize(problems, say):
        obs.correct = False
    for fit in obs.fits:
        fit.pop("model", None)
    return obs
