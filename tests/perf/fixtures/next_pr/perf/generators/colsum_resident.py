"""Column sums of a ring of resident batches, pass after pass: the toy
algorithm `colsum`. Its programs go through the program's own jit ledger
(`utils/xprof.py` `ledgered_jit`) and its host step through the program's
span (`utils/profiling.py` `trace_span`), as a model under
`spark_rapids_ml_tpu/models/` would: counters and spans come from there.
Files this PR added are found under the run's own tree (`ctx.root`)."""

from __future__ import annotations

import time

import numpy as np

from perf.harness import layout, trace


def run(ctx):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.utils.profiling import trace_span
    from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

    cfg, p, obs, say = ctx.config, ctx.params, ctx.obs, ctx.say
    reference = layout.load_module(ctx.root, "reference", cfg["algo"])
    d, rows, ring_n, folds = (cfg["n_cols"], p["batch_rows"], p["ring_batches"],
                              p["folds_per_pass"])
    if folds % ring_n:
        raise ValueError("folds_per_pass must be a multiple of ring_batches")
    rng = np.random.default_rng(ctx.seed)
    mean = rng.uniform(-0.5, 0.5, size=d)
    host = [(rng.standard_normal((rows, d)) + mean).astype(np.float32)
            for _ in range(ring_n)]
    ring = [jax.device_put(x) for x in host]

    def colsum_fold(state, x):
        return state[0] + x.shape[0], state[1] + jnp.sum(x, axis=0)

    def colsum_scale(state):
        return state[1] / jnp.maximum(state[0], 1)

    fold = ledgered_jit("colsum.fold", colsum_fold)
    scale = ledgered_jit("colsum.scale", colsum_scale)

    def one_pass():
        state = (jnp.zeros((), jnp.int32), jnp.zeros((d,), jnp.float32))
        with ctx.span("fold_loop"):
            start = time.monotonic()
            for i in range(folds):
                state = fold(state, ring[i % ring_n])
            jax.block_until_ready(state)
            end = time.monotonic()
        with ctx.span("scale"), trace_span("colsum.scale"):
            means = np.asarray(scale(state))
        return start, end, {"rows": int(state[0]), "colsum": np.asarray(state[1]),
                            "mean": means}

    ctx.stage(f"ring of {ring_n} batches of {rows} rows on the device")
    one_pass()  # both programs, on a fresh state and on a folded one
    obs.spans.clear()

    tracer = trace.TraceWindow(ctx.trace, min(0.5, ctx.seconds / 4),
                               min(p["trace_s"], ctx.seconds / 2), ctx.out_dir)
    begin = ctx.begin_window()
    deadline = obs.window[1]
    results = []
    with tracer:
        while time.monotonic() < deadline:
            start, end, result = one_pass()
            obs.attempted += folds + 1
            obs.passes.append({"fit": len(results), "pass": 0, "rows": folds * rows,
                               "start": start, "end": end})
            results.append(result)
    ctx.end_window()
    say(f"window closed after {time.monotonic() - begin:.2f} s: {len(results)} passes")
    obs.trace = tracer.reduced(obs.spans)
    obs.fold_rows_per_chip = rows // ctx.cell["chips"]

    want = reference.fit(host, [folds // ring_n] * ring_n)
    limit = cfg["tolerances"]["colsum_abs"]
    worst = 0.0
    for i, got in enumerate(results):
        if got["rows"] != want["rows"]:
            say(f"  DISAGREES: pass {i}: the state counts {got['rows']} rows, "
                f"{want['rows']} were to be folded")
            obs.correct = False
        worst = max(worst, float(np.max(np.abs(got["colsum"] - want["colsum"]))))
    say(f"agreement over {len(results)} passes: column sums off by at most "
        f"{worst:.3e} (limit {limit})")
    obs.compared = {"colsum_abs": [worst, limit]}
    if worst > limit or not results:
        obs.correct = False
    return obs
