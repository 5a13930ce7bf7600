"""Histogram-forest fits whose rows, labels and bag keys stay on the chip:
the daemon's own job object, every level pass from its pass cache.

The generator drives `serve/daemon.py` `_Job("rf", d, mesh, {...})` in
process — the object every wire op calls — and not the TCP wire, as
`newton_cached` does and for its reason: 4.7 GB through a Python `recv`
would put tens of seconds of host-clock noise into `setup_s`, and in the
window no op carries a row (PERF.md §4).

*Set-up* (`CachedForest`). The seeded rows and their labels are made on the
device batch by batch, fetched, and fed through `_Job.fold` as partitioned
feeds, then `commit`ted (the pad copy, the puts and the staging run as for a
Spark task); the job keeps what its fold placed — rows, mask, label column,
bag keys — as its cached pass. The bin edges are the program's quantile
sketch over the first `forest_seed_sample_rows` rows and the start tables
its depth-0 iterate, installed with `set_iterate`, as `spark/estimator.py`
does. One whole fit is the warm-up; it is also the fit the comparison looks
at in depth: its frontier histograms (every tree at depth 0, the compared
trees at every depth) are fetched to the host before the window opens.

*Window.* Fits back to back, closed loop at the device's pace. A fit =
`set_iterate(start)` → `max_depth` × (`rescan` → `step` → the tables read)
— every depth from the cache, the first too. A `rescan` folds the cached
batches a group a dispatch (`serve/daemon.py` `_RESCAN_GROUP`), batch by
batch and chunk by chunk inside the program; a "fold" in this cell's
`pass_fold_device_ms` and `pass_fold_roofline` is one such program — one
name, `jit_hist_update_group`, for every depth's — and
`obs.fold_rows_per_chip` its rows. A pass is one level, from before
`rescan` until `step` has returned, with its `depth`.

**`obs.passes` lists the levels of WHOLE fits only.** A fit's levels are not
equal work (the frontier doubles a depth), so the levels of the fit the
deadline cuts — which ones end before it moves with the program's speed —
are run (the pass the deadline falls in runs to its end, then the fit is
dropped) and counted in `obs.attempted`, but not listed: every depth weighs
the same in `pass_rows_per_s` in every run. The counters' window ends with
the last whole fit for the same reason.

*A traced run* profiles from before the window's first fit until the window
has closed (stopping the profiler takes the host seconds: outside the
window), and reduces the trace over the FIRST FIT alone: every depth's
program once.

*Outside the window.* The job is dropped (its cache freed), the same
batches are made again on the device and the plain reference
(`reference/rf.py`) takes every level's statistics from them under the
warm-up fit's own tables; `harness/agree_rf.py` compares.
"""

from __future__ import annotations

import contextlib
import tempfile
import time

import numpy as np

from perf.harness import layout, stats, trace
from perf.harness.observe import take_counters

#: the ledger's name of the program `rescan` dispatches (`ops/histogram.py`
#: `hist_update_group_fn`); the configuration's `fold_program` is its name
#: in a trace
FOLD_FN = "histogram.update_group"


def refuse_unless_cacheable():
    """Before a byte of data is made: a program whose forest job keeps no
    cached pass cannot run this cell, and says so at once."""
    try:
        from spark_rapids_ml_tpu.models.jobs import job_algorithm

        algorithm = job_algorithm("rf")
    except (ImportError, ValueError) as e:
        raise RuntimeError(f"this program has no table of job algorithms with 'rf' in "
                           f"it ({e}): the cell levels_cached cannot run on it") from e
    if not getattr(algorithm, "cacheable", False):
        raise RuntimeError(
            "models/random_forest.py `RandomForestJob` is not `cacheable`: this program "
            "keeps no pass cache for the forest job, the cell levels_cached cannot run on it")
    return algorithm


def compared_trees(seed: int, n_trees: int, k: int):
    return sorted(int(t) for t in np.random.default_rng([seed, 7]).choice(
        n_trees, size=min(k, n_trees), replace=False))


class FitTrace(trace.TraceWindow):
    """The profiler driven by the fit loop itself, not by a timer: on from
    `begin()`; `mark()` ends the interval the trace is reduced over (a fit's
    end) while the profiler runs on; `end()` stops it."""

    def __init__(self, enabled, out_dir=None):
        super().__init__(enabled, 0.0, 0.0, out_dir)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()

    def begin(self):
        if self.enabled:
            self.logdir = tempfile.mkdtemp(prefix="perf-trace-")
            self._before = time.monotonic()
            trace.start(self.logdir)
            self._began = time.time()

    def mark(self):
        if self.enabled and self.traced is None:
            self.traced = (self._began, time.time())

    def end(self):
        if self.enabled and self.profiled is None and self.logdir is not None:
            self.mark()
            trace.stop()
            self.profiled = (self._before, time.monotonic())


class CachedForest:
    """The cell's deployment, set up: the job with its cached pass, the
    start tables, and what the comparison needs of both."""

    def __init__(self, root, cfg, params, seed, chips, say, stage=lambda s: None):
        import jax

        from spark_rapids_ml_tpu import config
        from spark_rapids_ml_tpu.models import random_forest as forest
        from spark_rapids_ml_tpu.ops import histogram
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh, make_mesh
        from spark_rapids_ml_tpu.serve.daemon import _Job

        if cfg["algo"] != "rf" or cfg["n_classes"]:
            raise KeyError(f"levels_cached has no fit for algo {cfg['algo']!r} with "
                           f"{cfg['n_classes']} classes")
        self.data = layout.load_module(root, "harness", "rf_data")
        self.cfg, self.seed = cfg, seed
        self.d, self.depth = cfg["n_cols"], cfg["max_depth"]
        self.rows, self.n_batches = params["batch_rows"], params["cached_batches"]
        self.cached_rows = self.rows * self.n_batches
        self.trees = compared_trees(seed, cfg["num_trees"], params["compare_trees"])
        self.job_params = {
            "num_trees": cfg["num_trees"], "max_depth": cfg["max_depth"],
            "max_bins": cfg["max_bins"], "n_classes": 0, "seed": cfg["seed"],
            "bootstrap": cfg["bootstrap"], "min_instances": cfg["min_instances_per_node"],
            "subset": cfg["feature_subset_strategy"],
        }
        mesh = (default_mesh() if len(jax.devices()) == chips
                else make_mesh(devices=jax.devices()[:chips]))
        if mesh.shape[DATA_AXIS] != chips:
            raise RuntimeError(f"mesh {dict(mesh.shape)} does not put {chips} chips on 'data'")
        say(f"mesh {dict(mesh.shape)}; a cached pass: {self.n_batches} batches x {self.rows} "
            f"rows = {self.cached_rows} rows, {self.cached_rows * self.d * 4 / chips / 1e9:.2f} "
            f"GB on each chip; a fit: {self.depth} level passes of {cfg['num_trees']} trees, "
            f"{cfg['max_bins']} bins; compared in depth: trees {self.trees}")
        # the two budgets the configuration states hold for as long as the
        # job lives: the gate reads its own at every pass boundary
        self._options = contextlib.ExitStack()
        self._options.enter_context(
            config.option("daemon_pass_cache_mb", int(cfg["daemon_pass_cache_mb"])))
        self._options.enter_context(
            config.option("forest_hist_budget_mb", int(cfg["forest_hist_budget_mb"])))
        self.planted = self.data.spec(seed, self.d)
        parts = params["partitions"]
        if self.n_batches % parts:
            raise ValueError("cached_batches must be a multiple of partitions")
        per_part = self.n_batches // parts
        self.job = _Job("rf", self.d, mesh, self.job_params)
        spec = forest.forest_spec_from_params(self.job_params, self.d)
        for i in range(self.n_batches):
            x, y = (np.asarray(a) for a in self.batch(i))
            if i == 0:
                cap = int(config.get("forest_seed_sample_rows"))
                self.edges = histogram.quantile_bin_edges(x[:cap], spec.max_bins)
                self.start = forest.init_forest_arrays(spec, self.edges)
                self.job.set_iterate(self.start, 0)
                stage(f"bin edges from the first {min(cap, len(x))} rows, start tables installed")
            part = i // per_part
            self.job.fold(x, y, partition=part, pass_id=0)
            if (i + 1) % per_part == 0:
                self.job.commit(part, pass_id=0)
        #: (partition, offset) of every batch's first row: its rows' identities
        self.placed = [(i // per_part, (i % per_part) * self.rows) for i in range(self.n_batches)]
        del x, y
        ack = self.job.cache_ack()
        if not ack.get("cached") or ack["cached_rows"] != self.cached_rows:
            raise RuntimeError(f"the job did not keep the pass it was fed: {ack} "
                               f"(budget {cfg['daemon_pass_cache_mb']} MiB a device)")
        stage(f"{self.n_batches} batches made on the device, fetched, fed with their labels and "
              f"committed in {parts} partitions; the job holds {self.job.pass_cache_bytes} "
              "bytes a device")

    def batch(self, index):
        return self.data.device_rows(self.planted, self.d, self.seed, index, self.rows)

    def fit(self, index, span, deadline=None, capture=None):
        """→ (passes, levels, ops): the timed level passes; the tables
        before each pass and after the last, or None for a fit the deadline
        cut; the job ops made. `capture(depth, histogram)`: called with each
        pass's frontier histogram, still on the device, before its step."""
        job, passes = self.job, []
        with span("set_iterate"):
            job.set_iterate(self.start, job.iteration + 1)
        levels, ops = [self.start], 1
        for depth in range(self.depth):
            begin = time.monotonic()
            with span("rescan"):
                job.rescan(job.iteration)
            scanned = time.monotonic()
            if capture is not None:
                capture(depth, job.peek_pass_state()[0])
            with span("boundary"):
                info = job.step({})
            end = time.monotonic()
            passes.append({"fit": index, "pass": depth, "depth": depth, "rows": self.cached_rows,
                           "start": begin, "scanned": scanned, "end": end})
            with span("model_read"):
                levels.append(job.get_iterate()[0])
            ops += 3
            if info["open_nodes"] == 0:
                break
            if deadline is not None and end >= deadline:
                return passes, None, ops
        if deadline is not None and passes[-1]["end"] > deadline:
            return passes, None, ops
        return passes, levels, ops

    def captured_fit(self, span=lambda name: contextlib.nullcontext()):
        """The warm-up fit with its histograms fetched: what
        `agree_rf.check` takes as `captured` (without `pred`)."""
        import jax.numpy as jnp

        root, kept = [], []

        def capture(depth, hist):
            if depth == 0:
                root.append(np.asarray(hist))
            kept.append(np.asarray(hist[jnp.asarray(self.trees)]))

        passes, levels, _ = self.fit(-1, span, capture=capture)
        return passes, {"levels": levels, "hist_root": root[0], "hist": kept}

    def release(self):
        self.job.release()
        self.job = None
        self._options.close()

    def predictions(self, tables):
        """The finished model served as the library serves it: the first
        cached batch through `RandomForestRegressionModel.predict`."""
        from spark_rapids_ml_tpu.models.random_forest import RandomForestRegressionModel

        arrays = {k: np.asarray(v) for k, v in tables.items() if k != "depth"}
        arrays["n_classes"] = np.asarray([0], np.int64)
        return RandomForestRegressionModel(arrays=arrays).predict(np.asarray(self.batch(0)[0]))

    def reference(self, reference, levels, rounded=None):
        """What `agree_rf.check` takes as `ref`: the plain reference over
        the same batches, made again, under the tables `levels`."""
        cfg, n_levels = self.cfg, len(levels) - 1
        keys = [reference.row_keys(p, o, self.rows) for p, o in self.placed]
        all_trees = list(range(cfg["num_trees"]))

        def batches():
            return (self.batch(i) for i in range(self.n_batches))

        (root,) = reference.level_statistics(
            batches(), keys, self.edges, levels, all_trees, cfg["seed"], 1, rounded)
        left = reference.level_statistics(
            batches(), keys, self.edges, levels, self.trees, cfg["seed"], n_levels, rounded)
        scored = []
        for level in range(n_levels):
            is_open = np.asarray(levels[level]["feature"])[
                self.trees, (1 << level) - 1: (2 << level) - 1] == reference.OPEN
            subset = reference.feature_subset(
                self.trees, level, self.d, self.subset_m, cfg["seed"])
            scored.append(reference.best_splits(
                left[level], is_open, subset, cfg["min_instances_per_node"]))
        bag_rows = sum(reference.bag_weights(k, all_trees, cfg["seed"]).sum(1, dtype=np.float64)
                       for k in keys)
        return {"trees": self.trees, "root": root, "left": left, "scored": scored,
                "bag_rows": bag_rows,
                "pred": reference.predict(self.batch(0)[0], self.edges, levels[-1],
                                          cfg["max_depth"], rounded)}

    def compared(self, captured, fits, agree, reference, say, rounded=None):
        """Frees the job, serves the captured fit's model once, takes the
        reference's statistics (from rows `rounded`, for a control) and
        → `agree_rf.check`'s numbers, each beside its limit."""
        if self.job is not None:
            self.release()
        if "pred" not in captured:
            captured["pred"] = self.predictions(captured["levels"][-1])
        ref = self.reference(reference, captured["levels"], rounded)
        return agree.check(captured, fits, ref, self.cfg["tolerances"], say)

    @property
    def subset_m(self):
        # Spark's featureSubsetStrategy "auto" for regression: a third of the columns
        if self.cfg["feature_subset_strategy"] != "auto":
            raise KeyError("levels_cached states only featureSubsetStrategy 'auto'")
        return max(1, self.d // 3)


def run(ctx):
    refuse_unless_cacheable()
    cfg, p, obs, say = ctx.config, ctx.params, ctx.obs, ctx.say
    agree = layout.load_module(ctx.root, "harness", "agree_rf")
    reference = layout.load_module(ctx.root, "reference", "rf")
    chips = ctx.cell["chips"]
    forest = CachedForest(ctx.root, cfg, p, ctx.seed, chips, say, ctx.stage)

    warm_passes, captured = forest.captured_fit(ctx.span)  # every program a fit meets
    obs.spans.clear()
    ctx.stage("one whole fit as warm-up, its histograms fetched; a level took, by depth: "
              + ", ".join(f"{pa['depth']}: {pa['end'] - pa['start']:.2f} s" for pa in warm_passes))

    tracer = FitTrace(ctx.trace, ctx.out_dir)
    begin = ctx.begin_window()
    deadline = obs.window[1]
    after_whole = None
    with tracer:
        tracer.begin()
        index = 0
        while time.monotonic() < deadline:
            passes, levels, ops = forest.fit(index, ctx.span, deadline)
            obs.attempted += ops
            tracer.mark()  # the trace is reduced over the window's first fit
            if levels is not None:
                obs.passes += passes
                obs.fits.append({"fit": index, "rows": sum(pa["rows"] for pa in passes),
                                 "end": passes[-1]["end"], "levels": levels})
                after_whole = take_counters(ctx.watch)
            index += 1
        ctx.end_window()
    if after_whole is not None:
        # the counters' window ends with the last whole fit; a program built
        # while the cut fit ran would still be in the window
        obs.after = {**after_whole, "compile_events": obs.after["compile_events"],
                     "compile_seconds": obs.after["compile_seconds"]}
    say(f"window closed after {time.monotonic() - begin:.2f} s: {len(obs.fits)} whole fits "
        f"({len(obs.passes)} level passes listed), {index - len(obs.fits)} cut by the deadline")
    obs.trace = tracer.reduced(obs.spans)
    if tracer.profiled:
        say(f"the profiler was on for {tracer.profiled[1] - tracer.profiled[0]:.2f} s; the "
            f"trace is reduced over the first fit's {tracer.traced[1] - tracer.traced[0]:.2f} s")
    by_depth = {}
    for pa in obs.passes:
        by_depth.setdefault(pa["depth"], []).append(pa)
    for depth, done in sorted(by_depth.items()):
        took = [pa["end"] - pa["start"] for pa in done]
        say(f"  depth {depth}: a pass {np.mean(took):.3f} s (rescan "
            f"{1e3 * np.mean([pa['scanned'] - pa['start'] for pa in done]):.1f} ms), "
            f"{len(done)} listed")
    obs.notes["depth_pass_s"] = {d: float(np.mean([pa["end"] - pa["start"] for pa in v]))
                                 for d, v in by_depth.items()}
    stats.say_passes(obs.passes, deadline, say)
    # A fold program's rows, as the program counted them: `rescan` folds its
    # cached batches a group a dispatch, and the group is the program's to choose.
    folded = obs.counter_delta("srml_daemon_pass_rows_total", source="cache")
    refed = obs.counter_delta("srml_daemon_pass_rows_total", source="wire")
    dispatched = obs.counter_delta("srml_xla_calls_total", fn=FOLD_FN)
    if dispatched > 0:
        obs.fold_rows_per_chip = int(round(folded / dispatched)) // chips
        say(f"a fold program folds {obs.fold_rows_per_chip * chips} rows "
            f"({obs.fold_rows_per_chip * chips // forest.rows} cached batches a dispatch)")
    if folded + refed > 0:
        say(f"rows folded in the window: {folded:.0f} from the cache, {refed:.0f} from "
            f"the wire ({100.0 * folded / (folded + refed):.6g}% cached)")

    # Outside the window: free the program's rows, serve the model once,
    # make the rows again and take the reference's statistics from them.
    obs.compared = {**forest.compared(captured, obs.fits, agree, reference, say),
                    "rows_refed_in_window": [float(refed), 0.0]}
    problems = agree.problems(obs.compared)
    if not obs.fits:
        problems.append("no whole fit completed inside the window")
    for problem in problems[:20]:
        say(f"  DISAGREES: {problem}")
    if problems:
        obs.correct = False
    for fit in obs.fits:
        fit.pop("levels", None)
    return obs
