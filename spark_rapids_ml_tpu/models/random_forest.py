"""RandomForest — histogram trees grown level-synchronously on device.

The first non-linear-algebra workload in the package (ROADMAP item 4a):
the cuML-era spark-rapids-ml surface is dominated by tree ensembles, and
their compute shape — per-node split histograms over BINNED features —
is a ``reduce_sum`` over the DrJAX primitives (parallel/mapreduce.py),
not a GEMM. The design keeps everything inside compiled programs
(ops/histogram.py):

* Features quantize once to uint8 bin ids against quantile-sketch edges
  (the edges ARE part of the model iterate, so every daemon in a
  distributed fit bins identically — the kmeans-seed pattern).
* All trees grow LEVEL-SYNCHRONOUSLY: one dataset pass per depth routes
  every row to its frontier node in every tree and accumulates ONE
  ``(tree, node, feature, bin, stat)`` histogram tensor — additive, so
  it rides the daemon merge / ``reduce_mesh`` plane completely
  unchanged, and the pass boundary (``step``) is exactly the Lloyd /
  Newton boundary the recovery + elastic machinery already snapshots.
* Split selection is one vectorized device program over every
  (node, feature, threshold) candidate (Gini / variance gain).
* The fitted forest is a dense ``(tree, node)`` heap table (children of
  i at 2i+1 / 2i+2); ``predict_matrix`` descends ALL trees by gather in
  one jitted program, bucketer-padded (``run_bucketed``) so it rides the
  serving scheduler and fleet plane like every other model.

Bootstrap bags are counter-based Poisson(1) weights keyed on each row's
(partition, offset) identity — deterministic under Spark task retries,
batch re-chunking, and daemon re-routing (ops/histogram.py).
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core.dataset import as_column, as_matrix, with_column
from spark_rapids_ml_tpu.core.params import (
    Estimator,
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasSeed,
    Model,
    ParamDecl,
    ParamValidators,
    TypeConverters,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops import histogram as hist_ops
from spark_rapids_ml_tpu.ops.histogram import LEAF, OPEN
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel.sharding import (
    pad_rows,
    row_sharding,
    run_bucketed,
)
from spark_rapids_ml_tpu.utils import metrics as metrics_mod
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit

#: Forest telemetry (docs/observability.md catalogs these; the lint
#: gates require every hot path booked).
_M_FIT_PASSES = metrics_mod.counter(
    "srml_forest_fit_passes_total",
    "Level-synchronous histogram passes applied (one per tree depth), "
    "by role (classifier|regressor)",
)
_M_NODES_SPLIT = metrics_mod.counter(
    "srml_forest_nodes_split_total",
    "Frontier nodes split into children across all trees, by role",
)
_M_FRONTIER_NODES = metrics_mod.counter(
    "srml_forest_frontier_nodes_total",
    "Open frontier nodes whose histogram a level pass built, by how: "
    "folded (its rows contracted) or derived (the parent's histogram less "
    "its folded sibling's)",
)
_M_HIST_ROWS = metrics_mod.counter(
    "srml_forest_hist_rows_total",
    "Rows folded into per-node split histograms (each dataset pass "
    "counts every row once), by role",
)
_M_TRANSFORM_ROWS = metrics_mod.counter(
    "srml_forest_transform_rows_total",
    "Rows scored through forest predict/transform, by role",
)

#: Dense-heap bound: max_nodes = 2^(maxDepth+1) − 1 per tree, so the
#: node-table (and the deepest frontier histogram) stays addressable.
MAX_MAX_DEPTH = 16

#: In-memory fit row chunk: what one placed batch of the fit holds, the
#: way streaming fits bound their batches (the fold bounds its own
#: transient: ops/histogram.py FOLD_CHUNK_ROWS); the last partial chunk
#: pads to the data axis, so chunking never changes the (additive)
#: histograms.
FIT_CHUNK_ROWS = 8192


class ForestCapacityError(ValueError):
    """A frontier histogram tensor over the per-device budget — raised
    at pass OPEN (job creation / step), never as a mid-pass OOM (the
    Gram-capacity contract, docs/mesh.md, for the tree shape).
    ``ValueError`` like ``GramCapacityError``: deterministic — a
    recovery replay cannot fix a too-large shape."""


class ForestSpec(NamedTuple):
    """Resolved creation params of one forest job — the single parse of
    the wire ``params`` dict shared by the in-memory fit, the daemon job
    and the split scorer (drift between them would desync replays)."""

    num_trees: int
    max_depth: int
    max_bins: int
    n_classes: int  # 0 = regression
    subset_m: int
    seed: int
    bootstrap: bool
    min_instances: int

    @property
    def n_stats(self) -> int:
        return self.n_classes if self.n_classes > 0 else 3

    @property
    def max_nodes(self) -> int:
        return (1 << (self.max_depth + 1)) - 1

    def role(self) -> str:
        return "classifier" if self.n_classes > 0 else "regressor"


def subset_size(strategy: str, n_cols: int, classifier: bool) -> int:
    """featureSubsetStrategy → per-node candidate-feature count (Spark
    ML semantics: auto = sqrt for classification, onethird for
    regression; also all|sqrt|onethird|log2, an integer count, or a
    (0, 1] fraction)."""
    s = str(strategy).strip().lower()
    if s == "auto":
        s = "sqrt" if classifier else "onethird"
    if s == "all":
        return n_cols
    if s == "sqrt":
        return max(1, int(math.ceil(math.sqrt(n_cols))))
    if s == "onethird":
        return max(1, n_cols // 3)
    if s == "log2":
        return max(1, int(math.floor(math.log2(max(n_cols, 2)))))
    try:
        v = float(s)
    except ValueError:
        raise ValueError(
            f"unknown featureSubsetStrategy {strategy!r} "
            "(auto|all|sqrt|onethird|log2|<int>|<fraction>)"
        ) from None
    if 0.0 < v <= 1.0 and "." in s:
        return max(1, int(math.ceil(v * n_cols)))
    if v >= 1.0 and v == int(v):
        return min(n_cols, int(v))
    raise ValueError(
        f"featureSubsetStrategy {strategy!r} must be a strategy name, an "
        "integer >= 1, or a fraction in (0, 1]"
    )


def forest_spec_from_params(params: Dict, n_cols: int) -> ForestSpec:
    """Validate + resolve one wire/constructor ``params`` dict
    (docs/protocol.md "The `rf` job algo"). Raises ``ValueError`` for
    out-of-range creation params — a first-feed-rejection class error,
    never a mid-fit surprise."""
    params = params or {}

    def _p(key, default, cast=int):
        # None-aware (never `or`): an EXPLICIT 0 must reach the range
        # validation below, not silently coerce to the default.
        v = params.get(key)
        return default if v is None else cast(v)

    num_trees = _p("num_trees", 20)
    max_depth = _p("max_depth", 5)
    max_bins = _p("max_bins", 32)
    n_classes = _p("n_classes", 0)
    seed = _p("seed", 0)
    bootstrap = _p("bootstrap", True, bool)
    min_instances = _p("min_instances", 1)
    strategy = _p("subset", "auto", str)
    if num_trees < 1:
        raise ValueError(f"num_trees = {num_trees} must be >= 1")
    if not 1 <= max_depth <= MAX_MAX_DEPTH:
        raise ValueError(
            f"max_depth = {max_depth} out of range [1, {MAX_MAX_DEPTH}] "
            "(dense (tree, node) heap tables)"
        )
    if not 2 <= max_bins <= 256:
        raise ValueError(
            f"max_bins = {max_bins} out of range [2, 256] (uint8 bin ids)"
        )
    if n_classes == 1 or n_classes < 0:
        raise ValueError(f"n_classes = {n_classes} must be 0 (regression) or >= 2")
    if min_instances < 1:
        raise ValueError(f"min_instances = {min_instances} must be >= 1")
    return ForestSpec(
        num_trees=num_trees,
        max_depth=max_depth,
        max_bins=max_bins,
        n_classes=n_classes,
        subset_m=subset_size(strategy, n_cols, n_classes > 0),
        seed=seed,
        bootstrap=bootstrap,
        min_instances=min_instances,
    )


def require_hist_capacity(spec: ForestSpec, depth: int, n_cols: int) -> None:
    """Refuse a frontier histogram over the per-device budget (config
    ``forest_hist_budget_mb`` / SRML_FOREST_HIST_BUDGET_MB) at the pass
    boundary that would allocate it — the forest twin of the Gram
    capacity gate (never a mid-pass OOM). The tensor is replicated on
    every device, so the budget is per device."""
    budget = int(config.get("forest_hist_budget_mb")) << 20
    itemsize = jnp.dtype(config.get("accum_dtype")).itemsize
    need = (
        spec.num_trees * (1 << depth) * n_cols * spec.max_bins
        * spec.n_stats * itemsize
    )
    if budget and need > budget:
        raise ForestCapacityError(
            f"the depth-{depth} frontier histogram "
            f"({spec.num_trees} trees x {1 << depth} nodes x {n_cols} "
            f"features x {spec.max_bins} bins x {spec.n_stats} stats = "
            f"{need >> 20} MiB) exceeds forest_hist_budget_mb "
            f"({budget >> 20} MiB); lower maxDepth/maxBins/numTrees or "
            "raise SRML_FOREST_HIST_BUDGET_MB"
        )


def init_forest_arrays(spec: ForestSpec, bin_edges: np.ndarray) -> Dict[str, np.ndarray]:
    """The depth-0 iterate: quantile edges + empty node tables with every
    root OPEN. These arrays ARE the wire iterate (get/set_iterate), the
    durable pass-boundary snapshot payload, and the driver recovery
    ledger entry — one layout everywhere (docs/protocol.md)."""
    edges = np.asarray(bin_edges, np.float64)
    if edges.ndim != 2 or edges.shape[1] != spec.max_bins - 1:
        raise ValueError(
            f"bin_edges shape {edges.shape} != (n_cols, {spec.max_bins - 1})"
        )
    T, N, S = spec.num_trees, spec.max_nodes, spec.n_stats
    feature = np.full((T, N), LEAF, np.int32)
    feature[:, 0] = OPEN
    return {
        "bin_edges": edges,
        "feature": feature,
        "threshold": np.zeros((T, N), np.int32),
        "value": np.zeros((T, N, S), np.float64),
        "depth": np.zeros((1,), np.int64),
    }


def validate_forest_arrays(
    arrays: Dict[str, np.ndarray], spec: ForestSpec, n_cols: int
) -> Dict[str, np.ndarray]:
    """Full shape validation at the iterate boundary (the set_iterate /
    durable-restore contract): a mis-shaped table installed here would
    otherwise crash opaquely inside the next pass's jitted update."""
    T, N, S = spec.num_trees, spec.max_nodes, spec.n_stats
    want = {
        "bin_edges": (n_cols, spec.max_bins - 1),
        "feature": (T, N),
        "threshold": (T, N),
        "value": (T, N, S),
        "depth": (1,),
    }
    out = {}
    for name, shape in want.items():
        a = arrays.get(name)
        if a is None:
            raise ValueError(f"forest iterate missing array {name!r}")
        a = np.asarray(a)
        if tuple(a.shape) != shape:
            raise ValueError(
                f"forest iterate array {name!r} shape {tuple(a.shape)} "
                f"!= {shape}"
            )
        out[name] = a
    depth = int(out["depth"][0])
    if not 0 <= depth <= spec.max_depth + 1:
        raise ValueError(
            f"forest iterate depth {depth} out of range "
            f"[0, {spec.max_depth + 1}]"
        )
    out["bin_edges"] = np.asarray(out["bin_edges"], np.float64)
    out["feature"] = np.asarray(out["feature"], np.int32)
    out["threshold"] = np.asarray(out["threshold"], np.int32)
    out["value"] = np.asarray(out["value"], np.float64)
    out["depth"] = np.asarray(out["depth"], np.int64)
    return out


def open_frontier_nodes(feature: np.ndarray, depth: int) -> int:
    """How many nodes await a split at ``depth`` (the driver's stop
    signal once it reaches 0)."""
    W = 1 << depth
    base = W - 1
    if base >= feature.shape[1]:
        return 0
    return int(np.sum(feature[:, base: base + W] == OPEN))


def pair_signs(tables: Dict[str, np.ndarray], spec: ForestSpec) -> np.ndarray:
    """Which child of every frontier PAIR the next pass folds and which it
    derives from the parent's histogram — ``(T, W/2, 2)`` of +1 (folded),
    -1 (derived) and 0 (closed), from the iterate alone (``depth >= 1``):
    where both children of a split are OPEN, the one with the smaller count
    in the ``value`` table folds (ties: the left) and its sibling — the
    larger, so its relative error the smaller — is derived; where one is
    OPEN it folds and nothing is derived; a parent that did not split has
    no open child. An operand of the halved fold beside ``feature`` and
    ``threshold`` (ops/histogram.py `hist_update_group_fn`)."""
    depth = int(tables["depth"][0])
    W = 1 << depth
    level = slice(W - 1, 2 * W - 1)
    T = spec.num_trees
    is_open = (tables["feature"][:, level] == OPEN).reshape(T, W // 2, 2)
    value = tables["value"][:, level]
    count = (value.sum(-1) if spec.n_classes > 0 else value[..., 0]).reshape(
        T, W // 2, 2)
    right_folds = np.where(
        is_open.all(-1), count[..., 1] < count[..., 0], is_open[..., 1])
    folded = is_open & (np.arange(2) == right_folds[..., None])
    derived = is_open & ~folded
    return folded.astype(np.int8) - derived.astype(np.int8)


def open_pass(tables: Dict[str, np.ndarray], spec: ForestSpec, n_cols: int,
              parent=None):
    """A pass's state before its first fold, at the installed depth:
    ``(state, signs)``. ``()`` where no node is open (no scan will fold
    there: no frontier is allocated and the capacity gate, which a
    histogram nobody builds must not trip, is not asked); else the gate,
    then zeros — ``signs`` None: every fold of the pass contracts the whole
    frontier — or, given ``parent`` (the COMPLETE histogram of the same
    rows one depth up, consumed here), what is already known of the new
    frontier (`hist_ops.seed_hist`) and the ``signs`` (:func:`pair_signs`)
    every fold of the pass then takes: the one opening of a pass for the
    in-memory fit and the daemon job. Dispatches."""
    depth = int(tables["depth"][0])
    if open_frontier_nodes(tables["feature"], depth) == 0:
        return (), None
    require_hist_capacity(spec, depth, n_cols)
    accum = jnp.dtype(config.get("accum_dtype"))
    if parent is None:
        return hist_ops.zero_hist(
            spec.num_trees, depth, n_cols, spec.max_bins, spec.n_stats, accum
        ), None
    signs = pair_signs(tables, spec)
    return hist_ops.seed_hist(parent, jnp.asarray(signs)), signs


def row_identity_keys(partition: Optional[int], offset: int, n: int) -> np.ndarray:
    """uint32 bootstrap-bag identity keys for ``n`` rows starting at
    partition-relative ``offset`` — a pure function of (partition,
    offset), never of batch boundaries: task retries restart their
    stage at offset 0 and replay the identical keys, and a partition
    lands on the same keys whichever daemon it routes to."""
    pid = 0 if partition is None else int(partition)
    base = np.uint32((pid * 2654435761 + int(offset)) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):  # uint32 wraparound is the point
        return (base + np.arange(n, dtype=np.uint32)).astype(np.uint32)


def accumulate_histogram(
    hist, tables: Dict[str, np.ndarray], xs, ys, masks, row_keys,
    spec: ForestSpec, mesh: Mesh, n_valid: int, signs=None,
):
    """Fold a run of placed batches — four tuples of equal length, a
    single batch a run of one — into the frontier histogram in ONE
    program: the ONE entry the in-memory fit, the daemon job's ``fold``
    and its ``fold_group`` use (drift would break the
    single-daemon-oracle bitwise contract). Inputs are already padded +
    row-sharded; replicated table arrays upload per call (tiny next to
    the batch). ``n_valid`` is the run's unpadded row count (booking
    only). ``signs``: what :func:`open_pass` gave with the pass's state —
    None folds the whole frontier, a table the halved fold of a state
    seeded from the parent."""
    depth = int(tables["depth"][0])
    update = hist_ops.hist_update_group_fn(
        mesh, spec.num_trees, spec.max_bins, depth, spec.n_classes,
        spec.bootstrap, spec.seed, config.get("accum_dtype"),
        config.get("compute_dtype"), halved=signs is not None,
        use_pallas=bool(config.get("use_pallas")),
    )
    _M_HIST_ROWS.inc(int(n_valid), role=spec.role())
    # Edges upload in the accumulation dtype EXPLICITLY: on a non-x64
    # runtime a bare f64 upload truncates to f32 anyway (with a warning
    # per batch); naming the dtype keeps fit and predict binning in the
    # same precision on every profile (f64 under the parity tests).
    accum = jnp.dtype(config.get("accum_dtype"))
    operands = (
        jnp.asarray(tables["bin_edges"], accum),
        jnp.asarray(tables["feature"]),
        jnp.asarray(tables["threshold"]),
    )
    if signs is not None:
        operands += (jnp.asarray(signs, accum),)
    return update(
        hist, operands,
        tuple(xs), tuple(ys), tuple(masks), tuple(row_keys),
    )


def grow_level(
    tables: Dict[str, np.ndarray], hist, spec: ForestSpec,
) -> Dict[str, int]:
    """Apply one level's split decisions from the pass histogram: score
    every candidate on device, then write the (small, host-side) node
    tables — split features/thresholds on the frontier, child stats +
    OPEN/LEAF marks one level down. Mutates ``tables`` in place and
    advances ``depth``; returns ``{"open_nodes", "splits", "depth"}``.
    Call with the device lock held when the daemon owns the devices."""
    depth = int(tables["depth"][0])
    W = 1 << depth
    base = W - 1
    scorer = hist_ops.best_splits_fn(
        spec.num_trees, depth, spec.n_classes, spec.subset_m, spec.seed,
        spec.min_instances, config.get("accum_dtype"),
    )
    mask = hist_ops.feature_subset_mask(
        spec.num_trees, W, depth, hist.shape[2], spec.subset_m, spec.seed
    )
    # the scorer's dispatch and the read of its result: the wait for the
    # pass's folds is in it (the histogram is their output)
    with trace_span("forest.score"):
        score, bf, bb, left, right, tot = (
            np.asarray(jax.device_get(a)) for a in scorer(hist, mask)
        )
    score = np.where(np.isfinite(score), score, -np.inf)
    feat, thr, val = tables["feature"], tables["threshold"], tables["value"]
    fl = feat[:, base: base + W]  # basic slices: views, writes stick
    tl = thr[:, base: base + W]
    vl = val[:, base: base + W]
    open_mask = fl == OPEN
    clf = spec.n_classes > 0
    n_l = left.sum(-1) if clf else left[..., 0]
    n_r = right.sum(-1) if clf else right[..., 0]
    vl[open_mask] = tot[open_mask]
    can = (
        open_mask
        & (depth < spec.max_depth)
        & (score > 1e-12)
        & (n_l >= spec.min_instances)
        & (n_r >= spec.min_instances)
    )
    fl[open_mask & ~can] = LEAF
    fl[can] = bf[can]
    tl[can] = bb[can]
    opened = 0
    if depth < spec.max_depth and can.any():
        base2 = 2 * W - 1
        for side, stats, n_side in ((0, left, n_l), (1, right, n_r)):
            cf = feat[:, base2 + side: base2 + 2 * W: 2]
            cv = val[:, base2 + side: base2 + 2 * W: 2]
            cv[can] = stats[can]
            if clf:
                pure = (n_side - stats.max(-1)) <= 1e-9
            else:
                resid = stats[..., 2] - (
                    stats[..., 1] ** 2 / np.maximum(n_side, 1)
                )
                pure = resid <= 1e-12 * np.maximum(1.0, stats[..., 2])
            grow = (
                can
                & (depth + 1 < spec.max_depth)
                & (n_side >= 2 * spec.min_instances)
                & ~pure
            )
            cf[can] = np.where(grow, OPEN, LEAF)[can]
            opened += int(grow.sum())
    n_split = int(can.sum())
    _M_NODES_SPLIT.inc(n_split, role=spec.role())
    _M_FIT_PASSES.inc(role=spec.role())
    tables["depth"] = np.asarray([depth + 1], np.int64)
    return {"open_nodes": opened, "splits": n_split, "depth": depth + 1}


def close_pass(
    tables: Dict[str, np.ndarray], hist, spec: ForestSpec, signs=None,
) -> Dict[str, int]:
    """The end of a pass :func:`open_pass` opened (``signs``: what it
    gave): books how the level's open nodes — the ones :func:`grow_level`
    is about to score — came by their histograms, then grows the level."""
    n_open = open_frontier_nodes(tables["feature"], int(tables["depth"][0]))
    n_derived = 0 if signs is None else int((signs < 0).sum())
    _M_FRONTIER_NODES.inc(n_open - n_derived, how="folded")
    _M_FRONTIER_NODES.inc(n_derived, how="derived")
    return grow_level(tables, hist, spec)


class RandomForestJob(JobAlgorithm):
    """Histogram tree ensembles as a daemon job (docs/protocol.md "The
    `rf` job algo"): one pass per tree depth. The iterate is the (bin
    edges + node tables) bundle, installed by the driver's set_iterate
    BEFORE the first scan (a peer daemon not pre-seeded rejects its feeds
    loudly); a pass's state is ONE additive (tree, node, feature, bin,
    stat) histogram of the installed depth's frontier, so the cross-daemon
    merge plane carries it like any other.

    A job that stepped its own pass keeps that pass's histogram across the
    boundary: the next pass's state starts from it (`open_pass`) and its
    folds — direct, staged, cached — contract one child of every split, the
    sibling being the parent less that child. Decided by what the job
    holds, never by an option: an installed iterate (`set_iterate`, a
    restore, a peer daemon) comes with no parent, and a state that took a
    merge holds other daemons' rows beside this one's — the parent must be
    of exactly the rows the next pass folds HERE — so from its first merge
    until an iterate is installed again the job hands no parent on (a peer
    that once held rows may hold none in one pass and some in the next):
    those passes fold the whole frontier."""

    name = "rf"
    needs_labels = True
    iterative = True
    # The cached batch is everything the fold placed: rows, mask, the
    # label column and the bag keys (`place_columns`), so a cached pass
    # weighs every row as the fed one did, whatever the params.
    cacheable = True
    # what the device waits for between two depths: the scorer (the wait
    # for the pass's folds is in its read), the host's table update, the
    # next depth's opening state (zeros, or the parent's seed), the snapshot
    boundary_span = "forest.boundary"
    no_iterate = {
        # The kmeans-seed contract: a peer daemon the driver never
        # configured fails its tasks loudly instead of binning differently.
        "feed": (
            "rf feed before the forest iterate is installed; the "
            "driver sends set_iterate (bin edges + node tables) "
            "to every configured daemon before the first scan "
            "(spark.srml.daemon.addresses)"
        ),
        "get_iterate": "forest job has no iterate yet (set_iterate first)",
        "step": "step before the forest iterate is installed",
        "finalize": "finalize before any feed: no forest iterate",
    }
    no_iterate["staged_feed"] = no_iterate["feed"]

    def __init__(self, n_cols, mesh, params):
        super().__init__(n_cols, mesh, params)
        self.spec = forest_spec_from_params(params, n_cols)
        # Depth-0 capacity gate at creation (the Gram-capacity contract):
        # a clean first-feed error, never a mid-pass OOM.
        require_hist_capacity(self.spec, 0, n_cols)
        self.tables = None
        # Of the pass being folded: its signs (None: the whole frontier) ...
        self._signs = None
        # ... whether a state of this job has taken another daemon's since
        # the iterate was installed, and — between `step` and
        # `next_pass_state` alone — the histogram `step` was handed.
        self._merged = False
        self._parent = None

    @staticmethod
    def feed_classes(params) -> int:
        """A request's ``n_classes``: 0 (or absent) is regression."""
        return int(params.get("n_classes") or 0)

    @classmethod
    def check_labels(cls, params, y):
        # a classifier feed's labels validate like multinomial logreg's
        # (integers in [0, C))
        n_classes = cls.feed_classes(params)
        if n_classes > 0:
            from spark_rapids_ml_tpu.models.logistic_regression import (
                validate_multiclass_labels,
            )

            validate_multiclass_labels(y, n_classes)

    def feed_mismatch(self, params):
        want = self.feed_classes(params)
        if want != self.spec.n_classes:
            return (f"has n_classes={self.spec.n_classes}; "
                    f"feed carried n_classes={want}")
        return None

    @property
    def installed(self):
        return self.tables is not None

    def iterate_arrays(self):
        # Host-side tables: copies, so a later in-place grow cannot
        # mutate an already-shipped ledger/snapshot payload.
        return {k: np.array(v) for k, v in self.tables.items()}

    def install_iterate(self, arrays):
        # Copies: `grow_level` writes the tables in place, and an in-process
        # caller's arrays (the wire's are its own already) must stay the
        # iterate it installed.
        self.tables = {
            k: np.array(v) for k, v in validate_forest_arrays(
                arrays, self.spec, self.n_cols).items()
        }
        # an installed iterate comes with no histogram: its pass folds whole
        self._signs = self._parent = None
        self._merged = False

    def zero_state(self):
        if self.tables is None:
            return ()  # no iterate yet — feeds are rejected anyway
        return open_pass(self.tables, self.spec, self.n_cols)[0]

    def next_pass_state(self):
        parent, self._parent = self._parent, None
        state, self._signs = open_pass(
            self.tables, self.spec, self.n_cols, parent)
        return state

    def state_merged(self):
        self._merged = True

    def place_columns(self, target, y=None, n=0, partition=None, offset=0):
        # Bootstrap-bag identity: the batch's rows are (partition,
        # offset..offset+n) — read before this fold, so replays of a
        # restarted stage mint identical keys.
        ys = self._place_column(y, target, np.float64)
        ks = self._place_column(
            row_identity_keys(partition, offset, n), target, np.uint32)
        return ys, ks

    def fold(self, state, xs, ms, columns=(), n=0):
        ys, ks = columns
        return accumulate_histogram(
            state, self.tables, (xs,), (ys,), (ms,), (ks,), self.spec,
            self.mesh, n_valid=n, signs=self._signs,
        )

    def fold_group(self, state, xs, ms, columns=()):
        ys, ks = columns
        # The run's true rows, for the booking: what its masks count (a
        # cached batch's mask was placed before this pass began; the read
        # waits for no program).
        n = sum(int(np.count_nonzero(np.asarray(m))) for m in ms)
        return accumulate_histogram(
            state, self.tables, xs, ys, ms, ks, self.spec, self.mesh,
            n_valid=n, signs=self._signs,
        )

    def step(self, state, params):
        # the tables grow first: the job's next_pass_state() that follows,
        # in the same hold of the device lock, is of the NEW depth — and
        # starts from this histogram where it is this job's rows alone
        grown = close_pass(self.tables, state, self.spec, self._signs)
        self._parent = None if self._merged else state
        return {k: grown[k] for k in ("depth", "open_nodes", "splits")}

    def finalize(self, state, params, rows, iteration):
        out = {
            k: np.array(v) for k, v in self.tables.items() if k != "depth"
        }
        out["n_classes"] = np.asarray([self.spec.n_classes], np.int64)
        out["n_iter"] = np.asarray([iteration])
        return out


# ---------------------------------------------------------------------------
# In-memory fit (the single-process oracle of the daemon protocol)
# ---------------------------------------------------------------------------


class ForestSolution(NamedTuple):
    arrays: Dict[str, np.ndarray]
    n_classes: int
    n_rows: int
    n_passes: int


def _place_batch(x, y, mask, keys, mesh: Mesh):
    """Pad to the data-axis multiple and place row-sharded (the daemon
    fold's placement, shared so the in-memory fit compiles the same
    programs)."""
    n_data = mesh.shape[DATA_AXIS]
    xp, _ = pad_rows(np.asarray(x), n_data)
    pad = xp.shape[0] - x.shape[0]

    def padv(v, dtype):
        v = np.asarray(v, dtype).reshape(-1)
        return np.concatenate([v, np.zeros((pad,), dtype)]) if pad else v

    xs = jax.device_put(xp, row_sharding(mesh))
    v_sh = row_sharding(mesh, ndim=1)
    return (
        xs,
        jax.device_put(padv(y, np.float64), v_sh),
        jax.device_put(padv(mask, np.float32), v_sh),
        jax.device_put(padv(keys, np.uint32), v_sh),
    )


def _fit_forest(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: int,
    num_trees: int,
    max_depth: int,
    max_bins: int,
    feature_subset: str,
    seed: int,
    bootstrap: bool,
    min_instances: int,
    mesh: Optional[Mesh],
) -> ForestSolution:
    from spark_rapids_ml_tpu.parallel.sharding import require_single_process

    require_single_process(
        "fit_random_forest (quantile binning samples local data)"
    )
    mesh = mesh or default_mesh()
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64).reshape(-1)
    if x.ndim != 2 or x.shape[0] == 0:
        raise ValueError(f"features must be (n, d) with n > 0, got {x.shape}")
    if y.shape[0] != x.shape[0]:
        raise ValueError(
            f"labels length {y.shape[0]} != rows {x.shape[0]}"
        )
    n, d = x.shape
    spec = forest_spec_from_params(
        {
            "num_trees": num_trees, "max_depth": max_depth,
            "max_bins": max_bins, "n_classes": n_classes, "seed": seed,
            "bootstrap": bootstrap, "min_instances": min_instances,
            "subset": feature_subset,
        },
        n_cols=d,
    )
    if spec.n_classes > 0 and (
        np.any(y < 0) or np.any(y >= spec.n_classes) or np.any(y != np.floor(y))
    ):
        raise ValueError(
            f"classifier labels must be integers in [0, {spec.n_classes})"
        )
    with trace_span("forest binning"):
        cap = int(config.get("forest_seed_sample_rows"))
        edges = hist_ops.quantile_bin_edges(x[:cap], spec.max_bins)
    tables = init_forest_arrays(spec, edges)
    # Row identity for bootstrap bags: the whole matrix is "partition 0",
    # offset = row index — the daemon's (partition, offset) keying with
    # one partition, so a one-partition daemon fit reproduces this fit.
    keys = row_identity_keys(None, 0, n)
    mask = np.ones((n,), np.float32)
    n_passes = 0
    # Row-chunked passes, as the daemon path is chunked by feed batches.
    # Numerically free: histograms are sums.
    chunk = FIT_CHUNK_ROWS
    placed = [
        _place_batch(
            x[i: i + chunk], y[i: i + chunk], mask[i: i + chunk],
            keys[i: i + chunk], mesh,
        )
        for i in range(0, n, chunk)
    ]
    with trace_span("forest grow"):
        hist = None
        for depth in range(spec.max_depth + 1):
            if open_frontier_nodes(tables["feature"], depth) == 0:
                break
            # every pass but the first opens from the one before (the
            # single-daemon job's path: `step`, then `next_pass_state`)
            hist, signs = open_pass(tables, spec, d, parent=hist)
            for (xs, ys, ms, ks), i in zip(placed, range(0, n, chunk)):
                hist = accumulate_histogram(
                    hist, tables, (xs,), (ys,), (ms,), (ks,), spec, mesh,
                    n_valid=min(chunk, n - i), signs=signs,
                )
            close_pass(tables, hist, spec, signs)
            n_passes += 1
    arrays = dict(tables)
    arrays.pop("depth")
    arrays["n_classes"] = np.asarray([spec.n_classes], np.int64)
    return ForestSolution(
        arrays=arrays, n_classes=spec.n_classes, n_rows=n, n_passes=n_passes
    )


def fit_random_forest_classifier(
    x: np.ndarray,
    y: np.ndarray,
    n_classes: Optional[int] = None,
    num_trees: int = 20,
    max_depth: int = 5,
    max_bins: int = 32,
    feature_subset: str = "auto",
    seed: int = 0,
    bootstrap: bool = True,
    min_instances: int = 1,
    mesh: Optional[Mesh] = None,
) -> ForestSolution:
    """Gini-split random forest on binned features (Spark ML
    RandomForestClassifier semantics). ``n_classes=None`` infers
    ``max(y) + 1`` (>= 2)."""
    with trace_span("forest fit"):
        y = np.asarray(y, np.float64).reshape(-1)
        if n_classes is None:
            n_classes = max(int(np.max(y)) + 1 if y.size else 2, 2)
        return _fit_forest(
            x, y, int(n_classes), num_trees, max_depth, max_bins,
            feature_subset, seed, bootstrap, min_instances, mesh,
        )


def fit_random_forest_regressor(
    x: np.ndarray,
    y: np.ndarray,
    num_trees: int = 20,
    max_depth: int = 5,
    max_bins: int = 32,
    feature_subset: str = "auto",
    seed: int = 0,
    bootstrap: bool = True,
    min_instances: int = 1,
    mesh: Optional[Mesh] = None,
) -> ForestSolution:
    """Variance-split random forest on binned features (Spark ML
    RandomForestRegressor semantics)."""
    with trace_span("forest fit"):
        return _fit_forest(
            x, np.asarray(y, np.float64), 0, num_trees, max_depth,
            max_bins, feature_subset, seed, bootstrap, min_instances,
            mesh,
        )


# ---------------------------------------------------------------------------
# Prediction: descend all trees by gather in one jitted program
# ---------------------------------------------------------------------------


def _forest_predictor(arrays: Dict[str, np.ndarray], n_classes: int,
                      max_depth_hint: Optional[int] = None):
    """Jitted row-wise scorer with the tables device-resident: bins the
    batch, descends every tree to its leaf by repeated gather, and
    aggregates — mean of per-tree class distributions (argmax) for
    classification, mean of per-tree leaf means for regression. Returns
    role-keyed outputs (the daemon ``transform`` surface)."""
    # Tables upload in the accumulation dtype (matches the fit-time
    # binning precision; avoids per-call f64-truncation warnings on
    # non-x64 runtimes) — outputs cast back to f64 host-side.
    accum = jnp.dtype(config.get("accum_dtype"))
    edges = jnp.asarray(np.asarray(arrays["bin_edges"], np.float64), accum)
    feature = jnp.asarray(np.asarray(arrays["feature"], np.int32))
    threshold = jnp.asarray(np.asarray(arrays["threshold"], np.int32))
    value = jnp.asarray(np.asarray(arrays["value"], np.float64), accum)
    n_nodes = int(feature.shape[1])
    depth = (
        max_depth_hint if max_depth_hint is not None
        else max(int(math.ceil(math.log2(n_nodes + 1))) - 1, 1)
    )

    @ledgered_jit("random_forest.predict")
    def predict(x):
        bins = hist_ops.bin_matrix(x.astype(edges.dtype), edges)
        idx, _ = hist_ops.descend_to_frontier(bins, feature, threshold, depth)
        leaves = jnp.take_along_axis(
            value, idx[:, :, None].astype(jnp.int32), axis=1
        )  # (T, n, S)
        if n_classes > 0:
            counts = jnp.sum(leaves, axis=-1, keepdims=True)
            proba = jnp.mean(leaves / jnp.maximum(counts, 1.0), axis=0)
            pred = jnp.argmax(proba, axis=1).astype(accum)
            return pred, proba
        means = leaves[..., 1] / jnp.maximum(leaves[..., 0], 1.0)
        pred = jnp.mean(means, axis=0)
        return pred, pred[:, None]

    return predict


class _ForestModelBase(Model, MLWritable, MLReadable):
    """Shared fitted-forest surface: dense tables + jitted descend."""

    def __init__(self, arrays: Optional[Dict[str, np.ndarray]] = None,
                 uid=None):
        super().__init__(uid=uid)
        self.arrays = (
            None if arrays is None
            else {k: np.asarray(v) for k, v in arrays.items()}
        )
        self._summary = None
        self._predict_cache: dict = {}

    @property
    def numClasses(self) -> int:
        if self.arrays is None:
            return 0
        return int(np.asarray(self.arrays.get("n_classes", [0]))[0])

    @property
    def totalNumNodes(self) -> int:
        """Materialized nodes across all trees (internal + leaves):
        roots plus the children of every node that actually split — a
        vectorized level-order reachability sweep over the dense heap
        (O(maxDepth) numpy ops, not a Python walk of every slot)."""
        f = np.asarray(self.arrays["feature"])
        T, N = f.shape
        alive = np.zeros((T, N), bool)
        alive[:, 0] = True  # roots always materialize
        base, width = 0, 1
        while 2 * base + 2 < N:
            level = slice(base, base + width)
            split = alive[:, level] & (f[:, level] >= 0)
            base2 = 2 * base + 1
            alive[:, base2: base2 + 2 * width: 2] = split
            alive[:, base2 + 1: base2 + 2 * width: 2] = split
            base, width = base2, 2 * width
        return int(alive.sum())

    def getNumTrees(self) -> int:
        return int(np.asarray(self.arrays["feature"]).shape[0])

    def _model_data(self):
        return dict(self.arrays)

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(arrays=dict(data), uid=uid)

    def _copy_extra_state(self, source):
        self.arrays = source.arrays
        self._summary = getattr(source, "_summary", None)
        self._predict_cache = {}

    def _predictor(self):
        if self.arrays is None:
            raise RuntimeError("forest model has no trees (unfitted?)")
        key = (config.get("compute_dtype"), config.get("accum_dtype"))
        if key not in self._predict_cache:
            self._predict_cache[key] = _forest_predictor(
                self.arrays, self.numClasses
            )
        return self._predict_cache[key]

    def predict(self, x: np.ndarray) -> np.ndarray:
        fn = self._predictor()
        x = np.asarray(x)
        _M_TRANSFORM_ROWS.inc(
            int(x.shape[0]),
            role="classifier" if self.numClasses > 0 else "regressor",
        )
        return run_bucketed(lambda xb: fn(xb)[0], x)

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py; see PCAModel's) —
        shared by the classifier and regressor surfaces (one jit serves
        both predict and predict_proba slices)."""
        if self.arrays is None:
            return None
        from spark_rapids_ml_tpu.parallel.sharding import bucket_rows

        d = int(np.asarray(self.arrays["bin_edges"]).shape[0])
        if int(n_cols) != d:
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"model's fitted width {d}"
            )
        return [(
            self._predictor(),
            (jax.ShapeDtypeStruct(
                (bucket_rows(int(n_rows)), d), jnp.dtype(dtype)
            ),),
        )]

    def transform_matrix(self, x: np.ndarray) -> dict:
        """Role-keyed device transform (daemon ``transform`` op surface):
        bucketer-padded like every served model, so it coalesces through
        the serving scheduler unchanged."""
        if self.arrays is None:
            raise RuntimeError("forest model has no trees (unfitted?)")
        with trace_span("forest transform"):
            return {"prediction": np.asarray(self.predict(x), np.float64)}

    def _transform(self, dataset):
        if self.arrays is None:
            raise RuntimeError("forest model has no trees (unfitted?)")
        x = as_matrix(dataset, self.getFeaturesCol())
        return with_column(
            dataset, self.getPredictionCol(), self.predict(x)
        )


class _RandomForestParams(HasFeaturesCol, HasLabelCol, HasPredictionCol,
                          HasSeed):
    numTrees = ParamDecl(
        "numTrees", "number of trees (>= 1)", TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )
    maxDepth = ParamDecl(
        "maxDepth", f"maximum tree depth (1..{MAX_MAX_DEPTH})",
        TypeConverters.toInt, validator=ParamValidators.gt(0),
    )
    maxBins = ParamDecl(
        "maxBins", "feature-quantization bins (2..256; uint8 ids)",
        TypeConverters.toInt, validator=ParamValidators.gt(1),
    )
    featureSubsetStrategy = ParamDecl(
        "featureSubsetStrategy",
        "per-node candidate features: auto|all|sqrt|onethird|log2|<n>",
        TypeConverters.toString,
    )
    bootstrap = ParamDecl(
        "bootstrap", "Poisson(1) bootstrap bags per tree",
        TypeConverters.toBoolean,
    )
    minInstancesPerNode = ParamDecl(
        "minInstancesPerNode", "minimum rows each split side must keep",
        TypeConverters.toInt, validator=ParamValidators.gt(0),
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(
            numTrees=20,
            maxDepth=5,
            maxBins=32,
            featureSubsetStrategy="auto",
            bootstrap=True,
            minInstancesPerNode=1,
            seed=0,
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
        )

    def getNumTrees(self) -> int:
        return self.getOrDefault(self.numTrees)

    def getMaxDepth(self) -> int:
        return self.getOrDefault(self.maxDepth)

    def getMaxBins(self) -> int:
        return self.getOrDefault(self.maxBins)

    def getFeatureSubsetStrategy(self) -> str:
        return self.getOrDefault(self.featureSubsetStrategy)

    def getBootstrap(self) -> bool:
        return self.getOrDefault(self.bootstrap)

    def getMinInstancesPerNode(self) -> int:
        return self.getOrDefault(self.minInstancesPerNode)

    def setNumTrees(self, value: int):
        return self._set(numTrees=value)

    def setMaxDepth(self, value: int):
        return self._set(maxDepth=value)

    def setMaxBins(self, value: int):
        return self._set(maxBins=value)

    def setFeatureSubsetStrategy(self, value: str):
        return self._set(featureSubsetStrategy=value)

    def setBootstrap(self, value: bool):
        return self._set(bootstrap=value)

    def setMinInstancesPerNode(self, value: int):
        return self._set(minInstancesPerNode=value)


class RandomForestClassifier(Estimator, _RandomForestParams, MLWritable,
                             MLReadable):
    """``RandomForestClassifier().setNumTrees(50).fit(df)`` — Spark ML
    classification API shape over the histogram-tree core."""

    _uid_prefix = "RandomForestClassifier"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "RandomForestClassificationModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        y = as_column(dataset, self.getLabelCol())
        sol = fit_random_forest_classifier(
            x, y,
            num_trees=self.getNumTrees(),
            max_depth=self.getMaxDepth(),
            max_bins=self.getMaxBins(),
            feature_subset=self.getFeatureSubsetStrategy(),
            seed=self.getSeed(),
            bootstrap=self.getBootstrap(),
            min_instances=self.getMinInstancesPerNode(),
            mesh=self._mesh,
        )
        model = RandomForestClassificationModel(arrays=sol.arrays)
        model.uid = self.uid
        self._copy_params_to(model)
        return model


class RandomForestClassificationModel(_ForestModelBase, _RandomForestParams):
    _uid_prefix = "RandomForestClassificationModel"

    # Daemon serving contract (serve/daemon.py).
    _serve_algo = "rf_classifier"
    _serve_outputs = (("prediction", "predictionCol", "double"),)

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        fn = self._predictor()
        x = np.asarray(x)
        _M_TRANSFORM_ROWS.inc(int(x.shape[0]), role="classifier")
        return run_bucketed(lambda xb: fn(xb)[1], x)


class RandomForestRegressor(Estimator, _RandomForestParams, MLWritable,
                            MLReadable):
    """``RandomForestRegressor().setNumTrees(50).fit(df)`` — Spark ML
    regression API shape over the histogram-tree core."""

    _uid_prefix = "RandomForestRegressor"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "RandomForestRegressionModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        y = as_column(dataset, self.getLabelCol())
        sol = fit_random_forest_regressor(
            x, y,
            num_trees=self.getNumTrees(),
            max_depth=self.getMaxDepth(),
            max_bins=self.getMaxBins(),
            feature_subset=self.getFeatureSubsetStrategy(),
            seed=self.getSeed(),
            bootstrap=self.getBootstrap(),
            min_instances=self.getMinInstancesPerNode(),
            mesh=self._mesh,
        )
        model = RandomForestRegressionModel(arrays=sol.arrays)
        model.uid = self.uid
        self._copy_params_to(model)
        return model


class RandomForestRegressionModel(_ForestModelBase, _RandomForestParams):
    _uid_prefix = "RandomForestRegressionModel"

    # Daemon serving contract (serve/daemon.py).
    _serve_algo = "rf_regressor"
    _serve_outputs = (("prediction", "predictionCol", "double"),)
