"""Binned-feature histograms for tree ensembles — the first non-GEMM op
family in the package (ROADMAP item 4a).

Three pieces, all shaped for the accelerator rather than ported from a
CPU tree library:

* **Quantile-sketch binning** (:func:`quantile_bin_edges` host-side,
  :func:`bin_matrix` on device): features quantize to uint8 bin ids
  against per-feature edge vectors, so the per-node split search becomes
  a dense histogram problem with a STATIC bin axis — the LightGBM/XGBoost
  "hist" idea, which is also exactly what a fixed-shape compiler wants
  (PAPERS.md 1703.08219: keep the whole pipeline inside one compiled
  program; a sort-based exact split search is shape-dynamic and hostile
  to XLA).

* **Fused per-node histogram builder** (:func:`hist_update_group_fn`):
  one jitted, donated dispatch per run of batches does bin →
  descend-to-frontier → scatter into the ``(tree, node, feature, bin,
  stat)`` tensor, walking each batch in row chunks inside the program (a
  65,536 x 3,000 batch is never expanded whole). The scatter is
  formulated as a one-hot × stats contraction (an einsum over the row
  axis) instead of a gather/scatter loop — MXU-shaped; on the chip its
  bin one-hot exists only tile by tile in VMEM
  (``pallas_kernels.hist_onehot_matmul_pallas``); the
  per-shard partials reduce with ``parallel.mapreduce.reduce_sum``
  (DrJAX psum; PAPERS.md 2403.07128) like every other sufficient
  statistic in the package. Histograms are ADDITIVE, so the tensor rides
  the daemon's cross-daemon merge/reduce_mesh plane completely unchanged.
  The contraction spends a row of its left operand on every frontier node
  though a data row stands on ONE of them, so from depth 1 on a pass that
  holds the complete histogram one depth up (:func:`seed_hist`) folds one
  child of every split only — the one with fewer rows — at half the height,
  and its sibling is the parent less it, subtracted as the product joins
  the accumulator (LightGBM's and XGBoost-hist's histogram subtraction).

* **Vectorized best-split scoring** (:func:`best_splits_fn`): cumulative
  sums along the bin axis give every (feature, threshold) candidate's
  left/right statistics at once; Gini (classification) and variance
  (regression) gains reduce to the shared ``Σg²/n`` form, scored and
  arg-maxed for ALL frontier nodes of ALL trees in one device program,
  tree by tree (no second tensor the size of the frontier's).

Stat layout (the ``S`` axis): classification keeps per-class counts
(``S = n_classes``; the count is their sum), regression keeps
``(count, Σy, Σy²)`` (``S = 3``). Both are plain sums of per-row terms,
so bootstrap resampling is a per-(tree, row) WEIGHT on those terms —
Poisson(1) weights derived from a counter-based hash of the row's
(partition, offset) identity, deterministic under task retries and
independent of batch boundaries (models/random_forest.py owns the tree
tables; docs/protocol.md "The `rf` job algo" has the wire contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit
from jax.sharding import PartitionSpec as P

#: Node-table sentinels (models/random_forest.py dense (tree, node)
#: layout): an OPEN node is on the current frontier awaiting its split;
#: a LEAF is closed (or was never created). Internal nodes store the
#: split feature id (>= 0).
OPEN = -2
LEAF = -1

#: Poisson(1) CDF at 0..5 — the lookup a uniform hash inverts to a
#: bootstrap weight (w = #thresholds below u, capped at 6). The tail
#: past 6 carries < 1e-4 of the mass.
_POISSON1_CDF = (
    0.36787944117144233,
    0.7357588823428847,
    0.9196986029286058,
    0.9810118431238462,
    0.9963401531726563,
    0.9994058151824183,
)


def quantile_bin_edges(sample: np.ndarray, max_bins: int) -> np.ndarray:
    """Per-feature quantile bin edges from a host-side sample.

    Returns ``(d, max_bins - 1)`` float64 interior edges; bin id =
    ``sum(x > edges)`` ∈ [0, max_bins). Duplicate edges (skewed or
    constant features) simply leave some bins empty — the split scorer
    sees zero-count candidates and never picks them. Deterministic: the
    edges ARE part of the model iterate, so every daemon bins
    identically once seeded (the kmeans-seed pattern)."""
    sample = np.asarray(sample, dtype=np.float64)
    if sample.ndim != 2 or sample.shape[0] == 0:
        raise ValueError(f"edge sample must be (n, d) with n > 0, got {sample.shape}")
    if not 2 <= int(max_bins) <= 256:
        raise ValueError(
            f"max_bins = {max_bins} out of range [2, 256] (bin ids are uint8)"
        )
    qs = np.linspace(0.0, 1.0, int(max_bins) + 1)[1:-1]
    # feature-major first: a column's values lie together for its
    # partition (the same numbers as over axis 0, in half the time at
    # 65,536 x 3,000)
    edges = np.quantile(np.ascontiguousarray(sample.T), qs, axis=1).T  # (d, B-1)
    return np.ascontiguousarray(edges, dtype=np.float64)


def bin_matrix(x, edges):
    """Device binning: ``(n, d)`` values against ``(d, B-1)`` edges →
    ``(n, d)`` int32 bin ids (``sum(x > edge)``; uint8-range by the
    max_bins cap). One broadcast compare + reduce — no sort, no loop."""
    return jnp.sum(
        x[:, :, None] > edges[None, :, :], axis=-1, dtype=jnp.int32
    )


def _hash_u32(h, xp=jnp):
    """splitmix-style avalanche on uint32 lanes (counter-based RNG: the
    weight of a row must be a pure function of its identity, never of
    batch boundaries or arrival order). ``xp``: ``jnp`` on the device,
    ``np`` for what is a function of static numbers alone."""
    h = xp.asarray(h, xp.uint32)
    h = h ^ (h >> xp.uint32(16))
    h = h * xp.uint32(0x7FEB352D)
    h = h ^ (h >> xp.uint32(15))
    h = h * xp.uint32(0x846CA68B)
    return h ^ (h >> xp.uint32(16))


def bootstrap_weights(row_key, n_trees: int, seed: int):
    """Poisson(1) bootstrap weights, ``(T, n)`` float32, from per-row
    uint32 identity keys: tree t's bag is an i.i.d.-looking but fully
    deterministic function of (seed, t, row identity) — identical under
    task retries, batch re-chunking, and daemon re-routing."""
    keys = jnp.asarray(row_key, jnp.uint32)[None, :]
    tweak = (
        jnp.arange(n_trees, dtype=jnp.uint32)[:, None]
        * jnp.uint32(0x9E3779B1)
        + jnp.uint32(np.uint32(seed & 0xFFFFFFFF))
    )
    u = _hash_u32(keys ^ _hash_u32(tweak)).astype(jnp.float32) * jnp.float32(
        1.0 / 4294967296.0
    )
    cdf = jnp.asarray(_POISSON1_CDF, jnp.float32)
    return jnp.sum(
        u[:, :, None] > cdf[None, None, :], axis=-1, dtype=jnp.int32
    ).astype(jnp.float32)


def descend_to_frontier(bins, feature, threshold, depth: int):
    """Route every row to its heap node index at ``depth`` in every tree.

    ``bins``: (n, d) int32; ``feature``/``threshold``: (T, N) int32 node
    tables (heap layout: children of i are 2i+1 / 2i+2; OPEN/LEAF < 0).
    Returns ``(idx (T, n) int32, alive (T, n) bool)`` — ``alive`` is
    False for rows that hit a leaf above ``depth`` (they are settled and
    contribute to no frontier histogram). A static Python loop of
    ``depth`` steps: the trees grow level-synchronously, so one compiled
    program per depth is the whole compile budget."""
    T = feature.shape[0]
    n = bins.shape[0]
    d = bins.shape[1]
    idx = jnp.zeros((T, n), jnp.int32)
    alive = jnp.ones((T, n), jnp.bool_)
    rows = jnp.arange(n, dtype=jnp.int32)[None, :]
    for _ in range(depth):
        f = jnp.take_along_axis(feature, idx, axis=1)
        internal = f >= 0
        bin_at = bins[rows, jnp.clip(f, 0, d - 1)]
        thr = jnp.take_along_axis(threshold, idx, axis=1)
        go_right = (bin_at > thr).astype(jnp.int32)
        idx = jnp.where(internal, 2 * idx + 1 + go_right, idx)
        alive = alive & internal
    return idx, alive


def route_to_frontier(bins, feature, threshold, depth: int, dtype):
    """The fold's own descent: every row's position ON the frontier of
    ``depth`` (0 .. 2^depth - 1) in every tree, ``(pos (T, n) int32, alive
    (T, n) bool)`` — :func:`descend_to_frontier`'s routing to the bit
    (``pos = idx - (2^depth - 1)`` where ``alive``), made of dense products
    and selects: a level's "bin at the node's split feature" is ONE small
    product of the rows' bin ids with the level's split features one-hot,
    ``(T * nodes, d) x (d, n)``, and a node's threshold, feature and
    state reach a row through its node one-hot. The chip runs a gather of
    (tree, row) pairs at tens of nanoseconds an element — 0.06-0.09 s a
    level and 65,536-row batch, a fifth of a deep level's fold (PERF.md
    §6, PR 37) — and this in a few milliseconds. ``dtype``: the product's
    operands (bin ids are whole numbers under 256: exact from bfloat16 up,
    accumulated in float32)."""
    T = feature.shape[0]
    n, d = bins.shape
    pos = jnp.zeros((T, n), jnp.int32)
    alive = jnp.ones((T, n), jnp.bool_)
    ids = bins.astype(dtype)
    for level in range(depth):
        width = 1 << level
        nodes = slice(width - 1, 2 * width - 1)
        feat, thr = feature[:, nodes], threshold[:, nodes]  # (T, width)
        splits_on = jax.nn.one_hot(jnp.clip(feat, 0, d - 1), d, dtype=dtype)
        bin_at = jnp.einsum(
            "twd,nd->twn", splits_on, ids,
            preferred_element_type=jnp.float32,
        )
        here = pos[:, None, :] == jnp.arange(width, dtype=jnp.int32)[None, :, None]
        right = jnp.any(
            here & (bin_at > thr[:, :, None].astype(jnp.float32)), axis=1)
        internal = jnp.any(here & (feat >= 0)[:, :, None], axis=1)
        pos = jnp.where(internal, 2 * pos + right.astype(jnp.int32), pos)
        alive = alive & internal
    return pos, alive


#: Rows of a shard the fold's XLA body walks at a time inside its one
#: program (the fused body, which builds no one-hot block:
#: `_FUSED_CHUNK_ROWS`). The batch is never expanded whole: a chunk's bin
#: one-hot is ``chunk * d * B`` elements, built a feature block at a time
#: (`_ONEHOT_BLOCK_BYTES`), and the frontier accumulator is read and
#: written once a chunk — a long chunk, so that the contraction and not
#: that traffic is what a chunk costs ...
FOLD_CHUNK_ROWS = 16384
#: ... and a short one while the contraction's left operand (trees x slots
#: x channels; slots: the frontier's nodes, or its pairs in a halved fold)
#: is under `_SHORT_CHUNKS_BELOW_ROWS` rows tall. The operand's height
#: decides, not the frontier tensor's bytes, and alike for both folds: for
#: 131,072 rows of 3,000 columns 210 rows read 0.32 s short and 0.65 long
#: (depth 0; the halved depth 1: 0.34 / 0.65), 420 rows 0.42 / 0.37 (depth
#: 1) and 0.48 / 0.38 (the halved depth 2), 840 rows 0.79 / 0.58 (PERF.md
#: §6, PR 37; PR 36's descent by gathers had put the line at 840).
_SHORT_CHUNK_ROWS = 4096
_SHORT_CHUNKS_BELOW_ROWS = 320
#: From this many rows of the contraction's left operand on, the XLA body
#: writes a feature block's one-hot out before the contraction instead of
#: generating it inside it: the product then runs at 85% of the MXU's bfloat16
#: peak instead of 61%, which is worth the one-hot's trip through HBM once
#: the product is tall — 3,360 rows 1.83 s written out against 2.25 (depth
#: 4; the halved depth 5: 1.94 / 2.35), 1,680 rows 1.30 / 1.16 (depth 3;
#: the halved depth 4: 1.35 / 1.21) (PERF.md §6, PRs 36 and 37).
_MATERIALIZE_FROM_ROWS = 2048
#: Rows of a float32 tile on the chip: the grain of the accumulator's
#: feature axis (its bins are the 128 lanes).
_FEATURE_TILE = 8
#: The XLA body builds a chunk's bin one-hot in feature blocks of at most
#: this size ...
_ONEHOT_BLOCK_BYTES = 512 << 20
#: ... and either body takes a feature block no larger than leaves its
#: product — every tree's and node's, before it joins the accumulator — at
#: most this size.
_PRODUCT_BLOCK_BYTES = 384 << 20
#: The scorer takes a frontier tensor of at most this size whole (its
#: transient is a few times that), a larger one tree by tree.
_SCORE_BLOCK_BYTES = 640 << 20
#: Digits a label statistic travels in when the compute dtype holds fewer
#: mantissa bits than the accumulation dtype (bfloat16 under float32: the
#: MXU's narrow operands). The contraction's other operand is 0/1, so a
#: term may travel as whole-number digits and the products accumulate
#: exactly: a chunk's terms are scaled by one power of two to 22 bits and
#: a sign, cut into three balanced base-256 digits — int8, the MXU's
#: fastest operand — multiplied into int32 and put together again in the
#: accumulation dtype. What is dropped is under 2^-22 of the chunk's
#: largest term (whole-number labels up to 2^22 travel exactly): the order
#: of a float32 accumulator's own rounding of such a sum.
_DIGITS = 3
_DIGIT_BITS = 8 * _DIGITS - 2
#: Rows of a shard the fold walks at a time where the bin one-hot is made
#: in VMEM (`_fused_hist_fold_applicable`): no block of it exists whose
#: bytes would bound the chunk, so a chunk is as long as a digit's int32
#: sums stay whole numbers of the accumulation dtype — 65,536 rows of
#: digits up to 128: under 2^24 — and the frontier accumulator is read and
#: written once a 65,536-row batch, not four times.
_FUSED_CHUNK_ROWS = 65536

_M_FOLD_PATH = metrics.counter(
    "srml_forest_fold_path_total",
    "Dispatches of the forest's histogram fold (histogram.update_group) by "
    "the body their program was built with: path=fused (the bin one-hot made "
    "in VMEM by hist_onehot_matmul_pallas) or path=xla (CPU, float32/float64 "
    "compute, a bin count off the 128-lane grid, chunk rows no multiple of "
    "512: jax.nn.one_hot and XLA's product)",
)


def _fused_hist_fold_applicable(
    shard_rows: int, operand, max_bins: int, use_pallas=None
) -> bool:
    """`hist_update_group_fn`'s gate for the kernel that makes the bin
    one-hot in VMEM (ops/pallas_kernels.hist_onehot_matmul_pallas), by what
    the code can observe: TPU backend, the narrow operand case (int8:
    bfloat16 compute under float32 accumulate, the chip's `auto` profile —
    a float32 or float64 operand keeps XLA's product in its own precision),
    a bin count on the 128-lane grid (a feature's bins are whole lane tiles
    of the product; Spark's default `maxBins` 32 keeps the XLA body), and a
    chunk — the shard's rows, at most `_FUSED_CHUNK_ROWS` — of whole row
    tiles (the constant imported from the kernel so the two cannot drift).
    Any height of the left operand: the kernel walks a tall one in parts
    of its VMEM budget, and was the faster at every height the cell has
    (PERF.md §6, PR 39)."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    if not _pallas_backend_ok(use_pallas):
        return False
    from spark_rapids_ml_tpu.ops.pallas_kernels import HIST_ONEHOT_ROW_MULTIPLE

    c = min(int(shard_rows), _FUSED_CHUNK_ROWS)
    return (
        jnp.dtype(operand) == jnp.dtype(jnp.int8)
        and max_bins % 128 == 0
        and c > 0
        and c % HIST_ONEHOT_ROW_MULTIPLE == 0
    )


def _digits(v):
    """``v`` (float) → (``_DIGITS`` int8 arrays, least significant first,
    the float32 factor that takes ``sum(digit_k * 256**k)`` back to ``v``)."""
    _, e = jnp.frexp(jnp.max(jnp.abs(v)))  # max|v| <= 2**e
    q = jnp.round(jnp.ldexp(v, _DIGIT_BITS - e)).astype(jnp.int32)
    out = []
    for _ in range(_DIGITS - 1):
        digit = ((q + 128) & 255) - 128  # balanced: in [-128, 127]
        out.append(digit.astype(jnp.int8))
        q = (q - digit) >> 8
    out.append(q.astype(jnp.int8))
    return out, jnp.ldexp(jnp.float32(1.0), e - _DIGIT_BITS)


@functools.lru_cache(maxsize=64)
def hist_update_group_fn(
    mesh, n_trees: int, max_bins: int, depth: int,
    n_classes: int, bootstrap: bool, seed: int, ad: str, cd: str,
    halved: bool = False, use_pallas: bool = False,
):
    """Build the fused per-depth histogram accumulate for one mesh:
    ``(hist, tables, xs, ys, masks, row_keys) -> hist`` with ``hist``
    donated, ``tables = (edges, feature, threshold)`` the replicated
    iterate and the other four TUPLES of equal length — a run of placed
    batches folded in order in ONE program (a feed's batch is a run of
    one; the daemon's cached pass a run of ``serve/daemon.py``
    ``_RESCAN_GROUP``). One device dispatch does, for each batch and each
    chunk of its rows: bin → descend → weight → one-hot contraction, the
    accumulator carried from chunk to chunk and from batch to batch; the
    per-shard partials meet in one ``reduce_sum`` a batch. The returned
    (T, W, d, B, S) tensor is replicated (it is the pass's sufficient
    statistic, exactly like a Gram block).

    ``halved`` (``depth >= 1``) is the fold of a pass whose state was
    seeded from the parent's histogram (:func:`seed_hist`): ``tables``
    then ends in ``signs`` (T, W/2, 2), the children of every frontier
    PAIR marked +1 (folded), -1 (derived: its slot holds the parent's
    histogram) or 0 (closed). The node one-hot runs over the W/2 pairs and
    weighs only the rows that stand on a pair's folded child, so the
    contraction is half as tall — depth ``d``'s costs what depth ``d - 1``'s
    did; the product joins the folded child's slot of the accumulator as it
    is and a derived sibling's negated: parent − child, what is left of the
    parent's rows. The fold stays a sum of per-row terms, so stages, runs
    of batches and the shards' ``reduce_sum`` (of a partial half as tall)
    add up as they do for the whole frontier, and once every row of the
    parent's pass is folded the state IS the frontier's histogram. A count
    channel's difference is exact (whole numbers under 2^24 in float32); a
    label statistic's carries one more rounding of the accumulation dtype a
    chunk, against the parent's cell.

    The "scatter" is a contraction over the row axis — MXU-shaped: the
    ``(T * W * channels, rows)`` matrix of node one-hots times bag weight
    times statistic against a feature block's ``(rows, features * B)``
    bin one-hot, the product accumulated in ``ad``. Where the compute
    dtype ``cd`` holds what ``ad`` holds (the CPU profiles) the operands
    are of it and a statistic is one channel. Where it is narrower
    (bfloat16 under float32, the chip) the operands are int8 — a count
    channel's factors (0/1, a bag weight up to 6) are whole numbers, and a
    label statistic travels as `_DIGITS` whole-number digits (`_digits`) —
    so nothing is rounded that a float32 accumulation would keep. Nothing
    of size rows x d x B x S, nor a second frontier tensor, ever exists:
    the transient is one feature block's one-hot and its product.

    ``use_pallas`` (the caller's snapshot of the config key, so that it
    keys the cached programs): where `_fused_hist_fold_applicable` holds
    for a batch's shard — the chip's int8 operands at a bin count on the
    lane grid — not even that one-hot exists: a block's product is
    `hist_onehot_matmul_pallas`, which makes the one-hot tile by tile in
    VMEM, and a chunk is `_FUSED_CHUNK_ROWS` long. The products are the
    same whole numbers either way; the XLA body below is what runs
    everywhere else, and the kernel's oracle.

    ``n_classes = 0`` selects the regression stat layout (count, Σy,
    Σy²); otherwise per-class counts. All one-hot factors are exact small
    integers, so fold order — chunking and grouping included — cannot
    perturb the count channel, classification histograms or
    integer-labeled regression."""
    accum, compute = jnp.dtype(ad), jnp.dtype(cd)
    W = 1 << depth
    n_stats = n_classes if n_classes > 0 else 3
    narrow = jnp.finfo(compute).nmant < jnp.finfo(accum).nmant
    operand, product = (jnp.int8, jnp.int32) if narrow else (compute, accum)
    digits = _DIGITS if narrow else 1
    route_dtype = compute if narrow else jnp.float32
    # channel -> the statistic it is a part of
    stat_of = (
        tuple(range(n_stats)) if n_classes > 0
        else (0,) + (1,) * digits + (2,) * digits
    )
    n_ch = len(stat_of)
    one_device = mesh.shape[DATA_AXIS] == 1
    if halved and depth < 1:
        raise ValueError("a halved fold needs a parent: depth >= 1")
    # what the node one-hot runs over: the frontier's nodes, or its pairs
    slots = W // 2 if halved else W

    def signed(h, signs):
        """A pairs' histogram (T, W/2, ...) → (T, W/2, 2, ...): what it
        adds to each child's slot (+h folded, -h derived, 0 closed)."""
        return signs.reshape(signs.shape + (1,) * (h.ndim - 2)) * h[:, :, None]

    def chunk_operand(tables, x, y, mask, row_key):
        """One chunk's rows → (bins (c, d) int32, lhs (T * slots * n_ch,
        c), scales (n_ch,): what a channel's product is multiplied by)."""
        edges, feature, threshold = tables[:3]
        c = x.shape[0]
        bins = bin_matrix(x.astype(edges.dtype), edges)
        pos, alive = route_to_frontier(
            bins, feature, threshold, depth, route_dtype)
        # Contributing rows: unpadded, not settled at a shallower leaf ...
        w = (alive & (mask > 0)[None, :]).astype(accum)
        if bootstrap:
            w = w * bootstrap_weights(row_key, n_trees, seed).astype(accum)
        # ... and standing on a node that is actually OPEN this pass — of
        # a halved pass: on a pair's folded child, and under the pair.
        weighs = (
            tables[3].reshape(n_trees, W) > 0 if halved
            else feature[:, W - 1: 2 * W - 1] == OPEN
        )
        node = (
            pos[:, None, :] == jnp.arange(W, dtype=jnp.int32)[None, :, None]
        ) & weighs[:, :, None]  # (T, W, c)
        if halved:
            node = jnp.any(node.reshape(n_trees, slots, 2, c), axis=2)
        node_w = node.astype(accum) * w[:, None, :]  # (T, slots, c)
        ones = jnp.ones((), accum)
        if n_classes > 0:
            stat = jax.nn.one_hot(
                jnp.clip(y.astype(jnp.int32), 0, n_classes - 1),
                n_classes, dtype=accum,
            ).T  # (C, c): 0/1, whole numbers in any dtype
            channels = [
                (node_w * stat[s][None, None, :]).astype(operand)
                for s in range(n_classes)
            ]
            scales = [ones] * n_classes
        else:
            ya = y.astype(accum)
            channels, scales = [node_w.astype(operand)], [ones]
            for label in (ya, ya * ya):
                v = node_w * label[None, None, :]
                if narrow:
                    parts, unit = _digits(v)
                    channels += parts
                    scales += [
                        (unit * 256.0 ** k).astype(accum)
                        for k in range(digits)
                    ]
                else:
                    channels.append(v.astype(operand))
                    scales.append(ones)
        lhs = jnp.stack(channels, axis=2)  # (T, slots, n_ch, c)
        return bins, lhs.reshape(n_trees * slots * n_ch, c), jnp.stack(scales)

    def fold_chunk(acc, bins, lhs, scales, signs=None, fused=False):
        """acc (T, slots, S, d, B) += the chunk's histogram, a feature
        block at a time: every tree's and slot's channels against the
        block's bin one-hot in one contraction, joined to the accumulator
        in place. With ``signs`` the accumulator is the whole frontier's,
        seen by pairs — (T, W/2, 2, S, d, B) — and a pair's product joins
        both of its children's slots, each under its sign. ``fused``: the
        contraction is the kernel's, which takes the bin ids themselves —
        rows along the lanes, as the chip keeps what is computed from an
        (n, 3000) batch — and no one-hot block bounds the feature block."""
        c, d = bins.shape
        db = max(1, min(
            d,
            _PRODUCT_BLOCK_BYTES
            // (n_trees * slots * n_ch * max_bins * accum.itemsize),
            d if fused else _ONEHOT_BLOCK_BYTES
            // (c * max_bins * jnp.dtype(operand).itemsize),
        ))
        n_blocks = -(-d // db)
        db = -(-d // n_blocks)  # equal blocks; the last may reach back
        # In whole tiles of the accumulator's feature axis where d allows
        # it, so that a block joins at an aligned offset (with the index
        # unsigned — no wrap-around select — the chip makes the join one
        # fused add-and-update: 0.065 s a batch at the halved depth 5
        # where an update after an add took 0.24; PERF.md §6, PR 37).
        tile = _FEATURE_TILE if d % _FEATURE_TILE == 0 else 1
        db = min(d, -(-db // tile) * tile)
        n_blocks = -(-d // db)
        if fused:
            from spark_rapids_ml_tpu.ops import pallas_kernels

            bins = bins.T  # (d, c)

        def block(i, acc):
            # Block i covers features [i * db, (i + 1) * db); where that
            # would pass d it starts at d - db instead and the columns it
            # shares with the block before are blanked (bin id -1: an
            # all-zero one-hot), so one loop of one shape covers any d.
            f0 = tile * jnp.minimum(
                i * (db // tile), (d - db) // tile).astype(jnp.uint32)
            shared = (
                jnp.arange(db, dtype=jnp.int32) < i * db - f0.astype(jnp.int32))
            cols = jax.lax.dynamic_slice_in_dim(
                bins, f0, db, axis=0 if fused else 1)
            cols = jnp.where(
                shared[:, None] if fused else shared[None, :], -1, cols)
            if fused:
                h = pallas_kernels.hist_onehot_matmul_pallas(
                    lhs, cols, n_bins=max_bins)
                return join(acc, h, f0)
            bin_oh = jax.nn.one_hot(cols, max_bins, dtype=operand)
            if lhs.shape[0] >= _MATERIALIZE_FROM_ROWS:
                # written out, then a plain matrix product
                h = jnp.matmul(
                    lhs,
                    jax.lax.optimization_barrier(
                        bin_oh.reshape(c, db * max_bins)),
                    preferred_element_type=product,
                )
            else:
                # generated inside the contraction's own fusion
                h = jnp.einsum(
                    "mn,ndb->mdb", lhs, bin_oh,
                    preferred_element_type=product,
                )
            return join(acc, h, f0)

        def join(acc, h, f0):
            """A block's product (T * slots * n_ch, db * B) → its
            statistics, added to the accumulator's features from f0."""
            h = h.reshape(n_trees, slots, n_ch, db, max_bins)
            # the parts of one statistic join smallest first
            h = jnp.stack(
                [
                    functools.reduce(
                        jnp.add,
                        [h[:, :, k].astype(accum) * scales[k]
                         for k in range(n_ch) if stat_of[k] == s],
                    )
                    for s in range(n_stats)
                ],
                axis=2,
            )  # (T, slots, S, db, B)
            if signs is not None:
                h = signed(h, signs)
            zero = jnp.zeros((), jnp.uint32)
            at = (zero,) * (h.ndim - 2) + (f0, zero)
            old = jax.lax.dynamic_slice(acc, at, h.shape)
            return jax.lax.dynamic_update_slice(acc, old + h, at)

        return jax.lax.fori_loop(0, n_blocks, block, acc)

    def fold_batch(acc, tables, x, y, mask, row_key, signs=None):
        n = x.shape[0]
        fused = _fused_hist_fold_applicable(n, operand, max_bins, use_pallas)
        short = n_trees * slots * n_ch < _SHORT_CHUNKS_BELOW_ROWS
        c = min(n, _FUSED_CHUNK_ROWS if fused
                else _SHORT_CHUNK_ROWS if short else FOLD_CHUNK_ROWS)
        n_chunks = -(-n // c)
        pad = n_chunks * c - n
        if pad:  # a ragged tail folds as masked rows
            x = jnp.pad(x, ((0, pad), (0, 0)))
            y, mask, row_key = (jnp.pad(a, (0, pad)) for a in (y, mask, row_key))

        def chunk(i, acc):
            rows = [
                jax.lax.dynamic_slice_in_dim(a, i * c, c, axis=0)
                for a in (x, y, mask, row_key)
            ]
            return fold_chunk(
                acc, *chunk_operand(tables, *rows), signs=signs, fused=fused
            )

        return jax.lax.fori_loop(0, n_chunks, chunk, acc)

    def shard(hist, tables, x, y, mask, row_key):
        signs = tables[3] if halved else None
        if one_device:
            # no partial to reduce: the batch joins the frontier tensor
            # itself, and no second tensor of its size exists
            return fold_batch(hist, tables, x, y, mask, row_key, signs)
        # the shards' partial is of the contraction's height: the
        # frontier's nodes, or (halved) its pairs, signed after the sum
        h = fold_batch(
            jnp.zeros((n_trees, slots) + hist.shape[-3:], accum),
            tables, x, y, mask, row_key,
        )
        h = mr.reduce_sum(h, DATA_AXIS)
        return hist + (signed(h, signs) if halved else h)

    f = mr.map_fn(
        shard,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
        ),
        out_specs=P(),
        # the accumulator is carried through the chunk loop, replicated on
        # the way in and (on several devices) a per-shard partial inside
        check_vma=False,
    )

    def hist_update_group(hist, tables, xs, ys, masks, row_keys):
        # Inside the program the statistic axis stands before the feature
        # axis, (T, W, S, d, B): the order the contraction writes, and the
        # order the chip keeps a (T, W, d, B, S) array in anyway (bins
        # minor, the statistic axis of 3 never a padded tile) — there the
        # two moves are no copy, and no second frontier tensor exists. A
        # halved fold sees the node axis by pairs, (T, W/2, 2, ...).
        acc = jnp.moveaxis(hist, 4, 2)
        if halved:
            acc = acc.reshape((n_trees, slots, 2) + acc.shape[2:])
        pending = tuple(zip(xs, ys, masks, row_keys))
        while pending:
            batch, pending = pending[0], pending[1:]
            # the barrier keeps a run its calls bit for bit (XLA may not
            # merge two batches' loops or reorder their additions) — and,
            # over the batches still to come, one batch's temporaries at a
            # time: nothing of a later batch (its bin ids, its operand: 1.8
            # GB where a chunk is a whole batch) is made before this one
            # has joined the accumulator
            acc, pending = jax.lax.optimization_barrier(
                (f(acc, tables, *batch), pending)
            )
        if halved:
            acc = acc.reshape((n_trees, W) + acc.shape[3:])
        return jnp.moveaxis(acc, 2, 4)

    # One ledger name pools every depth's and every run length's
    # accounting (distinct shape-signatures under it — the ledger's own
    # keying); in a device trace the program is `jit_hist_update_group`.
    update = ledgered_jit(
        "histogram.update_group", hist_update_group, donate_argnums=(0,)
    )

    @functools.lru_cache(maxsize=None)
    def path(rows: int) -> str:
        fused = _fused_hist_fold_applicable(
            rows // mesh.shape[DATA_AXIS], operand, max_bins, use_pallas)
        return "fused" if fused else "xla"

    # One count a dispatch, by the body the program of that batch shape was
    # built with: the gate's own predicate, asked once a shape (a run is
    # one shape).
    update.on_dispatch = lambda hist, tables, xs, *cols: _M_FOLD_PATH.inc(
        path=path(xs[0].shape[0]))
    return update


def zero_hist(n_trees: int, depth: int, n_cols: int, max_bins: int,
              n_stats: int, ad) -> jnp.ndarray:
    """Zero (T, 2^depth, d, B, S) accumulator for one frontier pass."""
    return jnp.zeros(
        (n_trees, 1 << depth, n_cols, max_bins, n_stats), jnp.dtype(ad)
    )


@ledgered_jit("histogram.seed_frontier")
def seed_hist(parent, signs):
    """What a pass already knows of its frontier before a row is folded:
    the parent's complete histogram ``(T, W/2, d, B, S)`` in the slots of
    the children that will be DERIVED from it (``signs (T, W/2, 2) < 0``),
    zeros elsewhere → ``(T, W, d, B, S)``, the state the halved fold of
    :func:`hist_update_group_fn` brings to the whole frontier's."""
    kids = jnp.where(
        (signs < 0)[:, :, :, None, None, None], parent[:, :, None], 0
    )
    T, pairs = parent.shape[:2]
    return kids.reshape((T, 2 * pairs) + parent.shape[2:])


@functools.lru_cache(maxsize=16)
def feature_subset_mask(n_trees: int, width: int, depth: int, n_cols: int,
                        m: int, seed: int) -> np.ndarray:
    """Deterministic per-node feature subset (featureSubsetStrategy):
    ``(T, W, d)`` bool with exactly ``min(m, d)`` True per (tree, node),
    chosen by ranking counter-based hashes of (seed, tree, global node
    id, feature) — no RNG state to thread through replays. A function of
    static numbers alone, so it is taken on the host (numpy; a device
    sort of 3,000 keys a node costs the scorer's program ten seconds of
    compiling) and handed to the scorer as an operand."""
    if m >= n_cols:
        return np.ones((n_trees, width, n_cols), np.bool_)
    t = np.arange(n_trees, dtype=np.uint32)[:, None, None]
    node = (
        np.uint32(width - 1)
        + np.arange(width, dtype=np.uint32)[None, :, None]
    )
    f = np.arange(n_cols, dtype=np.uint32)[None, None, :]
    r = _hash_u32(
        f
        ^ _hash_u32(node * np.uint32(0x85EBCA6B), np)
        ^ _hash_u32(
            t * np.uint32(0xC2B2AE35)
            + np.uint32(np.uint32(seed & 0xFFFFFFFF)),
            np,
        ),
        np,
    )
    order = np.argsort(r, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    return rank < m


@functools.lru_cache(maxsize=64)
def best_splits_fn(
    n_trees: int, depth: int, n_classes: int, subset_m: int, seed: int,
    min_instances: int, ad: str,
):
    """Vectorized split scorer for one frontier: ``(hist (T, W, d, B, S),
    mask (T, W, d)) -> (score, feature, bin, left, right, total)`` —
    ``mask`` the nodes' feature subsets (:func:`feature_subset_mask`) —
    with ``score (T, W)`` the best impurity-improvement over every
    (feature, threshold-bin) candidate in the node's feature subset,
    ``left``/``right``/``total (T, W, S)`` the chosen split's child and
    node statistics (what the grower writes into the value table).

    The scores share one algebraic form: maximizing the Gini /
    variance gain is maximizing ``Σg²(left)/n(left) + Σg²(right)/
    n(right)`` (g = class counts for classification, Σy for regression)
    — the parent term is a per-node constant, reported via ``total``.
    Degenerate candidates (empty side, under ``min_instances``, feature
    outside the node's subset, duplicate-edge empty bins) score -inf."""
    accum = jnp.dtype(ad)

    def score_tree(args):
        """One tree's frontier: hist (W, d, B, S), mask (W, d)."""
        hist, mask = args
        # The statistic axis before the feature axis, as the chip keeps
        # the tensor (`hist_update_group_fn`): each statistic a whole slab.
        hist = jnp.moveaxis(hist, 3, 1)
        W, S, d, B = hist.shape
        cum = jnp.cumsum(hist, axis=3)
        tot = cum[:, :, 0, B - 1]  # (W, S) — identical per feature
        left = cum[:, :, :, : B - 1]  # (W, S, d, B-1)
        right = tot[:, :, None, None] - left
        if n_classes > 0:
            n_l = jnp.sum(left, axis=1)
            n_r = jnp.sum(right, axis=1)
            g_l = jnp.sum(left * left, axis=1)
            g_r = jnp.sum(right * right, axis=1)
        else:
            n_l, n_r = left[:, 0], right[:, 0]
            g_l = left[:, 1] * left[:, 1]
            g_r = right[:, 1] * right[:, 1]
        score = (
            g_l / jnp.maximum(n_l, 1) + g_r / jnp.maximum(n_r, 1)
        )
        # Parent constant subtracted so "score > 0" IS "gain > 0".
        if n_classes > 0:
            g_t = jnp.sum(tot * tot, axis=-1)
            n_t = jnp.sum(tot, axis=-1)
        else:
            g_t = tot[:, 1] * tot[:, 1]
            n_t = tot[:, 0]
        score = score - (g_t / jnp.maximum(n_t, 1))[:, None, None]
        mi = jnp.asarray(float(min_instances), accum)
        valid = (n_l >= mi) & (n_r >= mi) & mask[:, :, None]
        score = jnp.where(valid, score, -jnp.inf)  # (W, d, B-1)
        # The first maximum in (feature, bin) order, in two steps: the
        # first best bin of every feature, then the first best feature.
        bin_of = jnp.argmax(score, axis=-1)  # (W, d)
        best_f = jnp.argmax(jnp.max(score, axis=-1), axis=-1).astype(jnp.int32)
        best_b = jnp.take_along_axis(
            bin_of, best_f[:, None], axis=1)[:, 0].astype(jnp.int32)
        pick = lambda a, lead: jnp.take_along_axis(  # noqa: E731 - local gather
            jnp.take_along_axis(
                a, best_f.reshape((W,) + (1,) * (a.ndim - 1)), axis=lead),
            best_b.reshape((W,) + (1,) * (a.ndim - 1)), axis=lead + 1,
        )
        best_score = pick(score, 1)[:, 0, 0]
        return (best_score, best_f, best_b, pick(left, 2)[:, :, 0, 0],
                pick(right, 2)[:, :, 0, 0], tot)

    def scorer(hist, mask):
        # Tree by tree once the frontier is wide or deep: the cumulative
        # sums, both children's statistics
        # and the scores are each the size of what they are taken from, and
        # a frontier tensor may fill a third of the device (one tree's
        # share of it is the transient, not the whole).
        if hist.size * accum.itemsize <= _SCORE_BLOCK_BYTES:
            return jax.vmap(score_tree)((hist, mask))
        return jax.lax.map(score_tree, (hist, mask))

    return ledgered_jit("histogram.best_splits", scorer)
