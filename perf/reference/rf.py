"""Reference histogram forest (regression): the semantics of one level of a
level-synchronous forest, written out plainly and TEACHER-FORCED — at each
depth the reference takes the node tables as the program left them, takes
that level's statistics from the same rows itself, and scores them; one
flipped near-tie therefore costs one node's comparison, not every deeper
level's. Imports nothing from `spark_rapids_ml_tpu`.

What a level is, stated from docs/protocol.md "The `rf` job algo" and
re-derived here:

* **Row identity and bag weights.** Row `i` of partition `p` has the uint32
  key `(p * 2654435761 + i) mod 2^32`; tree `t`'s weight of it is
  Poisson(1) by inversion of a counter-based hash: `u = h(key ^ h(t *
  0x9E3779B1 + seed)) * 2^-32` in float32, the weight the number of
  Poisson(1) CDF values (at 0..5, float32) that `u` exceeds; `h` is the
  splitmix-style avalanche `hash_u32`. numpy, uint32.
* **Routing.** A row walks a tree from the root: at an internal node with
  feature `f` and threshold bin `b` it goes right iff `x[f] > edge[f, b]`
  (edges in float32, as the program bins), else left. It counts at level
  `l` iff the node it stands on at depth `l` is OPEN there.
* **Statistics.** For every OPEN node, feature and edge: the bag-weighted
  (count, Σy, Σy²) of the node's rows with `x[f] <= edge[f, b]` — the LEFT
  side of the candidate split (feature f, bin b), taken straight from the
  raw values against the edges (no bin ids, no histogram: `cum[..., b, :]`
  is what a histogram's cumulative sum over bins 0..b must equal) — and
  the node's totals as a last "edge" at +inf. Each batch's sums in float32
  at `highest` matmul precision on the device (the indicator is 0/1 and a
  count stays under 2^24: exact), their sum over the batches in float64 on
  the host.
* **Feature subset.** Node `w` (heap id `2^l - 1 + w`) of tree `t` may split
  on the `m` features that rank lowest by `h(f ^ h(node * 0x85EBCA6B) ^
  h(t * 0xC2B2AE35 + seed))` (stable order).
* **Split.** An explicit loop over trees and nodes: for each candidate with
  at least `min_instances` weighted rows on both sides, the variance gain
  `Σy_l² / n_l + Σy_r² / n_r − Σy² / n`; the first maximum in (feature,
  bin) order wins; a node splits iff its best gain exceeds 1e-12.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

OPEN, LEAF = -2, -1
POISSON1_CDF = (0.36787944117144233, 0.7357588823428847, 0.9196986029286058,
                0.9810118431238462, 0.9963401531726563, 0.9994058151824183)
#: rows of a batch the device takes at a time (the indicator is rows x d x B floats)
CHUNK_ROWS = 1024


def hash_u32(h: np.ndarray) -> np.ndarray:
    h = np.asarray(h, np.uint32)
    with np.errstate(over="ignore"):
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x7FEB352D)
        h = h ^ (h >> np.uint32(15))
        h = h * np.uint32(0x846CA68B)
        return h ^ (h >> np.uint32(16))


def row_keys(partition: int, offset: int, n: int) -> np.ndarray:
    base = np.uint32((int(partition) * 2654435761 + int(offset)) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        return (base + np.arange(n, dtype=np.uint32)).astype(np.uint32)


def bag_weights(keys: np.ndarray, trees: Sequence[int], seed: int) -> np.ndarray:
    """(len(trees), n) float32 Poisson(1) weights of the rows `keys`."""
    with np.errstate(over="ignore"):
        tweak = (np.asarray(trees, np.uint32)[:, None] * np.uint32(0x9E3779B1)
                 + np.uint32(seed & 0xFFFFFFFF))
    u = hash_u32(np.asarray(keys, np.uint32)[None, :] ^ hash_u32(tweak))
    u = u.astype(np.float32) * np.float32(1.0 / 4294967296.0)
    cdf = np.asarray(POISSON1_CDF, np.float32)
    return (u[:, :, None] > cdf[None, None, :]).sum(-1).astype(np.float32)


def feature_subset(trees: Sequence[int], depth: int, d: int, m: int, seed: int) -> np.ndarray:
    """(len(trees), 2^depth, d) bool: the features each frontier node may split on."""
    width = 1 << depth
    if m >= d:
        return np.ones((len(trees), width, d), bool)
    with np.errstate(over="ignore"):
        t = np.asarray(trees, np.uint32)[:, None, None]
        node = np.uint32(width - 1) + np.arange(width, dtype=np.uint32)[None, :, None]
        f = np.arange(d, dtype=np.uint32)[None, None, :]
        r = hash_u32(f ^ hash_u32(node * np.uint32(0x85EBCA6B))
                     ^ hash_u32(t * np.uint32(0xC2B2AE35) + np.uint32(seed & 0xFFFFFFFF)))
    rank = np.argsort(np.argsort(r, axis=-1, kind="stable"), axis=-1, kind="stable")
    return rank < m


def _one_level_down(x, edges, features, thresholds, node):
    """Every row of `x` (float32) one level down from `node` (trees, rows):
    right iff its value of the node's feature exceeds the node's edge; a
    row on a leaf stays. → (nodes, whether the node it left was internal)."""
    import jax.numpy as jnp

    f = jnp.take_along_axis(features, node, axis=1)
    b = jnp.take_along_axis(thresholds, node, axis=1)
    fc = jnp.clip(f, 0, x.shape[1] - 1)
    right = x[jnp.arange(x.shape[0])[None, :], fc] > edges[
        fc, jnp.clip(b, 0, edges.shape[1] - 1)]
    return jnp.where(f >= 0, 2 * node + 1 + right.astype(jnp.int32), node), f >= 0


@functools.lru_cache(maxsize=None)
def _batch_stats(n_levels: int, chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(x, y, weights, edges, features, thresholds, open_tables):
        """x (n, d), y (n,), weights (T, n), edges (d, B-1) float32;
        features / thresholds (T, nodes) the finished routing tables,
        open_tables (levels, T, nodes) bool: OPEN at that level's pass.
        → per level l a (T, 2^l, 3, d, B) float32 of LEFT sums by edge (the
        last "edge" the node's total)."""
        n, d = x.shape
        trees = weights.shape[0]
        edges_inf = jnp.concatenate(
            [edges, jnp.full((d, 1), jnp.inf, jnp.float32)], axis=1)

        def one_chunk(i, acc):
            xs = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, 0).astype(jnp.float32)
            ys = jax.lax.dynamic_slice_in_dim(y, i * chunk, chunk, 0).astype(jnp.float32)
            ws = jax.lax.dynamic_slice_in_dim(weights, i * chunk, chunk, 1)
            left = (xs[:, :, None] <= edges_inf[None]).astype(jnp.float32)  # (c, d, B)
            stat = jnp.stack([jnp.ones_like(ys), ys, ys * ys], 0)  # (3, c)
            node = jnp.zeros((trees, chunk), jnp.int32)
            alive = jnp.ones((trees, chunk), bool)
            out = []
            for level in range(n_levels):
                is_open = jnp.take_along_axis(open_tables[level], node, axis=1)
                w = jnp.where(alive & is_open, ws, 0.0)
                at = jax.nn.one_hot(node - ((1 << level) - 1), 1 << level,
                                    dtype=jnp.float32)  # (T, c, W)
                v = at.transpose(0, 2, 1)[:, :, None, :] * (w[:, None, :] * stat[None])[:, None]
                with jax.default_matmul_precision("highest"):
                    out.append(acc[level] + jnp.einsum("twsn,ndb->twsdb", v, left))
                node, internal = _one_level_down(xs, edges, features, thresholds, node)
                alive = alive & internal
            return tuple(out)

        zero = tuple(jnp.zeros((trees, 1 << level, 3, d, edges.shape[1] + 1), jnp.float32)
                     for level in range(n_levels))
        return jax.lax.fori_loop(0, n // chunk, one_chunk, zero)

    return stats


def level_statistics(batches, keys: Sequence[np.ndarray], edges: np.ndarray,
                     levels: List[Dict[str, np.ndarray]], trees: Sequence[int], seed: int,
                     n_levels: int, rounded: Optional[Callable] = None,
                     chunk: int = CHUNK_ROWS) -> List[np.ndarray]:
    """The LEFT sums of `n_levels` levels of the trees `trees`, float64:
    a list by level of (len(trees), 2^l, 3, d, B) arrays, `[..., b]` over a
    node's rows with `x[f] <= edge[f, b]` and `[..., B-1]` its totals.
    `batches`: a sequence of (rows (n, d), labels (n,)) pairs (device or
    host), `keys` their rows' uint32 identities. `levels[l]`: the tables
    (`feature`, `threshold`) as they stood BEFORE the pass of depth `l`;
    `levels[n_levels]` the tables after the last one. `rounded`: applied
    to each batch's rows on the device (the control's precision)."""
    import jax
    import jax.numpy as jnp

    trees = list(trees)
    final = levels[n_levels]
    features = jnp.asarray(np.asarray(final["feature"])[trees], jnp.int32)
    thresholds = jnp.asarray(np.asarray(final["threshold"])[trees], jnp.int32)
    open_tables = jnp.asarray(np.stack(
        [np.asarray(levels[l]["feature"])[trees] == OPEN for l in range(n_levels)]))
    edges32 = jnp.asarray(np.asarray(edges, np.float32))
    total: Optional[List[np.ndarray]] = None
    for (x, y), key in zip(batches, keys):
        n = int(x.shape[0])
        c = min(chunk, n)
        if n % c:
            raise ValueError(f"a batch of {n} rows is not whole chunks of {c}")
        if rounded is not None:
            x = rounded(x)
        got = _batch_stats(n_levels, c)(
            x, y, jnp.asarray(bag_weights(key, trees, seed)), edges32, features,
            thresholds, open_tables)
        got = [np.asarray(a, np.float64) for a in jax.device_get(got)]
        total = got if total is None else [a + b for a, b in zip(total, got)]
    return total


def gains(left: np.ndarray, min_instances: int) -> np.ndarray:
    """left (3, d, B) of one node → (d, B-1) variance gains, -inf where a
    side holds fewer than `min_instances` weighted rows."""
    tot = left[:, :1, -1:]
    l, r = left[:, :, :-1], tot - left[:, :, :-1]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (l[1] ** 2 / np.maximum(l[0], 1) + r[1] ** 2 / np.maximum(r[0], 1)
                - tot[1] ** 2 / np.maximum(tot[0], 1))
    return np.where((l[0] >= min_instances) & (r[0] >= min_instances), gain, -np.inf)


def best_splits(stats: np.ndarray, is_open: np.ndarray, subset: np.ndarray,
                min_instances: int = 1) -> Dict[str, np.ndarray]:
    """One level's split decisions, node by node. stats (T, W, 3, d, B)
    LEFT sums, is_open (T, W), subset (T, W, d). → `gain` (T, W, d, B-1)
    with -inf outside the subset or under `min_instances`, `best_gain`,
    `feature`, `bin` (T, W) — feature LEAF where the node does not split
    (not open, or no candidate gains over 1e-12)."""
    T, W = is_open.shape
    gain = np.full(stats.shape[:2] + (stats.shape[3], stats.shape[4] - 1), -np.inf)
    best = np.full((T, W), -np.inf)
    feature = np.full((T, W), LEAF, np.int64)
    at_bin = np.zeros((T, W), np.int64)
    for t in range(T):
        for w in range(W):
            if not is_open[t, w]:
                continue
            g = np.where(subset[t, w][:, None], gains(stats[t, w], min_instances), -np.inf)
            gain[t, w] = g
            flat = int(np.argmax(g))  # the first maximum in (feature, bin) order
            f, b = divmod(flat, g.shape[1])
            best[t, w] = g[f, b]
            if g[f, b] > 1e-12:
                feature[t, w], at_bin[t, w] = f, b
    return {"gain": gain, "best_gain": best, "feature": feature, "bin": at_bin}


@functools.lru_cache(maxsize=None)
def _descend(depth: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def descend(x, edges, features, thresholds, values):
        x = x.astype(jnp.float32)
        node = jnp.zeros((features.shape[0], x.shape[0]), jnp.int32)
        for _ in range(depth):
            node, _ = _one_level_down(x, edges, features, thresholds, node)
        leaf = jnp.take_along_axis(values, node[:, :, None], axis=1)  # (T, n, 3)
        return jnp.mean(leaf[..., 1] / jnp.maximum(leaf[..., 0], 1.0), axis=0)

    return descend


def predict(x, edges: np.ndarray, tables: Dict[str, np.ndarray], depth: int,
            rounded: Optional[Callable] = None) -> np.ndarray:
    """The finished forest's prediction for the rows `x`: every tree walked
    from its root by raw thresholds to a leaf, the mean over the trees of
    the leaves' Σy / count (float32 on the device)."""
    import jax.numpy as jnp

    if rounded is not None:
        x = rounded(x)
    return np.asarray(_descend(depth)(
        x, jnp.asarray(np.asarray(edges, np.float32)),
        jnp.asarray(np.asarray(tables["feature"]), jnp.int32),
        jnp.asarray(np.asarray(tables["threshold"]), jnp.int32),
        jnp.asarray(np.asarray(tables["value"]), jnp.float32)), np.float64)
