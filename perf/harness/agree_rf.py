"""The comparison that decides `correct` in the forest cell: the program's
fit against the plain reference (`reference/rf.py`), TEACHER-FORCED level by
level, at the tolerances the configuration's file states.

What is compared in depth is ONE fit — the warm-up's, the same start tables
and the same cached rows as every fit of the window, whose frontier
histograms the generator fetched before the window opened: all trees at
depth 0 and a seeded subset of trees at every depth. Every fit of the
window then has to reproduce that fit's tables at every level bit for bit
(`fits_differ`; the program is deterministic, the rows are cached), so each
number below holds for every fit of the window or the run is not correct.

Numbers (printed beside their limits):

* `count_mismatch` — cells of the count channel, cumulated over the bins,
  that differ from the reference's count of the node's rows at or under
  that edge (whole numbers: exact, limit 0), over every compared level; the
  node tables' own counts (root, children of every split) are in it.
* `hist_rel` — the Σy and Σy² channels the same way: the larger, over the
  compared levels and the two channels, of ‖cum − ref‖_F ÷ ‖ref‖_F.
* `split_gain_rel` — over every OPEN node of the compared trees: how far the
  gain of the program's split, evaluated on the REFERENCE's statistics,
  lies under the reference's best gain, (best − gain) ÷ best; 1 where one
  of the two splits and the other does not, or the program's candidate is
  none the reference admits (outside the node's subset, an empty side).
* `split_equal_share` — the share of those nodes whose decision (feature,
  bin, or "no split") is the reference's own; a floor, not a ceiling.
* `leaf_rel` — Σy and Σy² of every node the tables hold against the
  reference's (the node's totals; a split's children from the reference's
  statistics at the program's split): ‖Δ‖ ÷ ‖ref‖, the larger channel.
* `pred_rel` — ‖pred − ref‖ ÷ ‖ref‖ of the finished model's predictions on
  one cached batch against the reference's walk of the same tables.
* `rows_miscounted` — the guarantee, on every tree and level of every fit:
  the counts of the nodes a level's rows stand on (frontier, or settled at
  a shallower leaf) against the tree's whole bag, Σ of its weights; limit 0.
* `fits_differ` — fits of the window whose tables differ at any level from
  the fit compared in depth; limit 0.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

OPEN, LEAF = -2, -1
CEILINGS = ("hist_rel", "split_gain_rel", "leaf_rel", "pred_rel")
TABLES = ("feature", "threshold", "value")


def _rel(got: np.ndarray, ref: np.ndarray) -> float:
    scale = float(np.linalg.norm(ref))
    return float(np.linalg.norm(got - ref)) / scale if scale else float(np.linalg.norm(got))


def histogram_agreement(hist: np.ndarray, ref_left: np.ndarray) -> Dict[str, float]:
    """One level: the program's histogram (T, W, d, B, S) against the
    reference's LEFT sums (T, W, S, d, B). → count cells that differ, and
    the relative distance of each label channel."""
    cum = np.moveaxis(np.cumsum(np.asarray(hist, np.float64), axis=3), 4, 2)
    return {
        "count_mismatch": float(np.count_nonzero(cum[:, :, 0] != ref_left[:, :, 0])),
        "sum_y": _rel(cum[:, :, 1], ref_left[:, :, 1]),
        "sum_y2": _rel(cum[:, :, 2], ref_left[:, :, 2]),
    }


def split_agreement(before: Dict, after: Dict, level: int, trees, scored: Dict,
                    ref_left: np.ndarray) -> Dict[str, np.ndarray]:
    """One level of the compared trees: the program's decisions (the tables
    `after` the pass) against the reference's (`scored`, from `ref_left`).
    → per OPEN node `gain_rel` and `equal`, and the node statistics the
    tables should hold: (`value`, `ref`) rows of (count, Σy, Σy²)."""
    base, width = (1 << level) - 1, 1 << level
    was_open = np.asarray(before["feature"])[trees, base: base + width] == OPEN
    feat = np.asarray(after["feature"])[trees, base: base + width]
    thr = np.asarray(after["threshold"])[trees, base: base + width]
    value = np.asarray(after["value"], np.float64)[trees]
    gain_rel, equal, held, ref = [], [], [], []
    for t in range(len(trees)):
        for w in range(width):
            if not was_open[t, w]:
                continue
            held.append(value[t, base + w])
            ref.append(ref_left[t, w, :, 0, -1])  # the node's totals
            splits, ref_splits = feat[t, w] >= 0, scored["feature"][t, w] >= 0
            equal.append(bool(splits == ref_splits and (not splits or (
                feat[t, w] == scored["feature"][t, w] and thr[t, w] == scored["bin"][t, w]))))
            if splits != ref_splits:
                gain_rel.append(1.0)
            elif splits:
                got, best = scored["gain"][t, w, feat[t, w], thr[t, w]], scored["best_gain"][t, w]
                gain_rel.append(1.0 if not np.isfinite(got) else float((best - got) / best))
                left = ref_left[t, w, :, feat[t, w], thr[t, w]]
                for side, stats in ((1, left), (2, ref_left[t, w, :, 0, -1] - left)):
                    held.append(value[t, 2 * (base + w) + side])
                    ref.append(stats)
    return {"gain_rel": np.asarray(gain_rel), "equal": np.asarray(equal, bool),
            "value": np.asarray(held).reshape(-1, 3), "ref": np.asarray(ref).reshape(-1, 3)}


def rows_miscounted(levels: List[Dict], bag_rows: np.ndarray) -> float:
    """The worst, over trees and levels, of |rows the level accounts for −
    the tree's bag|: after the pass of depth l every node of depth l that
    exists holds its count, and every leaf above it the rows settled there."""
    worst = 0.0
    for level in range(len(levels) - 1):
        after = levels[level + 1]
        feature, count = np.asarray(after["feature"]), np.asarray(after["value"])[..., 0]
        exists = np.zeros(feature.shape, bool)
        exists[:, 0] = True
        total = np.zeros(feature.shape[0])
        for depth in range(level + 1):
            base, width = (1 << depth) - 1, 1 << depth
            here = slice(base, base + width)
            if depth == level:
                total += np.where(exists[:, here], count[:, here], 0.0).sum(1)
                break
            total += np.where(exists[:, here] & (feature[:, here] == LEAF),
                              count[:, here], 0.0).sum(1)
            split = exists[:, here] & (feature[:, here] >= 0)
            exists[:, 2 * base + 1: 2 * base + 1 + 2 * width: 2] = split
            exists[:, 2 * base + 2: 2 * base + 2 + 2 * width: 2] = split
        worst = max(worst, float(np.abs(total - bag_rows).max()))
    return worst


def same_tables(a: List[Dict], b: List[Dict]) -> bool:
    return len(a) == len(b) and all(
        np.array_equal(np.asarray(x[k]), np.asarray(y[k])) for x, y in zip(a, b) for k in TABLES)


def check(captured: Dict, fits: List[Dict], ref: Dict, tol: Dict[str, float], say
          ) -> Dict[str, List[float]]:
    """→ `compared`: each number beside its limit. `captured`: the fit
    compared in depth — `levels` (its tables before each pass and after the
    last), `hist_root` (every tree's depth-0 histogram), `hist` (by level,
    the compared trees'), `pred` (its model's predictions). `fits`: the
    window's, each with `levels`. `ref`: `trees`, `root` and `left` (the
    reference's LEFT sums: every tree at depth 0; by level, the compared
    trees), `scored` (by level, its split decisions), `pred`, `bag_rows`."""
    levels, trees = captured["levels"], list(ref["trees"])
    per_level = [histogram_agreement(captured["hist_root"], ref["root"])]
    per_level += [histogram_agreement(h, left) for h, left in zip(captured["hist"], ref["left"])]
    splits = [split_agreement(levels[l], levels[l + 1], l, trees, ref["scored"][l], ref["left"][l])
              for l in range(len(ref["left"]))]
    gain_rel = np.concatenate([s["gain_rel"] for s in splits])
    equal = np.concatenate([s["equal"] for s in splits])
    value = np.concatenate([s["value"] for s in splits])
    node_ref = np.concatenate([s["ref"] for s in splits])
    seen = {
        "count_mismatch": sum(a["count_mismatch"] for a in per_level)
        + float(np.count_nonzero(value[:, 0] != node_ref[:, 0])),
        "hist_rel": max(max(a["sum_y"], a["sum_y2"]) for a in per_level),
        "split_gain_rel": float(gain_rel.max()) if gain_rel.size else 1.0,
        "split_equal_share": float(equal.mean()) if equal.size else 0.0,
        "leaf_rel": max(_rel(value[:, 1], node_ref[:, 1]), _rel(value[:, 2], node_ref[:, 2])),
        "pred_rel": _rel(np.asarray(captured["pred"], np.float64), ref["pred"]),
        "rows_miscounted": max([rows_miscounted(f["levels"], ref["bag_rows"]) for f in fits]
                               + [rows_miscounted(levels, ref["bag_rows"])]),
        "fits_differ": float(sum(not same_tables(f["levels"], levels) for f in fits)),
    }
    say(f"agreement over {len(fits)} fits, in depth on trees {trees} ({len(equal)} open nodes, "
        f"{int(equal.sum())} decided as the reference decides): " + ", ".join(
            f"{name} {value:.3e}" for name, value in seen.items()))
    say("  by level (root of every tree, then the compared trees' levels): " + "; ".join(
        f"count {a['count_mismatch']:.0f} Σy {a['sum_y']:.2e} Σy² {a['sum_y2']:.2e}"
        for a in per_level))
    out = {name: [seen[name], 0.0] for name in ("count_mismatch", "rows_miscounted", "fits_differ")}
    out.update({name: [seen[name], tol[name]] for name in CEILINGS})
    out["split_equal_share"] = [seen["split_equal_share"], tol["split_equal_share"]]
    return out


def problems(compared: Dict[str, List[float]]) -> List[str]:
    bad = [f"{name} {value:.6g} > {limit:g}" for name, (value, limit) in compared.items()
           if name != "split_equal_share" and not value <= limit]
    share, floor = compared["split_equal_share"]
    if not share >= floor:
        bad.append(f"split_equal_share {share:.6g} < {floor:g}")
    return bad
