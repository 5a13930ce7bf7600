"""The comparison that decides `correct` in the KMeans cell: every fit of
the window against the plain reference (`reference/kmeans.py`) at the
tolerances the configuration's file states, and the rows every pass folded
against the rows of the cached pass.

Numbers (each the worst over the window's fits, printed beside its limit):

* `rows_not_folded` — over every pass of every fit, |Σ counts − rows of the
  cached pass|; whole numbers under 2^24, exact in float32; limit 0.
* `pass0_stats_rel` — the first pass's statistics at the common start, where
  no iteration has amplified a flipped boundary row: the largest of
  ‖Δsums‖_F ÷ ‖sums‖_F, Σ|Δcounts| ÷ rows and |Δcost| ÷ cost.
* `centers_rel` — ‖C − C_ref‖_F ÷ ‖C_ref − mean row of C_ref‖_F after the fit.
* `cost_rel` — |cost − cost_ref| ÷ cost_ref, the training cost at the final
  centres.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def pass0_parts(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The three parts of `pass0_stats_rel`, which is their largest."""
    sums = np.asarray(got["sums"], np.float64)
    counts = np.asarray(got["counts"], np.float64)
    return {
        "sums": float(np.linalg.norm(sums - ref["sums"]) / np.linalg.norm(ref["sums"])),
        "counts": float(np.abs(counts - ref["counts"]).sum() / ref["counts"].sum()),
        "cost": float(abs(float(got["cost"]) - ref["cost"]) / ref["cost"]),
    }


def centers_rel(centers: np.ndarray, ref_centers: np.ndarray) -> float:
    ref = np.asarray(ref_centers, np.float64)
    spread = np.linalg.norm(ref - ref.mean(axis=0))
    return float(np.linalg.norm(np.asarray(centers, np.float64) - ref) / spread)


def check_fit(fit: Dict, ref: Dict, tol: Dict[str, float], cached_rows: int) -> List[str]:
    """Problems with one fit's model (empty = agrees). `fit`: `centers`,
    `cost`, `pass0` and `pass_counts` (Σ counts of each of its passes)."""
    centers = np.asarray(fit["centers"])
    if centers.shape != np.asarray(ref["centers"]).shape:
        return [f"centres of shape {centers.shape}"]
    if not (np.isfinite(centers).all() and np.isfinite(fit["cost"])):
        return ["non-finite values in the model"]
    parts = pass0_parts(fit["pass0"], ref["pass0"])
    seen = {
        "rows_not_folded": float(max(abs(cached_rows - n) for n in fit["pass_counts"])),
        "pass0_stats_rel": max(parts.values()),
        "centers_rel": centers_rel(centers, ref["centers"]),
        "cost_rel": abs(float(fit["cost"]) - ref["cost"]) / ref["cost"],
    }
    fit["_agreement"] = seen
    fit["_pass0_parts"] = parts
    bad = []
    if seen["rows_not_folded"]:
        short = [n for n in fit["pass_counts"] if n != cached_rows]
        bad.append(f"{len(short)} of {len(fit['pass_counts'])} passes folded "
                   f"{short[0]:.0f} rows, the cached pass holds {cached_rows}")
    for name in ("pass0_stats_rel", "centers_rel", "cost_rel"):
        if seen[name] > tol[name]:
            bad.append(f"{name} {seen[name]:.3e} > {tol[name]}")
    return bad


def check_fits(fits: List[Dict], ref: Dict, tol: Dict[str, float], cached_rows: int,
               say) -> List[str]:
    problems = [f"fit {f['fit']}: {b}" for f in fits
                for b in check_fit(f["model"], ref, tol, cached_rows)]
    worst = compared(fits, tol, cached_rows)
    if len(worst) > 1:
        say(f"agreement over {len(fits)} fits: " + ", ".join(
            f"{name} {value:.3e}" for name, (value, _) in worst.items()))
        parts = [f["model"]["_pass0_parts"] for f in fits if "_pass0_parts" in f["model"]]
        say("  pass0_stats_rel by part: " + ", ".join(
            f"{name} {max(p[name] for p in parts):.3e}" for name in ("sums", "counts", "cost")))
    return problems


def compared(fits: List[Dict], tol: Dict[str, float], cached_rows: int
             ) -> Dict[str, List[float]]:
    """Each number compared, the worst over the fits, beside its limit."""
    seen = [f["model"]["_agreement"] for f in fits if "_agreement" in f["model"]]
    if not seen:
        return {"rows_not_folded": [float(cached_rows), 0.0]}
    out = {"rows_not_folded": [max(a["rows_not_folded"] for a in seen), 0.0]}
    for name in ("pass0_stats_rel", "centers_rel", "cost_rel"):
        out[name] = [max(a[name] for a in seen), tol[name]]
    return out
