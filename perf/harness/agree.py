"""The comparison that decides `correct`: the system's models against the
plain reference, within the tolerances the configuration file states.
`component_cosines` is a copy of chip_smoke's."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def component_cosines(a: np.ndarray, b: np.ndarray) -> Tuple[float, float]:
    """(min per-component |cos|, min principal-angle cosine of the spans)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    per = np.abs(np.sum(a * b, axis=0)) / (
        np.linalg.norm(a, axis=0) * np.linalg.norm(b, axis=0))
    qa, _ = np.linalg.qr(a)
    qb, _ = np.linalg.qr(b)
    principal = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(per.min()), float(principal.min())


def check_pca_fit(fit: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                  tol: Dict[str, float], d: int, k: int) -> List[str]:
    """Problems with one fitted model (empty = agrees)."""
    bad: List[str] = []
    pc, ev, mean = fit["pc"], fit["explained_variance"], fit["mean"]
    if pc.shape != (d, k) or ev.shape != (k,) or mean.shape != (d,):
        return [f"shapes pc {pc.shape} ev {ev.shape} mean {mean.shape}"]
    if not all(np.isfinite(a).all() for a in (pc, ev, mean)):
        return ["non-finite values in the model"]
    per, principal = component_cosines(pc, ref["pc"])
    ev_rel = float(np.max(np.abs(np.asarray(ev) / ref["explained_variance"] - 1.0)))
    mean_err = float(np.max(np.abs(mean - ref["mean"])))
    if min(per, principal) < tol["min_cos"]:
        bad.append(f"components off the reference: min |cos| {per}, "
                   f"principal {principal} < {tol['min_cos']}")
    if ev_rel > tol["explained_variance_rel"]:
        bad.append(f"explained_variance off by {ev_rel} > "
                   f"{tol['explained_variance_rel']}")
    if mean_err > tol["mean_abs"]:
        bad.append(f"column means off by {mean_err} > {tol['mean_abs']}")
    fit["_agreement"] = {"min_cos": per, "min_principal_cos": principal,
                         "ev_rel": ev_rel, "mean_abs": mean_err}
    return bad


def check_pca_fits(fits: List[Dict], refs: Dict[int, Dict[str, np.ndarray]],
                   tol: Dict[str, float], d: int, k: int, say) -> List[str]:
    """Every fit's model (`fit["model"]`) against the reference of the part
    of the data it scanned (`refs[fit["part"]]`); says the worst agreement."""
    problems: List[str] = []
    for fit in fits:
        bad = check_pca_fit(fit["model"], refs[fit["part"]], tol, d, k)
        problems += [f"fit {fit['fit']}: {b}" for b in bad]
    worst = [f["model"]["_agreement"] for f in fits if "_agreement" in f["model"]]
    if worst:
        say(f"agreement over {len(worst)} fits: min |cos| "
            f"{min(w['min_cos'] for w in worst):.9f}, explained variance rel. "
            f"{max(w['ev_rel'] for w in worst):.2e}, mean "
            f"{max(w['mean_abs'] for w in worst):.2e}")
    return problems


def compared_pca(fits: List[Dict], tol: Dict[str, float], rows_per_fit: int
                 ) -> Dict[str, List[float]]:
    """Each number `check_pca_fits` compared, the worst over the fits, beside
    its limit: name → [number, limit]. `min_cos` must not fall under its
    limit, the others must not pass theirs. For the result line."""
    seen = [f["model"]["_agreement"] for f in fits if "_agreement" in f["model"]]
    out = {"rows_not_folded": [float(max((abs(rows_per_fit - f["model"]["rows"])
                                          for f in fits), default=rows_per_fit)), 0.0]}
    if seen:
        out["min_cos"] = [min(min(a["min_cos"], a["min_principal_cos"]) for a in seen),
                          tol["min_cos"]]
        out["explained_variance_rel"] = [max(a["ev_rel"] for a in seen),
                                         tol["explained_variance_rel"]]
        out["mean_abs"] = [max(a["mean_abs"] for a in seen), tol["mean_abs"]]
    return out


def summarize(problems: List[str], say) -> bool:
    for p in problems[:20]:
        say(f"  DISAGREES: {p}")
    return not problems
