"""The comparison that decides `correct` in the elastic-net logistic cell:
every fit of the window against the plain reference (`reference/logreg_en.py`)
at the tolerances the configuration's file states, and the rows every pass
folded against the rows of the cached pass.

Numbers (each the worst over the window's fits, printed beside its limit):

* `rows_not_folded` — over every pass of every fit, |n − rows of the cached
  pass|; limit 0.
* `pass0_grad_rel`, `pass0_hess_rel` — the first pass's statistics at the
  common start, as the L2 cell reads them (`harness/agree_logreg.py`
  `pass0_parts`): the larger of the joint gradient's and the loss's relative
  error, and the bordered Hessian's.
* `first_step_rel` — the FIRST boundary, taken by both from the common
  start: ‖z₁ − z₁_ref‖ ÷ ‖z₁_ref − z₀‖ (z = [w; b], z₀ the start, z₁ the
  iterate the first step leaves; the program's read in the warm-up fit,
  the reference's the exact step from its first pass). There the support
  moves most and the inner solve works hardest, so this reads how far the
  program's step stops short of the subproblem's minimiser — measured
  against the step's own length. One number a run: every fit starts alike.
* `coef_rel` — ‖[w; b] − [w_ref; b_ref]‖ ÷ ‖[w_ref; b_ref]‖, the reference's
  last boundary taken from the iterate the fit's last pass started at
  (teacher forcing: one exact proximal-Newton step, against the program's).
* `support_mismatch` — coordinates zero in one of w, w_ref and nonzero in
  the other, counted where the nonzero one exceeds the configuration's
  `support_margin`; limit 0.
* `loss_rel` — |loss − loss_ref| ÷ loss_ref: the objective with its L1 term
  at the iterate the last pass evaluated (what the last `step` reports),
  against the reference's at the same iterate.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

from perf.harness import agree_logreg

RELATIVE = ("pass0_grad_rel", "pass0_hess_rel", "coef_rel", "loss_rel")
COUNTED = ("rows_not_folded", "support_mismatch")
#: the one number of a run, not of a fit
FIRST = "first_step_rel"


def first_step_rel(first: Dict, first_ref: Dict, start: Dict) -> float:
    """‖z₁ − z₁_ref‖ ÷ ‖z₁_ref − z₀‖ over z = [w; b]; `first`, `first_ref`
    and `start` each hold `w` and `b`."""
    def joint(z):
        return np.concatenate([np.asarray(z["w"], np.float64).reshape(-1),
                               np.asarray(z["b"], np.float64).reshape(-1)[:1]])

    z0, z1, z1_ref = joint(start), joint(first), joint(first_ref)
    return float(np.linalg.norm(z1 - z1_ref) / np.linalg.norm(z1_ref - z0))


def check_first_step(first: Dict, first_ref: Dict, start: Dict, tol: Dict[str, float]):
    """(the number beside its limit, problems) of the first boundary."""
    seen = first_step_rel(first, first_ref, start)
    bad = [] if seen <= tol[FIRST] else [
        f"{FIRST} {seen:.3e} > {tol[FIRST]} (the first step's nonzero coefficients "
        f"{np.count_nonzero(first['w'])}, the reference's {np.count_nonzero(first_ref['w'])})"]
    return [seen, tol[FIRST]], bad


def support_mismatch(w, ref_w, margin: float) -> int:
    """Coordinates zero in one and nonzero in the other, the nonzero one
    above `margin` in magnitude."""
    w = np.asarray(w, np.float64).reshape(-1)
    ref_w = np.asarray(ref_w, np.float64).reshape(-1)
    differ = (w == 0.0) != (ref_w == 0.0)
    return int(np.sum(differ & (np.maximum(np.abs(w), np.abs(ref_w)) > margin)))


def check_fit(fit: Dict, pass0_ref: Dict, last_ref: Dict, tol: Dict[str, float],
              margin: float, cached_rows: int) -> List[str]:
    """Problems with one fit's model (empty = agrees). `fit`: `w`, `b`,
    `loss`, `pass0` (the first pass's raw sums) and `pass_rows`; `last_ref`:
    the reference's `w`, `b` and `loss` of the last boundary from the fit's
    own iterate."""
    w = np.asarray(fit["w"])
    if w.shape != np.asarray(last_ref["w"]).shape:
        return [f"coefficients of shape {w.shape}"]
    if not (np.isfinite(w).all() and np.isfinite(fit["b"]) and np.isfinite(fit["loss"])):
        return ["non-finite values in the model"]
    parts = agree_logreg.pass0_parts(fit["pass0"], pass0_ref)
    seen = {
        "rows_not_folded": float(max(abs(cached_rows - n) for n in fit["pass_rows"])),
        "support_mismatch": float(support_mismatch(w, last_ref["w"], margin)),
        "pass0_grad_rel": max(parts["grad"], parts["loss"]),
        "pass0_hess_rel": parts["hess"],
        "coef_rel": agree_logreg.coef_rel(w, fit["b"], last_ref["w"], last_ref["b"]),
        "loss_rel": abs(float(fit["loss"]) - last_ref["loss"]) / abs(last_ref["loss"]),
    }
    fit["_agreement"] = seen
    fit["_pass0_parts"] = parts
    bad = []
    if seen["rows_not_folded"]:
        short = [n for n in fit["pass_rows"] if n != cached_rows]
        bad.append(f"{len(short)} of {len(fit['pass_rows'])} passes folded "
                   f"{short[0]:.0f} rows, the cached pass holds {cached_rows}")
    if seen["support_mismatch"]:
        bad.append(f"support_mismatch {seen['support_mismatch']:.0f} coordinates "
                   f"(w nonzero {np.count_nonzero(w)}, reference "
                   f"{np.count_nonzero(last_ref['w'])})")
    for name in RELATIVE:
        if seen[name] > tol[name]:
            bad.append(f"{name} {seen[name]:.3e} > {tol[name]}")
    return bad


def check_fits(fits: List[Dict], pass0_ref: Dict, last_ref_of: Callable[[Dict], Dict],
               tol: Dict[str, float], margin: float, cached_rows: int, say) -> List[str]:
    problems = [f"fit {f['fit']}: {b}" for f in fits
                for b in check_fit(f["model"], pass0_ref, last_ref_of(f["model"]), tol,
                                   margin, cached_rows)]
    worst = compared(fits, tol, cached_rows)
    if len(worst) > 2:
        say(f"agreement over {len(fits)} fits: " + ", ".join(
            f"{name} {value:.3e}" for name, (value, _) in worst.items()))
        parts = [f["model"]["_pass0_parts"] for f in fits if "_pass0_parts" in f["model"]]
        say("  the first pass by part: " + ", ".join(
            f"{name} {max(p[name] for p in parts):.3e}" for name in ("grad", "loss", "hess")))
    return problems


def compared(fits: List[Dict], tol: Dict[str, float], cached_rows: int
             ) -> Dict[str, List[float]]:
    """Each number compared, the worst over the fits, beside its limit."""
    seen = [f["model"]["_agreement"] for f in fits if "_agreement" in f["model"]]
    if not seen:
        return {"rows_not_folded": [float(cached_rows), 0.0]}
    out = {name: [max(a[name] for a in seen), 0.0] for name in COUNTED}
    for name in RELATIVE:
        out[name] = [max(a[name] for a in seen), tol[name]]
    return out
