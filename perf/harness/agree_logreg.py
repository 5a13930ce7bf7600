"""The comparison that decides `correct` in the logistic cell: every fit of
the window against the plain reference (`reference/logreg.py`) at the
tolerances the configuration's file states, and the rows every pass folded
against the rows of the cached pass.

Numbers (each the worst over the window's fits, printed beside its limit):

* `rows_not_folded` — over every pass of every fit, |n − rows of the cached
  pass|, `n` the pass's own row count (whole numbers under 2^24, exact in
  float32); limit 0.
* `pass0_grad_rel` — the first pass's statistics at the common start, where
  Newton's fixed point hides nothing yet: the larger of ‖Δg‖ ÷ ‖g‖ over the
  joint gradient [Xᵀ(p − y); Σ(p − y)] (the intercept's entry in the vector,
  so that a small Σ(p − y) cannot blow a ratio of its own up) and
  |Δloss| ÷ loss. Float32-`highest` sums in the program.
* `pass0_hess_rel` — ‖ΔH‖_F ÷ ‖H‖_F over the bordered Hessian
  [[XᵀDX, XᵀD1], [·, ΣD]] of the same pass: a single bfloat16 product with
  float32 accumulation in the program, by design.
* `coef_rel` — ‖[w; b] − [w_ref; b_ref]‖ ÷ ‖[w_ref; b_ref]‖ after the fit.
* `loss_rel` — |loss − loss_ref| ÷ loss_ref, the objective the last pass
  evaluated (what the last `step` reports).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

RELATIVE = ("pass0_grad_rel", "pass0_hess_rel", "coef_rel", "loss_rel")


def _bordered(stats: Dict[str, np.ndarray]) -> np.ndarray:
    hww = np.asarray(stats["hww"], np.float64)
    hwb = np.asarray(stats["hwb"], np.float64)
    d = hwb.shape[0]
    out = np.empty((d + 1, d + 1), np.float64)
    out[:d, :d] = hww
    out[:d, d] = out[d, :d] = hwb
    out[d, d] = float(stats["hbb"])
    return out


def _joint_gradient(stats: Dict[str, np.ndarray]) -> np.ndarray:
    return np.concatenate([np.asarray(stats["gw"], np.float64).reshape(-1),
                           [float(stats["gb"])]])


def pass0_parts(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The parts of `pass0_grad_rel` (the larger of `grad`, `loss`) and
    `pass0_hess_rel` (`hess`)."""
    g, g_ref = _joint_gradient(got), _joint_gradient(ref)
    h, h_ref = _bordered(got), _bordered(ref)
    return {
        "grad": float(np.linalg.norm(g - g_ref) / np.linalg.norm(g_ref)),
        "loss": float(abs(float(got["loss"]) - float(ref["loss"])) / abs(float(ref["loss"]))),
        "hess": float(np.linalg.norm(h - h_ref) / np.linalg.norm(h_ref)),
    }


def coef_rel(w, b, ref_w, ref_b) -> float:
    got = np.concatenate([np.asarray(w, np.float64).reshape(-1), [float(b)]])
    ref = np.concatenate([np.asarray(ref_w, np.float64).reshape(-1), [float(ref_b)]])
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_fit(fit: Dict, ref: Dict, tol: Dict[str, float], cached_rows: int) -> List[str]:
    """Problems with one fit's model (empty = agrees). `fit`: `w`, `b`,
    `loss`, `pass0` (the first pass's raw sums) and `pass_rows` (the row
    count `n` of each of its passes)."""
    w = np.asarray(fit["w"])
    if w.shape != np.asarray(ref["w"]).shape:
        return [f"coefficients of shape {w.shape}"]
    if not (np.isfinite(w).all() and np.isfinite(fit["b"]) and np.isfinite(fit["loss"])):
        return ["non-finite values in the model"]
    parts = pass0_parts(fit["pass0"], ref["pass0"])
    seen = {
        "rows_not_folded": float(max(abs(cached_rows - n) for n in fit["pass_rows"])),
        "pass0_grad_rel": max(parts["grad"], parts["loss"]),
        "pass0_hess_rel": parts["hess"],
        "coef_rel": coef_rel(w, fit["b"], ref["w"], ref["b"]),
        "loss_rel": abs(float(fit["loss"]) - ref["loss"]) / abs(ref["loss"]),
    }
    fit["_agreement"] = seen
    fit["_pass0_parts"] = parts
    bad = []
    if seen["rows_not_folded"]:
        short = [n for n in fit["pass_rows"] if n != cached_rows]
        bad.append(f"{len(short)} of {len(fit['pass_rows'])} passes folded "
                   f"{short[0]:.0f} rows, the cached pass holds {cached_rows}")
    for name in RELATIVE:
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
        say("  the first pass by part: " + ", ".join(
            f"{name} {max(p[name] for p in parts):.3e}" for name in ("grad", "loss", "hess")))
    return problems


def compared(fits: List[Dict], tol: Dict[str, float], cached_rows: int
             ) -> Dict[str, List[float]]:
    """Each number compared, the worst over the fits, beside its limit."""
    seen = [f["model"]["_agreement"] for f in fits if "_agreement" in f["model"]]
    if not seen:
        return {"rows_not_folded": [float(cached_rows), 0.0]}
    out = {"rows_not_folded": [max(a["rows_not_folded"] for a in seen), 0.0]}
    for name in RELATIVE:
        out[name] = [max(a[name] for a in seen), tol[name]]
    return out
