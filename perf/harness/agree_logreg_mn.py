"""The comparison that decides `correct` in the multinomial logistic cell:
every fit of the window against the plain reference
(`reference/logreg_mn.py`) at the tolerances the configuration's file
states, and the rows every pass folded against the rows of the cached pass.

Numbers (each the worst over the window's fits, printed beside its limit):

* `rows_not_folded` — over every pass of every fit, |n − rows of the cached
  pass|, `n` the pass's own row count (whole numbers under 2^24, exact in
  float32); limit 0.
* `pass0_grad_rel` — the first pass's statistics at the common start, where
  the fixed point hides nothing yet: the larger of ‖Δg‖ ÷ ‖g‖ over the joint
  gradient [Xᵀ(P − Y); Σ(P − Y)] of every class (the intercepts' entries in
  the vector, so that a small Σ(p_c − y_c) cannot blow a ratio of its own
  up) and |Δloss| ÷ loss. Float32-`highest` sums in the program.
* `pass0_hess_rel` — the worst class's ‖ΔH_c‖_F ÷ ‖H_c‖_F over its bordered
  curvature block [[Xᵀ D_c X, Xᵀ D_c 1], [·, Σ D_c]], D_c = diag(p_c), of
  the same pass: a single bfloat16 product with float32 accumulation in the
  program, by design.
* `coef_rel` — ‖[W; b] − [W_ref; b_ref]‖ ÷ ‖[W_ref; b_ref]‖ after the fit,
  over every class, where (W_ref, b_ref) is the reference's last pass taken
  from the iterate the program's last pass started at (teacher forcing:
  `reference/logreg_mn.py` `one_pass`). MM-Newton is ten passes from its
  fixed point at the end of a fit, and its iterates carry the curvature's
  bfloat16 rounding, which the stated precision allows, as far as the data
  rounding of a control: two whole trajectories cannot be told apart by
  one limit (PERF.md §2). One pass from one iterate can: what it compares
  is the last pass's gradient, its solve and where its coefficients are
  produced.
* `loss_rel` — |loss − loss_ref| ÷ loss_ref, the objective the last pass
  evaluated (what the last `step` reports) against the reference's at the
  same iterate.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from perf.harness import layout

_BINARY = layout.load_module(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "harness", "agree_logreg")
#: the numbers compared, and the worst of each over the fits beside its
#: limit: the binary cell's (`harness/agree_logreg.py`)
RELATIVE = _BINARY.RELATIVE
compared = _BINARY.compared


def _bordered(stats: Dict[str, np.ndarray], c: int) -> np.ndarray:
    hww = np.asarray(stats["hw"][c], np.float64)
    hwb = np.asarray(stats["hwb"][c], np.float64)
    d = hwb.shape[0]
    out = np.empty((d + 1, d + 1), np.float64)
    out[:d, :d] = hww
    out[:d, d] = out[d, :d] = hwb
    out[d, d] = float(stats["hbb"][c])
    return out


def _joint_gradient(stats: Dict[str, np.ndarray]) -> np.ndarray:
    return np.concatenate([np.asarray(stats["gw"], np.float64).reshape(-1),
                           np.asarray(stats["gb"], np.float64).reshape(-1)])


def pass0_parts(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The parts of `pass0_grad_rel` (the larger of `grad`, `loss`) and
    `pass0_hess_rel` (`hess`, the worst class)."""
    g, g_ref = _joint_gradient(got), _joint_gradient(ref)
    hess = []
    for c in range(np.asarray(ref["hbb"]).shape[0]):
        h, h_ref = _bordered(got, c), _bordered(ref, c)
        hess.append(float(np.linalg.norm(h - h_ref) / np.linalg.norm(h_ref)))
    return {
        "grad": float(np.linalg.norm(g - g_ref) / np.linalg.norm(g_ref)),
        "loss": float(abs(float(got["loss"]) - float(ref["loss"])) / abs(float(ref["loss"]))),
        "hess": max(hess),
    }


def coef_rel(w, b, ref_w, ref_b) -> float:
    got = np.concatenate([np.asarray(w, np.float64).reshape(-1),
                          np.asarray(b, np.float64).reshape(-1)])
    ref = np.concatenate([np.asarray(ref_w, np.float64).reshape(-1),
                          np.asarray(ref_b, np.float64).reshape(-1)])
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_fit(fit: Dict, pass0_ref: Dict, last_ref: Dict, tol: Dict[str, float],
              cached_rows: int) -> List[str]:
    """Problems with one fit's model (empty = agrees). `fit`: `w` (d, C),
    `b` (C,), `loss`, `pass0` (the first pass's raw sums) and `pass_rows`
    (the row count `n` of each of its passes). `pass0_ref`: the reference's
    raw sums at the common start; `last_ref`: the reference's pass (`w`, `b`,
    `loss`) from the iterate the fit's last pass started at."""
    w, b = np.asarray(fit["w"]), np.asarray(fit["b"])
    if w.shape != np.asarray(last_ref["w"]).shape or b.shape != np.asarray(last_ref["b"]).shape:
        return [f"coefficients of shape {w.shape}, intercepts of shape {b.shape}"]
    if not (np.isfinite(w).all() and np.isfinite(b).all() and np.isfinite(fit["loss"])):
        return ["non-finite values in the model"]
    parts = pass0_parts(fit["pass0"], pass0_ref)
    seen = {
        "rows_not_folded": float(max(abs(cached_rows - n) for n in fit["pass_rows"])),
        "pass0_grad_rel": max(parts["grad"], parts["loss"]),
        "pass0_hess_rel": parts["hess"],
        "coef_rel": coef_rel(w, b, last_ref["w"], last_ref["b"]),
        "loss_rel": abs(float(fit["loss"]) - last_ref["loss"]) / abs(last_ref["loss"]),
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


def check_fits(fits: List[Dict], pass0_ref: Dict, last_pass_of, tol: Dict[str, float],
               cached_rows: int, say) -> List[str]:
    """Every fit of the window; `last_pass_of(model)` gives the reference's
    pass from the iterate that fit's last pass started at."""
    problems = [f"fit {f['fit']}: {b}" for f in fits
                for b in check_fit(f["model"], pass0_ref, last_pass_of(f["model"]), tol,
                                   cached_rows)]
    worst = compared(fits, tol, cached_rows)
    if len(worst) > 1:
        say(f"agreement over {len(fits)} fits: " + ", ".join(
            f"{name} {value:.3e}" for name, (value, _) in worst.items()))
        parts = [f["model"]["_pass0_parts"] for f in fits if "_pass0_parts" in f["model"]]
        say("  the first pass by part: " + ", ".join(
            f"{name} {max(p[name] for p in parts):.3e}" for name in ("grad", "loss", "hess")))
    return problems

