"""Reference binary elastic-net logistic regression: plain proximal Newton.
The objective is the program's (`models/logistic_regression.py`, Spark ML's
with `standardization=False` and `elasticNetParam` α), written out:

    J(w, b) = 1/n Σ_i [softplus(z_i) − y_i z_i] + λ(α‖w‖₁ + (1 − α)/2 ‖w‖²),
    z_i = x_i·w + b,

labels in {0, 1}, the intercept unpenalised. A pass is the L2 reference's
(`reference/logreg.py` `scan`): batch by batch, the raw sums at the current
(w, b) — the gradient, the full Hessian with its border, the loss and the
row count — each batch's in float32 at `highest` precision on the device,
their sum over the batches in float64 on the host. The step is the exact minimiser of
the quadratic model of the smooth part plus the L1 term,

    min_δ gᵀδ + ½ δᵀ H δ + λα ‖w + δ_w‖₁,   g, H of 1/n Σ loss + λ(1 − α)/2 ‖w‖²,

solved in float64 on the host: FISTA with adaptive restarts from the
current iterate, then the linear system on the support it found, with the
signs it found, and the optimality conditions checked on every coordinate;
a support that fails them is corrected (coordinates that changed sign
leave, the worst violators enter) or FISTA runs tighter. (w, b) ← the
minimiser, kept in float32 between passes as the program keeps its
iterate. No line search. Imports nothing from `spark_rapids_ml_tpu`."""

from __future__ import annotations

from typing import Dict

import numpy as np

from perf.reference.logreg import scan

#: the largest optimality-condition residual a step is accepted at, relative
#: to the scale of the model's linear term and the L1 weight
KKT_TOL = 1e-11


def penalties(reg: float, alpha: float):
    """(L1 weight λα, L2 weight λ(1 − α))."""
    return reg * alpha, reg * (1.0 - alpha)


def objective(stats: Dict[str, np.ndarray], w: np.ndarray, reg: float,
              alpha: float) -> float:
    l1, l2 = penalties(reg, alpha)
    w = np.asarray(w, np.float64)
    return float(stats["loss"] / stats["n"] + 0.5 * l2 * (w @ w) + l1 * np.abs(w).sum())


def model(stats: Dict[str, np.ndarray], w: np.ndarray, reg: float, alpha: float):
    """(g, H) of the smooth part at w, float64: the joint gradient
    [gw/n + λ₂w; gb/n] and the bordered Hessian with λ₂ on the w block."""
    _, l2 = penalties(reg, alpha)
    d = w.shape[0]
    n = stats["n"]
    w = np.asarray(w, np.float64)
    grad = np.concatenate([stats["gw"] / n + l2 * w, [stats["gb"] / n]])
    hess = np.empty((d + 1, d + 1), np.float64)
    hess[:d, :d] = stats["hww"] / n + l2 * np.eye(d)
    hess[:d, d] = hess[d, :d] = stats["hwb"] / n
    hess[d, d] = stats["hbb"] / n
    return grad, hess


def kkt_residual(hess, c, l1, z, free) -> float:
    """The largest violation of the optimality conditions of
    min ½zᵀHz + cᵀz + l1‖z_w‖₁ at z: |r_j + l1 sign z_j| where z_j ≠ 0,
    max(|r_j| − l1, 0) where z_j = 0 (r = c + Hz), |r_b| for a free b."""
    r = c + hess @ z
    rw, zw = r[:-1], z[:-1]
    on = zw != 0.0
    worst = max(np.max(np.abs(rw[on] + l1 * np.sign(zw[on])), initial=0.0),
                np.max(np.maximum(np.abs(rw[~on]) - l1, 0.0), initial=0.0))
    return max(worst, abs(r[-1]) if free else 0.0)


def _fista(hess, c, l1, z, free, lip, tol, max_iter=20000):
    """FISTA with adaptive restarts from z until the gradient mapping's
    largest entry is at most `tol`."""
    def prox(u):
        out = np.sign(u) * np.maximum(np.abs(u) - l1 / lip, 0.0)
        out[-1] = u[-1] if free else 0.0
        return out

    prev, t = z.copy(), 1.0
    for _ in range(max_iter):
        t_next = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
        y = z + ((t - 1.0) / t_next) * (z - prev)
        new = prox(y - (c + hess @ y) / lip)
        if lip * np.max(np.abs(new - y)) <= tol:
            return new
        if (new - z) @ (y - new) > 0.0:
            t_next = 1.0
        prev, z, t = z, new, t_next
    return z


def _polish(hess, c, l1, z, free, rounds=50):
    """The minimiser with z's support and signs, by the linear system on
    the support; coordinates whose sign the solve turns leave, the worst
    violator of the conditions off the support enters. None if it does not
    settle."""
    d = len(c) - 1
    signs = np.sign(z[:d])
    for _ in range(rounds):
        support = np.flatnonzero(signs)
        free_set = np.concatenate([support, [d]]) if free else support
        rhs = -(c[free_set] + l1 * np.concatenate([signs[support], [0.0]])[:len(free_set)])
        out = np.zeros_like(c)
        if len(free_set):
            out[free_set] = np.linalg.solve(hess[np.ix_(free_set, free_set)], rhs)
        turned = support[np.sign(out[support]) != signs[support]]
        if len(turned):
            signs[turned] = 0.0
            continue
        r = (c + hess @ out)[:d]
        over = np.abs(r) - l1
        over[support] = -np.inf
        j = int(np.argmax(over))
        if over[j] <= KKT_TOL * (np.max(np.abs(c)) + l1):
            return out
        signs[j] = -np.sign(r[j])
    return None


def solve_subproblem(hess, c, l1, z0, free=True) -> np.ndarray:
    """argmin_z ½zᵀHz + cᵀz + l1‖z_w‖₁ (z = [w; b], b unpenalised and free
    if `free`, else fixed at z0's), float64, to `KKT_TOL`."""
    from scipy.sparse.linalg import eigsh

    c = np.asarray(c, np.float64)
    scale = np.max(np.abs(c)) + l1
    z = np.asarray(z0, np.float64).copy()
    if not free:  # b fixed: solve for w alone, b's column in the linear term
        c = c.copy()
        c[:-1] += hess[:-1, -1] * z[-1]
        c[-1] = 0.0
        hess = hess.copy()
        hess[:-1, -1] = hess[-1, :-1] = 0.0
        fixed_b, z[-1] = z[-1], 0.0
    got = _polish(hess, c, l1, z, free, rounds=2)  # z0's support, if it holds
    if got is None or kkt_residual(hess, c, l1, got, free) > KKT_TOL * scale:
        lip = 1.01 * float(eigsh(hess, k=1, which="LA", return_eigenvectors=False)[0])
        tol = 1e-6 * scale
        for _ in range(6):
            z = _fista(hess, c, l1, z, free, lip, tol)
            got = _polish(hess, c, l1, z, free)
            if got is not None and kkt_residual(hess, c, l1, got, free) <= KKT_TOL * scale:
                break
            tol *= 1e-2
        else:
            raise RuntimeError("the reference's subproblem did not settle")
    if not free:
        got[-1] = fixed_b
    return got


def prox_newton_step(stats: Dict[str, np.ndarray], w: np.ndarray, b: float, reg: float,
                     alpha: float, fit_intercept: bool = True):
    """(new w, new b, ‖δ‖) from one pass's sums, in float64."""
    l1, _ = penalties(reg, alpha)
    grad, hess = model(stats, w, reg, alpha)
    x = np.concatenate([np.asarray(w, np.float64), [float(b)]])
    z = solve_subproblem(hess, grad - hess @ x, l1, x, fit_intercept)
    d = w.shape[0]
    return z[:d], float(z[d]), float(np.linalg.norm(z - x))


def fit(batches, start: Dict[str, np.ndarray], max_iter: int, tol: float, reg: float,
        alpha: float, fit_intercept: bool = True, rounded=None) -> Dict[str, np.ndarray]:
    """`batches`: a re-scannable sequence of (rows (n, d), labels (n,))
    pairs. `start`: `w` (d,), `b` (1,). Returns the final `w` (float32) and
    `b`; `first_w`, `first_b` — the iterate the FIRST boundary left;
    `last_w`, `last_b` — the iterate the LAST pass evaluated; `loss` — the
    objective there, which is what the program's last `step` reports;
    `n_iter`, `rows`, and `pass0`: the first pass's raw sums at the start."""
    w = np.asarray(start["w"], np.float32)
    b = float(np.asarray(start["b"], np.float32).reshape(-1)[0])
    pass0 = None
    loss = float("nan")
    n_iter, rows = 0, 0.0
    last_w, last_b = w, b
    first_w, first_b = w, b
    for it in range(max_iter):
        stats = scan(batches, w, b, rounded)
        if pass0 is None:
            pass0 = stats
        loss = objective(stats, w, reg, alpha)
        rows = stats["n"]
        last_w, last_b = w, b
        new_w, new_b, delta = prox_newton_step(stats, w, b, reg, alpha, fit_intercept)
        w, b = new_w.astype(np.float32), float(np.float32(new_b))
        if it == 0:
            first_w, first_b = w, b
        n_iter = it + 1
        if delta <= float(tol):
            break
    return {"w": w, "b": b, "first_w": first_w, "first_b": first_b, "last_w": last_w,
            "last_b": last_b, "loss": loss, "n_iter": n_iter, "rows": rows, "pass0": pass0}


def first_step(pass0: Dict[str, np.ndarray], start: Dict[str, np.ndarray], reg: float,
               alpha: float, fit_intercept: bool = True) -> Dict[str, np.ndarray]:
    """The reference's first boundary from the common start, from its first
    pass's sums `pass0` (`scan` at the start): the exact step, where the
    support moves most and an inner solve works hardest."""
    w = np.asarray(start["w"], np.float32)
    b = float(np.asarray(start["b"], np.float32).reshape(-1)[0])
    new_w, new_b, _ = prox_newton_step(pass0, w, b, reg, alpha, fit_intercept)
    return {"w": new_w, "b": new_b}


def last_step(batches, w: np.ndarray, b: float, reg: float, alpha: float,
              fit_intercept: bool = True) -> Dict[str, np.ndarray]:
    """The reference's boundary from a given iterate (teacher forcing): one
    pass at (w, b), the objective there and the exact step from it."""
    stats = scan(batches, np.asarray(w, np.float32), float(np.float32(b)))
    new_w, new_b, _ = prox_newton_step(stats, np.asarray(w, np.float32),
                                       float(np.float32(b)), reg, alpha, fit_intercept)
    return {"w": new_w, "b": new_b, "loss": objective(stats, np.asarray(w, np.float32),
                                                       reg, alpha)}
