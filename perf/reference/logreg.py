"""Reference binary logistic regression: plain full-batch Newton in
`jax.numpy`, float32 at `highest` matmul precision. The objective is the
program's (`models/logistic_regression.py`, Spark ML's with
`standardization=False`), written out:

    J(w, b) = 1/n Σ_i [softplus(z_i) − y_i z_i] + λ/2 ‖w‖²,  z_i = x_i·w + b

with labels in {0, 1} and the intercept unpenalised. A pass takes, batch by
batch (a batch is a block: no mask, no padding, no cache, no group), the
raw sums at the current (w, b) — the gradient Xᵀ(p − y) and Σ(p − y), the
full Hessian Xᵀ diag(p(1−p)) X with its border Xᵀ p(1−p) and Σ p(1−p), the
loss and the row count — each batch's in float32 on the device, their sum
over the batches in float64 on the host. The Newton system

    (H / n + λ diag(1 … 1, 0)) δ = g / n + λ [w; 0]

is solved dense in float64 on the host, and (w, b) ← (w, b) − δ, kept in
float32 between passes as the program keeps its iterate. `max_iter` passes
from the given start, stopping early when ‖δ‖ ≤ `tol`; no line search, no
PCG, no floor. Imports nothing from `spark_rapids_ml_tpu`."""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np


@functools.lru_cache(maxsize=None)
def _batch_stats():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(w, b, x, y):
        with jax.default_matmul_precision("highest"):
            x = x.astype(jnp.float32)
            y = y.astype(jnp.float32)
            z = x @ w + b
            p = jax.nn.sigmoid(z)
            r = p - y
            wgt = p * (1.0 - p)
            return {
                "gw": x.T @ r,
                "gb": jnp.sum(r),
                "hww": x.T @ (x * wgt[:, None]),
                "hwb": x.T @ wgt,
                "hbb": jnp.sum(wgt),
                "loss": jnp.sum(jax.nn.softplus(z) - y * z),
            }

    return stats


def scan(batches, w: np.ndarray, b: float, rounded=None) -> Dict[str, np.ndarray]:
    """One pass: the raw sums in float64 over every (rows, labels) batch at
    fixed (w, b), and `n`, the rows. `rounded`: a function applied, on the
    device, to each batch's rows (the control's precision: what a fold
    computed in it would see of them); labels are 0 or 1 in any precision
    and the iterate stays float32, as the program keeps it."""
    import jax
    import jax.numpy as jnp

    wd = jnp.asarray(w, jnp.float32)
    bd = jnp.asarray(b, jnp.float32)
    total: Dict[str, np.ndarray] = {}
    n = 0
    for x, y in batches:
        if rounded is not None:
            x = rounded(x)
        got = jax.device_get(_batch_stats()(wd, bd, x, y))
        for key, value in got.items():
            value = np.asarray(value, np.float64)
            total[key] = value if key not in total else total[key] + value
        n += int(x.shape[0])
    total["n"] = float(n)
    return total


def objective(stats: Dict[str, np.ndarray], w: np.ndarray, reg: float) -> float:
    w = np.asarray(w, np.float64)
    return float(stats["loss"] / stats["n"] + 0.5 * reg * (w @ w))


def newton_step(stats: Dict[str, np.ndarray], w: np.ndarray, b: float, reg: float,
                fit_intercept: bool = True):
    """(new w, new b, ‖δ‖) from one pass's sums, in float64."""
    d = w.shape[0]
    n = stats["n"]
    w = np.asarray(w, np.float64)
    grad = np.concatenate([stats["gw"] / n + reg * w, [stats["gb"] / n]])
    hess = np.empty((d + 1, d + 1), np.float64)
    hess[:d, :d] = stats["hww"] / n + reg * np.eye(d)
    hess[:d, d] = hess[d, :d] = stats["hwb"] / n
    hess[d, d] = stats["hbb"] / n
    if fit_intercept:
        delta = np.linalg.solve(hess, grad)
    else:
        delta = np.concatenate([np.linalg.solve(hess[:d, :d], grad[:d]), [0.0]])
    return w - delta[:d], float(b) - float(delta[d]), float(np.linalg.norm(delta))


def fit(batches, start: Dict[str, np.ndarray], max_iter: int, tol: float, reg: float,
        fit_intercept: bool = True, rounded=None) -> Dict[str, np.ndarray]:
    """`batches`: a re-scannable sequence of (rows (n, d), labels (n,))
    pairs (device or host). `start`: `w` (d,), `b` (1,). Returns the final
    `w` (float32) and `b`, `loss` — the objective at the iterate the LAST
    pass evaluated, which is what the program's last `step` reports —
    `n_iter`, `rows`, and `pass0`: the first pass's raw sums at the start."""
    w = np.asarray(start["w"], np.float32)
    b = float(np.asarray(start["b"], np.float32).reshape(-1)[0])
    pass0 = None
    loss = float("nan")
    n_iter = 0
    rows = 0.0
    for it in range(max_iter):
        stats = scan(batches, w, b, rounded)
        if pass0 is None:
            pass0 = stats
        loss = objective(stats, w, reg)
        rows = stats["n"]
        new_w, new_b, delta = newton_step(stats, w, b, reg, fit_intercept)
        w, b = new_w.astype(np.float32), float(np.float32(new_b))
        n_iter = it + 1
        if delta <= float(tol):
            break
    return {"w": w, "b": b, "loss": loss, "n_iter": n_iter, "rows": rows,
            "pass0": pass0}
