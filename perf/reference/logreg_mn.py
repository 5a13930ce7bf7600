"""Reference multinomial logistic regression: plain MM-Newton in
`jax.numpy`, float32 at `highest` matmul precision. The objective is the
program's (`models/logistic_regression.py`, Spark ML's multinomial family
with `standardization=False`), written out:

    J(W, b) = 1/n Σ_i [log Σ_c exp(z_ic) − z_i,y_i] + λ/2 ‖W‖²,
    z_i = x_i W + b,

with labels in {0 … C−1} and the intercepts unpenalised. A pass takes,
batch by batch (a batch is a block: no mask, no padding, no cache, no
group), the raw sums at the current (W, b) — the exact gradient Xᵀ(P − Y)
and Σ(P − Y), for each class c the curvature block Xᵀ diag(p_c) X with its
border Xᵀ p_c and Σ p_c, the loss and the row count — each batch's in
float32 on the device, their sum over the batches in float64 on the host.
Each class's bordered system

    (H_c / n + λ diag(1 … 1, 0)) δ_c = g_c / n + λ [w_c; 0]

is solved dense in float64 on the host, and (W, b) ← (W, b) − δ, kept in
float32 between passes as the program keeps its iterate. `max_iter` passes
from the given start, stopping early when ‖δ‖ ≤ `tol`; no line search.

Its one departure from Spark, and the program's: Spark's solver (and
cuML's, which the upstream suite runs) is L-BFGS on the same objective;
this one is MM-Newton, which bounds the softmax Hessian's class coupling
diag(p) − ppᵀ by diag(p) — a block-diagonal majorizer, one (d+1) system a
class — and descends monotonically to the same optimum. The reference
computes the same algorithm as the program so that the iterates, and not
only the optimum, can be compared. Imports nothing from
`spark_rapids_ml_tpu`."""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np


@functools.lru_cache(maxsize=None)
def _batch_stats(n_classes: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(w, b, x, y):
        with jax.default_matmul_precision("highest"):
            x = x.astype(jnp.float32)
            labels = y.astype(jnp.int32)
            z = x @ w + b  # (n, C)
            top = jnp.max(z, axis=1, keepdims=True)
            e = jnp.exp(z - top)
            total = jnp.sum(e, axis=1, keepdims=True)
            p = e / total
            onehot = (labels[:, None] == jnp.arange(n_classes)[None, :]).astype(jnp.float32)
            r = p - onehot
            z_label = jnp.sum(z * onehot, axis=1)
            loss = jnp.sum(top[:, 0] + jnp.log(total[:, 0]) - z_label)
            return {
                "gw": x.T @ r,
                "gb": jnp.sum(r, axis=0),
                "hw": jnp.stack([x.T @ (x * p[:, c:c + 1]) for c in range(n_classes)]),
                "hwb": (x.T @ p).T,
                "hbb": jnp.sum(p, axis=0),
                "loss": loss,
            }

    return stats


def scan(batches, w: np.ndarray, b: np.ndarray, rounded=None) -> Dict[str, np.ndarray]:
    """One pass: the raw sums in float64 over every (rows, labels) batch at
    fixed (W, b), and `n`, the rows. `rounded`: a function applied, on the
    device, to each batch's rows (a control's precision: what a fold
    computed in it would see of them); labels are class numbers in any
    precision and the iterate stays float32, as the program keeps it."""
    import jax
    import jax.numpy as jnp

    wd = jnp.asarray(w, jnp.float32)
    bd = jnp.asarray(b, jnp.float32)
    stats = _batch_stats(int(wd.shape[1]))
    total: Dict[str, np.ndarray] = {}
    n = 0
    for x, y in batches:
        if rounded is not None:
            x = rounded(x)
        got = jax.device_get(stats(wd, bd, x, y))
        for key, value in got.items():
            value = np.asarray(value, np.float64)
            total[key] = value if key not in total else total[key] + value
        n += int(x.shape[0])
    total["n"] = float(n)
    return total


def objective(stats: Dict[str, np.ndarray], w: np.ndarray, reg: float) -> float:
    w = np.asarray(w, np.float64)
    return float(stats["loss"] / stats["n"] + 0.5 * reg * np.sum(w * w))


def mm_step(stats: Dict[str, np.ndarray], w: np.ndarray, b: np.ndarray, reg: float,
            fit_intercept: bool = True):
    """(new W, new b, ‖δ‖) from one pass's sums, one bordered solve a
    class, in float64."""
    d, n_classes = w.shape
    n = stats["n"]
    w = np.asarray(w, np.float64)
    b = np.asarray(b, np.float64)
    new_w, new_b, step = w.copy(), b.copy(), []
    for c in range(n_classes):
        grad = np.concatenate([stats["gw"][:, c] / n + reg * w[:, c], [stats["gb"][c] / n]])
        hess = np.empty((d + 1, d + 1), np.float64)
        hess[:d, :d] = stats["hw"][c] / n + reg * np.eye(d)
        hess[:d, d] = hess[d, :d] = stats["hwb"][c] / n
        hess[d, d] = stats["hbb"][c] / n
        if fit_intercept:
            delta = np.linalg.solve(hess, grad)
        else:
            delta = np.concatenate([np.linalg.solve(hess[:d, :d], grad[:d]), [0.0]])
        new_w[:, c] -= delta[:d]
        new_b[c] -= delta[d]
        step.append(delta)
    return new_w, new_b, float(np.linalg.norm(np.concatenate(step)))


def one_pass(batches, iterate: Dict[str, np.ndarray], reg: float,
             fit_intercept: bool = True, rounded=None) -> Dict[str, np.ndarray]:
    """One MM-Newton pass from `iterate` (`w` (d, C), `b` (C,)): the next
    `w` (float32) and `b`, `loss` — the objective at `iterate` — `delta`,
    `rows` and `stats`, the pass's raw sums. From an iterate the program
    reached, the reference's step from there (teacher forcing)."""
    w = np.asarray(iterate["w"], np.float32)
    b = np.asarray(iterate["b"], np.float32).reshape(-1)
    stats = scan(batches, w, b, rounded)
    new_w, new_b, delta = mm_step(stats, w, b, reg, fit_intercept)
    return {"w": new_w.astype(np.float32), "b": new_b.astype(np.float32),
            "loss": objective(stats, w, reg), "delta": delta, "rows": stats["n"],
            "stats": stats}


def fit(batches, start: Dict[str, np.ndarray], max_iter: int, tol: float, reg: float,
        fit_intercept: bool = True, rounded=None) -> Dict[str, np.ndarray]:
    """`batches`: a re-scannable sequence of (rows (n, d), labels (n,))
    pairs (device or host). `start`: `w` (d, C), `b` (C,). Returns the final
    `w` (float32) and `b`, `loss` — the objective at the iterate the LAST
    pass evaluated, which is what the program's last `step` reports —
    `before_last`, that iterate, `n_iter`, `rows`, `delta` (the step's
    length, by pass) and `pass0`: the first pass's raw sums at the start."""
    iterate = {"w": np.asarray(start["w"], np.float32),
               "b": np.asarray(start["b"], np.float32).reshape(-1)}
    out = {"loss": float("nan"), "n_iter": 0, "rows": 0.0, "delta": [], "pass0": None,
           "before_last": iterate}
    for it in range(max_iter):
        step = one_pass(batches, iterate, reg, fit_intercept, rounded)
        out["pass0"] = step["stats"] if out["pass0"] is None else out["pass0"]
        out["before_last"] = iterate
        out["delta"].append(step["delta"])
        out.update(loss=step["loss"], rows=step["rows"], n_iter=it + 1)
        iterate = {"w": step["w"], "b": step["b"]}
        if step["delta"] <= float(tol):
            break
    return {**out, **iterate}
