#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` takes no options, runs in ONE process (daemon,
client threads and references together — a chip belongs to one process)
and drives the main path once at the north-star width (BASELINE.json
config #2: d=2048, k=32) under the shipped ``auto`` profile:

  0. device     the default backend must be ``tpu`` — no override, no CPU run
  1. fit        Arrow over TCP → DataPlaneDaemon → mesh → ``finalize_pca``
  2. serve      ``ensure_model`` + ``warmup`` + threaded ``transform`` requests
  3. library    ``srml.PCA().setK(k).fit(...)`` / ``model.transform(...)``
  4. agreement  fit vs a float32 ``highest`` Gram + float64 host ``eigh``;
                every served response vs ``x @ pc`` in numpy
  5. fallbacks  nothing compiled during the serve window, no AOT miss, which
                Gram path ran
  6. kernels    every Pallas kernel ``use_pallas=auto`` / ``ann_fused_scan=
                auto`` turns on, through the model-level function that gates
                it, against the plain-jnp path
  7. forest     one RandomForestRegressor fit at the upstream suite's widths
                (3,000 float32 columns, 128 bins, depth 6; a few trees, two
                65,536-row batches) through the daemon's cached job — every
                depth a ``rescan`` — against the benchmark's plain reference
                (``perf/reference/rf.py``) under the deployment's tolerances;
                and its depth-2 level folded both ways — the whole frontier,
                and one child of every pair with the sibling taken from the
                parent's histogram — the count channel equal cell for cell;
                every fold of the fit through the kernel that makes the bin
                one-hot in VMEM (``srml_forest_fold_path_total{path=fused}``),
                and that level once more through the XLA body: the same counts
  8. summary    two JSON lines close stdout: the full summary (per-stage
                seconds, compiles, cache hits, kernel verdicts, ``"claim":
                null``), then — the last line, which the driver parses —
                exactly ``{"ok": true, "device": {"platform", "kind",
                "count"}}`` as JAX reports the device

It exits non-zero at the first stage that fails and prints no result then.
The stage functions take their sizes as arguments so tier-1
(tests/test_chip_smoke.py) runs stages 1–5 small on the CPU mesh; the
``__main__`` path always demands the chip. No timing here is a performance
claim — seconds are set-up information.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: BASELINE.json config #2 width; depth (rows) is cut.
D, K = 2048, 32
#: Five partitions, 161,072 rows: four full and one ragged, each fed as
#: Arrow batches of at most FEED_ROWS rows — the ragged tail batch (13,616
#: rows) makes ``_Job._bucket`` pad + mask.
PARTITION_ROWS = (32768, 32768, 32768, 32768, 30000)
FEED_ROWS = 16384
#: Serve row counts on and between the 64/256/1024/4096 ladder, and beyond
#: it (the oversize bypass — by design a lazy jit at a shape nothing primed).
LADDER_ROWS = (64, 256, 1000, 1024, 4096)
OVERSIZE_ROWS = (5000,)
SERVE_THREADS = 4
SERVE_ROUNDS = 2  # each thread sends every LADDER_ROWS size this many times

# Tolerances, each with its reason (bf16 inputs have a 2^-9 relative
# rounding error per element; accumulation is float32 throughout).
#: Per-component |cos| and top-k principal-angle cosine, fit vs reference.
#: Rounding x to bf16 perturbs the Gram by a random symmetric E with
#: ‖E‖ ≈ 2·√d·2^-9·√n·σ² ≈ 300 at (n, d) = (161072, 2048), against planted
#: eigen-gaps of n·1.75 ≈ 2.8e5 (adjacent components) and n·11.6 (subspace):
#: sin θ ≈ 1e-3, cos ≈ 1 − 5e-7. 1 − 1e-4 leaves a 14× margin in angle.
TOL_COS = 1.0 - 1e-4
#: Relative error of explained_variance (σᵢ/Σσ): δλ/λ ≤ ‖E‖/(n·λ_k) ≈ 1.5e-4,
#: halved by the square root; 2^-9 is one bf16 ulp.
TOL_EV_REL = 2.0**-9
#: Served y = x·pc: two bf16 roundings per product give |Δy| ≤
#: 2^-8·Σ|x_j·pc_j| ≤ 2^-8·‖x_row‖·‖pc_col‖ (Cauchy–Schwarz); 2^-7 doubles
#: that for the float32 accumulation and the response's float32 cast.
TOL_SERVE = 2.0**-7
#: Four-chip vs one-device fit of the same data: same bf16 inputs, only the
#: float32 reduction order differs (psum of four partial Grams).
TOL_COS_MESH = 1.0 - 1e-6


class SmokeFailure(AssertionError):
    """A stage's check did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# ---------------------------------------------------------------------------
# Process-wide compile observation (independent of the jit ledger, so a
# compile outside any ledgered call is still seen)
# ---------------------------------------------------------------------------


class CompileWatch:
    """Counts every program the process builds — or loads from the
    persistent cache: JAX fires the same event for both, so "zero" here
    means neither happened."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw: Any) -> None:
        if event.endswith("backend_compile_duration"):
            with self._lock:
                self.count += 1
                self.seconds += float(duration)


# ---------------------------------------------------------------------------
# Stage 0 — device
# ---------------------------------------------------------------------------


def stage_device() -> Dict[str, Any]:
    """Name the device; anything but a TPU default backend is a failure.
    (JAX falls back to the CPU with only a warning when the TPU cannot be
    initialised, e.g. because another process holds it — this stage is
    what turns that into a failure.)"""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(
        f"jax {jax.__version__}  default_backend={backend}  "
        f"platform={info['platform']}  device_kind={info['kind']}  "
        f"devices={info['count']}  "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
    )
    check(
        backend == "tpu" and info["platform"] == "tpu",
        f"chip_smoke needs a TPU: the default JAX backend here is "
        f"{backend!r} (platform {info['platform']!r}, "
        f"{info['kind']!r}). It does not run on anything else.",
    )
    return info


# ---------------------------------------------------------------------------
# Built from what git would commit
# ---------------------------------------------------------------------------


def build_native_bridge() -> None:
    """Delete any stale library and build ``native/build/libsrml_tpu.so``
    from ``native/src/columnar.cpp`` on THIS machine, before the first
    bridge call (a copy built for another CPU can die with an illegal
    instruction; a fresh checkout has none)."""
    native = os.path.join(REPO, "native")
    subprocess.run(["make", "-C", native, "clean"], check=True,
                   stdout=subprocess.DEVNULL)
    subprocess.run(["make", "-C", native], check=True,
                   stdout=subprocess.DEVNULL)


# ---------------------------------------------------------------------------
# Data: a planted, well-separated top-k spectrum
# ---------------------------------------------------------------------------


def planted_data(seed: int, n_rows: int, d: int, k: int) -> np.ndarray:
    """(n_rows, d) float32 rows = k planted orthonormal directions with
    standard deviations 32·0.93^i (covariance eigenvalues ≈ 1025 … 12.6,
    adjacent ratio 0.865) over a unit noise floor (eigenvalue 1), plus a
    small column mean so the fused centering does work."""
    rng = np.random.default_rng(seed)
    basis, _ = np.linalg.qr(rng.standard_normal((d, k)))
    scale = (32.0 * 0.93 ** np.arange(k)).astype(np.float32)
    z = rng.standard_normal((n_rows, k), dtype=np.float32) * scale
    x = z @ basis.T.astype(np.float32)
    x += rng.standard_normal((n_rows, d), dtype=np.float32)
    x += rng.uniform(-0.5, 0.5, size=d).astype(np.float32)
    return x


# ---------------------------------------------------------------------------
# Stage 1 — fit through the daemon
# ---------------------------------------------------------------------------


def _feed_partition(address, job: str, pid: int, part: np.ndarray,
                    feed_rows: int) -> int:
    """What one Spark task does: its partition as Arrow
    ``fixed_size_list<float32>`` batches over one connection, then commit."""
    import pyarrow as pa

    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column
    from spark_rapids_ml_tpu.serve import DataPlaneClient

    with DataPlaneClient(*address) as client:
        for lo in range(0, part.shape[0], feed_rows):
            table = pa.table(
                {"features": matrix_to_list_column(part[lo:lo + feed_rows])}
            )
            client.feed(job, table, algo="pca", partition=pid)
        return client.commit(job, partition=pid)


def _fit_through(daemon, parts: List[np.ndarray], k: int, job: str,
                 feed_rows: int) -> Dict[str, np.ndarray]:
    from spark_rapids_ml_tpu.serve import DataPlaneClient

    with concurrent.futures.ThreadPoolExecutor(max_workers=len(parts)) as pool:
        futures = [
            pool.submit(_feed_partition, daemon.address, job, pid, part,
                        feed_rows)
            for pid, part in enumerate(parts)
        ]
        for f in futures:
            f.result()
    with DataPlaneClient(*daemon.address) as client:
        return client.finalize_pca(job, k=k)


def _peak_bytes() -> Optional[List[int]]:
    import jax

    stats = [dev.memory_stats() for dev in jax.devices()]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


def component_cosines(a: np.ndarray, b: np.ndarray) -> Tuple[float, float]:
    """(min per-component |cos|, min principal-angle cosine of the spans)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    per = np.abs(np.sum(a * b, axis=0)) / (
        np.linalg.norm(a, axis=0) * np.linalg.norm(b, axis=0)
    )
    qa, _ = np.linalg.qr(a)
    qb, _ = np.linalg.qr(b)
    principal = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(per.min()), float(principal.min())


def stage_fit_daemon(daemon, parts: List[np.ndarray], k: int,
                     feed_rows: int) -> Dict[str, Any]:
    """Feed ≥4 partitions through ``daemon`` (an in-process
    ``DataPlaneDaemon()`` on loopback — how the Spark driver starts one
    when unconfigured), commit each, ``finalize_pca``. On more than one
    device: the mesh must span them all, every device must take part, and
    the fit must agree with a one-device fit of the same data."""
    import jax

    from spark_rapids_ml_tpu.parallel.mesh import (
        DATA_AXIS,
        default_mesh,
        make_mesh,
    )
    from spark_rapids_ml_tpu.serve import DataPlaneDaemon

    n_dev = len(jax.devices())
    mesh = default_mesh()
    say(f"  default mesh: {dict(mesh.shape)} over {n_dev} device(s)")
    check(mesh.shape[DATA_AXIS] == n_dev,
          f"default_mesh() put {mesh.shape[DATA_AXIS]} devices on the data "
          f"axis; this host has {n_dev}")
    peak_before = _peak_bytes()
    fit = _fit_through(daemon, parts, k, "smoke-pca", feed_rows)
    n_rows = sum(p.shape[0] for p in parts)
    d = parts[0].shape[1]
    check(fit["pc"].shape == (d, k) and fit["explained_variance"].shape == (k,)
          and fit["mean"].shape == (d,),
          f"finalize_pca shapes: pc {fit['pc'].shape}, "
          f"ev {fit['explained_variance'].shape}, mean {fit['mean'].shape}")
    check(all(np.isfinite(fit[name]).all() for name in fit),
          "finalize_pca returned non-finite values")
    out: Dict[str, Any] = {"fit": fit, "rows": n_rows, "mesh_data": n_dev}
    peak_after = _peak_bytes()
    if peak_before is not None and peak_after is not None:
        rose = [a > b for a, b in zip(peak_after, peak_before)]
        say(f"  peak_bytes_in_use per device: {peak_before} -> {peak_after}")
        check(all(rose), f"not every device took part in the fold: {rose}")
        out["devices_took_part"] = n_dev
    else:
        say("  device memory_stats: not reported by this backend")
        out["devices_took_part"] = None
    if n_dev > 1:
        one = make_mesh(devices=jax.devices()[:1])
        with DataPlaneDaemon(mesh=one) as single:
            fit1 = _fit_through(single, parts, k, "smoke-pca-1dev", feed_rows)
        per, principal = component_cosines(fit["pc"], fit1["pc"])
        ev_rel = float(np.max(np.abs(
            fit["explained_variance"] / fit1["explained_variance"] - 1.0)))
        say(f"  {n_dev}-device vs 1-device fit: min |cos| {per:.9f}, "
            f"min principal cos {principal:.9f}, ev rel {ev_rel:.2e}")
        check(per >= TOL_COS_MESH and principal >= TOL_COS_MESH
              and ev_rel <= TOL_EV_REL,
              "the multi-device fit disagrees with the one-device fit")
        out["vs_one_device"] = {"min_cos": per, "min_principal_cos": principal,
                                "ev_rel": ev_rel}
    return out


# ---------------------------------------------------------------------------
# Stage 2 — serve
# ---------------------------------------------------------------------------


def _aot_status(client, name: str) -> Dict[str, Any]:
    status = client.model_status(name)
    check("aot" in status, f"model {name!r} has no AOT ledger: {status}")
    return status["aot"]


def _serve_window(address, name: str, x: np.ndarray, sizes: Sequence[int],
                  n_threads: int, rounds: int) -> List[Tuple[int, int, np.ndarray]]:
    """``n_threads`` client threads, each with its own connection, each
    sending every size ``rounds`` times at its own row offset. Returns
    (offset, rows, response) per request."""
    from spark_rapids_ml_tpu.serve import DataPlaneClient

    span = max(sizes)
    check(x.shape[0] >= span, f"{x.shape[0]} rows cannot fill a {span}-row request")
    barrier = threading.Barrier(n_threads)

    def loop(tid: int) -> List[Tuple[int, int, np.ndarray]]:
        got = []
        offset = tid * ((x.shape[0] - span) // n_threads)
        with DataPlaneClient(*address) as client:
            barrier.wait(timeout=60)
            for _ in range(rounds):
                for rows in sizes:
                    out = client.transform(name, x[offset:offset + rows])
                    got.append((offset, rows, out["output"]))
        return got

    with concurrent.futures.ThreadPoolExecutor(max_workers=n_threads) as pool:
        futures = [pool.submit(loop, tid) for tid in range(n_threads)]
        return [r for f in futures for r in f.result()]


def stage_serve(daemon, fit: Dict[str, np.ndarray], x: np.ndarray,
                watch: CompileWatch,
                ladder_rows: Sequence[int] = LADDER_ROWS,
                oversize_rows: Sequence[int] = OVERSIZE_ROWS,
                n_threads: int = SERVE_THREADS,
                rounds: int = SERVE_ROUNDS) -> Dict[str, Any]:
    """Register the fitted model, warm the bucket ladder (AOT), then two
    request windows from ``n_threads`` client threads, scheduler in its
    default state: ladder traffic (must be served entirely by held
    executables, zero compiles), then the oversize bypass (by design one
    lazy compile at a shape nothing primed)."""
    import jax

    from spark_rapids_ml_tpu.models.pca import PCAModel
    from spark_rapids_ml_tpu.serve import DataPlaneClient

    d = x.shape[1]
    name = "smoke-pca-model"
    arrays = PCAModel(
        pc=fit["pc"], explained_variance=fit["explained_variance"],
        mean=fit["mean"],
    )._model_data()
    with DataPlaneClient(*daemon.address) as client:
        check(client.ensure_model(name, "pca", arrays), "model already existed")
        warm = client.warmup(name, n_cols=d)
        say(f"  warmup ack: {warm}")
        # 3 for the default ladder: the 64 bucket dedupes onto the 256-row
        # floor shape run_bucketed dispatches.
        want = len({max(256, int(b)) for b in warm["buckets"]})
        check(warm.get("enabled") is True and warm.get("aot") is True
              and warm["compiled"] == want,
              f"warmup did not AOT-compile the ladder ({want} programs): {warm}")
        aot0 = _aot_status(client, name)

        compiles0 = watch.count
        ladder = _serve_window(daemon.address, name, x, ladder_rows,
                               n_threads, rounds)
        ladder_compiles = watch.count - compiles0
        aot1 = _aot_status(client, name)
        say(f"  ladder window: {len(ladder)} requests from {n_threads} "
            f"threads, compiles {ladder_compiles}, AOT hits "
            f"{aot1['hits'] - aot0['hits']}, misses "
            f"{aot1['misses'] - aot0['misses']}")
        check(len(ladder) >= 20, f"only {len(ladder)} ladder requests")
        check(ladder_compiles == 0,
              f"{ladder_compiles} compile(s) during warmed serve requests")
        check(aot1["misses"] == aot0["misses"] and aot1["hits"] > aot0["hits"],
              f"warmed requests were not all served by held executables: "
              f"{aot0} -> {aot1}")

        compiles1 = watch.count
        oversize = _serve_window(daemon.address, name, x, oversize_rows,
                                 n_threads, 1)
        oversize_compiles = watch.count - compiles1
        aot2 = _aot_status(client, name)
        sched = client.health()["scheduler"]
        say(f"  oversize window: {len(oversize)} requests, compiles "
            f"{oversize_compiles}, AOT misses "
            f"{aot2['misses'] - aot1['misses']} (each bypass is a lazy-jit "
            f"dispatch by design)")
        # One shape (5000 → 8192 rows), compiled at most once; every bypass
        # request is a counted miss, none is an executable that rejected.
        check(oversize_compiles <= len(set(oversize_rows)),
              f"{oversize_compiles} compiles for {len(set(oversize_rows))} "
              f"oversize shape(s)")
        check(aot2["misses"] - aot1["misses"] == len(oversize),
              f"AOT misses {aot2['misses'] - aot1['misses']} != "
              f"{len(oversize)} oversize requests")
        client.drop_model(name)
    return {
        "responses": ladder + oversize,
        "ladder_requests": len(ladder),
        "oversize_requests": len(oversize),
        "ladder_compiles": ladder_compiles,
        "oversize_compiles": oversize_compiles,
        "aot": {"compiled": warm["compiled"], "buckets": warm["buckets"],
                "ladder_hits": aot1["hits"] - aot0["hits"],
                "ladder_misses": aot1["misses"] - aot0["misses"]},
        "scheduler": {key: sched.get(key) for key in ("enabled", "batches")},
        # Every served model lives on the default device until replicas
        # per chip exist (ROADMAP R7).
        "served_on": str(jax.devices()[0]),
    }


# ---------------------------------------------------------------------------
# Stage 3 — the library path (the README's front-page call)
# ---------------------------------------------------------------------------


def stage_library(x: np.ndarray, k: int, transform_rows: int) -> Dict[str, Any]:
    """``fit_pca`` (one fused SPMD program) rather than the daemon's
    ``streaming_update``."""
    import spark_rapids_ml_tpu as srml

    model = srml.PCA().setK(k).fit({"features": x})
    out = model.transform({"features": x[:transform_rows]})["pca_features"]
    check(model.pc.shape == (x.shape[1], k), f"library pc {model.pc.shape}")
    check(np.asarray(out).shape == (transform_rows, k),
          f"library transform shape {np.asarray(out).shape}")
    return {"pc": model.pc, "explained_variance": model.explainedVariance,
            "transform": np.asarray(out), "transform_rows": transform_rows}


# ---------------------------------------------------------------------------
# Stage 4 — agreement, outside any timing
# ---------------------------------------------------------------------------


def reference_pca(x: np.ndarray, k: int, chunk_rows: int) -> Dict[str, np.ndarray]:
    """ROADMAP's definition of a reference: a plain float32 ``jax.numpy``
    Gram at ``default_matmul_precision("highest")`` (chunked, summed in
    float64 on the host) + a float64 host ``eigh``, reference semantics for
    explained variance (σᵢ/Σσ)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def moments(c):
        with jax.default_matmul_precision("highest"):
            return c.T @ c, jnp.sum(c, axis=0)

    n, d = x.shape
    gram = np.zeros((d, d), np.float64)
    colsum = np.zeros((d,), np.float64)
    for lo in range(0, n, chunk_rows):
        c = x[lo:lo + chunk_rows]
        if c.shape[0] < chunk_rows:  # zero rows add nothing; one shape
            c = np.concatenate(
                [c, np.zeros((chunk_rows - c.shape[0], d), c.dtype)])
        g, s = jax.device_get(moments(jnp.asarray(c)))
        gram += np.asarray(g, np.float64)
        colsum += np.asarray(s, np.float64)
    mean = colsum / n
    w, v = np.linalg.eigh(gram - np.outer(mean, colsum))
    w, v = w[::-1], v[:, ::-1]
    sigma = np.sqrt(np.clip(w, 0.0, None))
    return {"pc": v[:, :k], "explained_variance": (sigma / sigma.sum())[:k],
            "mean": mean}


def _check_fit(label: str, pc, ev, ref) -> Dict[str, float]:
    per, principal = component_cosines(pc, ref["pc"])
    ev_rel = float(np.max(np.abs(np.asarray(ev) / ref["explained_variance"] - 1.0)))
    say(f"  {label}: min |cos| {per:.9f}  min principal cos "
        f"{principal:.9f}  explained_variance max rel err {ev_rel:.2e}")
    check(per >= TOL_COS and principal >= TOL_COS,
          f"{label}: components off the reference (min |cos| {per}, "
          f"principal {principal}; need >= {TOL_COS})")
    check(ev_rel <= TOL_EV_REL,
          f"{label}: explained_variance off by {ev_rel} (> {TOL_EV_REL})")
    return {"min_cos": per, "min_principal_cos": principal, "ev_rel": ev_rel}


def _projection_error(x_rows: np.ndarray, pc: np.ndarray, got: np.ndarray) -> float:
    """max |got − x·pc| in units of ‖x_row‖·‖pc_col‖ (see TOL_SERVE)."""
    want = x_rows.astype(np.float64) @ np.asarray(pc, np.float64)
    check(got.shape == want.shape, f"response {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values in a response")
    unit = (np.linalg.norm(x_rows.astype(np.float64), axis=1)[:, None]
            * np.linalg.norm(np.asarray(pc, np.float64), axis=0)[None, :])
    return float(np.max(np.abs(got - want) / unit))


def stage_agreement(x: np.ndarray, k: int, fit, serve, library,
                    chunk_rows: int) -> Dict[str, Any]:
    ref = reference_pca(x, k, chunk_rows)
    out = {
        "daemon_fit": _check_fit("daemon fit ", fit["pc"],
                                 fit["explained_variance"], ref),
        "library_fit": _check_fit("library fit", library["pc"],
                                  library["explained_variance"], ref),
    }
    mean_err = float(np.max(np.abs(fit["mean"] - ref["mean"])))
    check(mean_err <= TOL_EV_REL * float(np.max(np.abs(x[:4096]))),
          f"column means off by {mean_err}")
    worst = 0.0
    for offset, rows, got in serve["responses"]:
        worst = max(worst, _projection_error(x[offset:offset + rows],
                                             fit["pc"], got))
    say(f"  served responses: {len(serve['responses'])} checked, worst error "
        f"{worst:.2e} of ‖x_row‖·‖pc_col‖ (tolerance {TOL_SERVE:.2e})")
    check(worst <= TOL_SERVE, f"a served response is off by {worst}")
    lib_err = _projection_error(x[:library["transform_rows"]], library["pc"],
                                library["transform"])
    say(f"  library transform: error {lib_err:.2e}")
    check(lib_err <= TOL_SERVE, f"library transform is off by {lib_err}")
    out.update(serve_worst=worst, library_transform=lib_err,
               tolerances={"cos": TOL_COS, "ev_rel": TOL_EV_REL,
                           "serve": TOL_SERVE})
    return out


# ---------------------------------------------------------------------------
# Stage 5 — nothing fell back
# ---------------------------------------------------------------------------


def stage_no_fallback(d: int, feed_rows: int) -> Dict[str, Any]:
    """Print the jit ledger (per-fn calls and compiles) and say which Gram
    path ran. The serve-window assertions (no compile, no AOT miss) live in
    stage 2, next to the traffic they judge."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.utils import xprof

    snap = xprof.snapshot()
    say("  fn                               calls  compiles  compile_s")
    for name, agg in sorted(snap.items()):
        say(f"  {name:<32} {agg['calls']:>5}  {agg['compiles']:>8}  "
            f"{agg['compile_s']:>9.3f}")
    check(snap.get("gram.streaming_update", {}).get("calls", 0) > 0,
          "the daemon fold did not go through gram.streaming_update")
    check(snap.get("pca.fit", {}).get("calls", 0) > 0,
          "the library fit did not go through pca.fit")
    check(snap.get("pca.project", {}).get("calls", 0) > 0,
          "no transform went through pca.project")
    cd, ad = config.get("compute_dtype"), config.get("accum_dtype")
    use_pallas = bool(config.get("use_pallas"))
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
    from spark_rapids_ml_tpu.utils import metrics

    shard_rows = feed_rows // default_mesh().shape[DATA_AXIS]
    fused = gram_ops._fused_fold_applicable((shard_rows, d), cd)
    pallas_gram = gram_ops._pallas_gram_applicable((feed_rows, d), cd, ad)
    gram_path = ("pallas.gram_colsum_pallas" if fused
                 else "pallas.gram_pallas" if pallas_gram
                 else f"xla dot_general[{cd}]")
    counter = metrics.counter("srml_gram_fold_path_total")
    fold_paths = {p: counter.value(path=p) for p in ("fused", "xla")}
    say(f"  profile: compute_dtype={cd} accum_dtype={ad} use_pallas="
        f"{use_pallas} finalize={config.get('finalize')} "
        f"solver={config.get('solver')}")
    say(f"  Gram path of the daemon fold (gram.streaming_update) at "
        f"{shard_rows} x {d} rows a shard: {gram_path}; "
        f"srml_gram_fold_path_total {fold_paths}")
    # Where the one-read kernel's gate holds for the feeds' bucket (the
    # chip at a lane-aligned width) the daemon's folds must have taken it.
    check(not fused or fold_paths["fused"] > 0,
          f"the daemon's folds never counted path=fused where the gate holds: "
          f"{fold_paths}")
    return {
        "gram_path": gram_path,
        "fold_paths": fold_paths,
        "profile": {"compute_dtype": cd, "accum_dtype": ad,
                    "use_pallas": use_pallas},
        "ledger": {name: {"calls": a["calls"], "compiles": a["compiles"],
                          "compile_s": round(a["compile_s"], 3)}
                   for name, a in snap.items()},
    }


# ---------------------------------------------------------------------------
# Stage 6 — every gated Pallas kernel compiles and agrees
# ---------------------------------------------------------------------------


class PallasSpy(contextlib.AbstractContextManager):
    """Records every ``pl.pallas_call`` traced inside the block as
    (kernel name, interpret) and passes it through untouched — how a case
    proves the kernel, not its XLA twin, is in the program it ran."""

    def __init__(self) -> None:
        from jax.experimental import pallas as pl

        self._pl = pl
        self.calls: List[Tuple[str, bool]] = []

    def __enter__(self) -> "PallasSpy":
        self._real = self._pl.pallas_call

        def spy(kernel, *args, **kwargs):
            fn = getattr(kernel, "func", kernel)
            self.calls.append((kwargs.get("name") or fn.__name__,
                               bool(kwargs.get("interpret", False))))
            return self._real(kernel, *args, **kwargs)

        self._pl.pallas_call = spy
        return self

    def __exit__(self, *exc) -> None:
        self._pl.pallas_call = self._real


def rel_to_max(got, want) -> float:
    """max |got − want| / max |want| — the error in units of the result's
    largest entry (for a Gram, its largest diagonal)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


#: bf16 inputs against a float32 reference: each product carries two
#: roundings, |ΔΣ x_i·y_i| ≤ 2^-8·‖x‖·‖y‖ ≤ 2^-8·(largest diagonal); 2^-7
#: doubles it for accumulation order.
TOL_BF16 = 2.0**-7
#: float32 kernels at HIGHEST against float32 ``highest`` jnp: only the
#: accumulation order differs (≈ √n·2^-24 typical, n ≤ 16384).
TOL_F32 = 2.0**-14


def _clustered(rng, n: int, d: int, n_centers: int) -> Tuple[np.ndarray, np.ndarray]:
    centers = rng.standard_normal((n_centers, d), dtype=np.float32) * 4.0
    label = rng.integers(0, n_centers, size=n)
    x = centers[label] + rng.standard_normal((n, d), dtype=np.float32)
    return x, centers


def _case_gram(mesh, d: int, n: int):
    """gram_pallas ← ops.gram.streaming_update at float32 compute (the
    daemon's PCA fold for a user who sets compute_dtype=float32; the gate
    sits in local_stats), masked rows."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    rng = np.random.default_rng(61)
    x = rng.standard_normal((n, d), dtype=np.float32)
    mask = np.ones((n,), np.float32)
    mask[n - 100:] = 0.0
    gate = gram_ops._pallas_gram_applicable((n, d), "float32", "float32")
    update = gram_ops.streaming_update(mesh, "float32", "float32")
    count, colsum, gram = jax.device_get(update(
        gram_ops.init_stats(d, "float32"),
        jax.device_put(x, row_sharding(mesh)),
        jax.device_put(mask, row_sharding(mesh, 1)),
    ))
    with jax.default_matmul_precision("highest"):
        xm = jnp.asarray(x) * jnp.asarray(mask)[:, None]
        want = jax.device_get((xm.T @ xm, xm.sum(0)))
    errs = {"gram": rel_to_max(gram, want[0]),
            "colsum": rel_to_max(colsum, want[1]),
            "count": abs(float(count) - float(mask.sum()))}
    return gate, ["_gram_kernel"], errs, TOL_F32


def _case_gram_colsum(mesh, d: int, n: int, seeded: bool):
    """gram_colsum_pallas ← ops.gram.streaming_update on float32 rows (cast
    in the kernel): seeded (the donated state folds inside the kernel) on
    a one-device mesh, unseeded (+ psum + XLA add) on a mesh with more data
    devices. On one chip the fold never picks the unseeded variant, so it
    is called directly after the gate predicate said yes."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding
    from spark_rapids_ml_tpu.utils import metrics

    cd = jnp.dtype(config.get("compute_dtype"))
    n_data = mesh.shape[DATA_AXIS]
    rows = n * n_data
    rng = np.random.default_rng(62 + seeded)
    x = jnp.asarray(rng.standard_normal((rows, d), dtype=np.float32))
    n_valid = rows - 300  # the boundary block pays the in-kernel mask
    gate = gram_ops._fused_fold_applicable((n, d), cd)
    xf = x.astype(cd).astype(jnp.float32)[:n_valid]
    with jax.default_matmul_precision("highest"):
        want_g, want_s = jax.device_get((xf.T @ xf, xf.sum(0)))
    if seeded or n_data > 1:
        counter = metrics.counter("srml_gram_fold_path_total")
        before = counter.value(path="fused")
        update = gram_ops.streaming_update(mesh)
        state = gram_ops.init_stats(d)
        xs = jax.device_put(x, row_sharding(mesh))
        mask = jax.device_put(
            (np.arange(rows) < n_valid).astype(np.float32), row_sharding(mesh, 1))
        state = update(state, xs, mask)
        state = update(state, xs, mask)  # second fold onto a live state
        count, colsum, gram = jax.device_get(state)
        folds = 2.0
        # the fold took the kernel iff the gate said it would
        gate = gate and counter.value(path="fused") - before == 2
    else:
        gram, colsum, count = jax.device_get(gram_colsum_pallas(
            x, jnp.asarray(n_valid, jnp.int32), compute_dtype=cd.name))
        folds = 1.0
    errs = {"gram": rel_to_max(gram, folds * want_g),
            "colsum": rel_to_max(colsum, folds * want_s),
            "count": abs(float(count) - folds * n_valid)}
    # The reference is of the rows after the cast, so products are exact in
    # float32 and only the accumulation order differs.
    return gate, ["_gram_colsum_kernel"], errs, TOL_F32


def _case_lloyd(mesh, d: int, k: int, n: int, f32: bool):
    """lloyd_step_pallas (shipped profile) and, at float32 compute,
    assign_min_dist_pallas ← models.kmeans._lloyd_fn, against the same
    program built with ``use_pallas=False`` at float32."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import kmeans as km
    from spark_rapids_ml_tpu.parallel.sharding import shard_rows

    rng = np.random.default_rng(63)
    x, centers = _clustered(rng, n, d, k)
    centers0 = jnp.asarray(
        centers + 0.3 * rng.standard_normal((k, d), dtype=np.float32))
    cd = "float32" if f32 else config.get("compute_dtype")
    ad = config.get("accum_dtype")
    gates = [km._pallas_step_applicable(n, k, d, cd)]
    kernels = ["_lloyd_step_kernel"]
    if f32:
        gates.append(km._pallas_assign_applicable(n, k, d, cd))
        kernels.append("_assign_kernel")
    xs, mask, _ = shard_rows(x, mesh)
    fn = km._lloyd_fn(mesh, k, 2, 0.0, cd, ad,
                      use_pallas=bool(config.get("use_pallas")))
    got_c, got_cost, got_it = jax.device_get(fn(xs, mask, centers0))
    twin = km._lloyd_fn(mesh, k, 2, 0.0, "float32", "float32", use_pallas=False)
    want_c, want_cost, want_it = jax.device_get(twin(xs, mask, centers0))
    errs = {"centers": rel_to_max(got_c, want_c),
            # bf16 distance noise enters at the scale of Σ‖x‖², not of the
            # (much smaller) within-cluster cost.
            "cost": abs(float(got_cost) - float(want_cost))
            / float(np.sum(x.astype(np.float64) ** 2)),
            "n_iter": abs(int(got_it) - int(want_it))}
    return all(gates), kernels, errs, TOL_F32 if f32 else TOL_BF16


def _case_linreg(mesh, d: int, n: int):
    """linreg_stats_pallas ← models.linear_regression.
    streaming_normal_eq_update."""
    import jax

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import linear_regression as lr
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    rng = np.random.default_rng(64)
    # Off-centre columns: Σx is then a sum that does not cancel, so its
    # bf16 error is judged against its own size.
    x = rng.standard_normal((n, d), dtype=np.float32) + 0.5
    y = (x @ rng.standard_normal((d,), dtype=np.float32)
         + rng.standard_normal((n,), dtype=np.float32))
    mask = np.ones((n,), np.float32)
    mask[n - 77:] = 0.0
    args = (jax.device_put(x, row_sharding(mesh)),
            jax.device_put(y, row_sharding(mesh, 1)),
            jax.device_put(mask, row_sharding(mesh, 1)))
    gate = bool(config.get("use_pallas")) and config.backend_is_tpu()
    got = jax.device_get(lr.streaming_normal_eq_update(mesh)(
        lr.init_normal_eq_stats(d), *args))
    twin = lr._streaming_normal_eq_update(mesh, "float32", "float32", False)
    want = jax.device_get(twin(lr.init_normal_eq_stats(d, "float32"), *args))
    names = ("xtx", "xty", "sx", "sy", "syy", "n")
    errs = {name: rel_to_max(g, w) for name, g, w in zip(names, got, want)}
    return gate, ["_linreg_stats_kernel"], errs, TOL_BF16


def _case_newton(mesh, d: int, n: int):
    """newton_stats_pallas ← models.logistic_regression._newton_fn (three
    Newton steps from zero, ``tol=0``)."""
    import jax

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    rng = np.random.default_rng(65)
    x = rng.standard_normal((n, d), dtype=np.float32)
    w_true = rng.standard_normal((d,), dtype=np.float32) / np.sqrt(d)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-(x @ w_true)))).astype(np.float32)
    mask = np.ones((n,), np.float32)
    args = (jax.device_put(x, row_sharding(mesh)),
            jax.device_put(y, row_sharding(mesh, 1)),
            jax.device_put(mask, row_sharding(mesh, 1)))
    cd, ad = config.get("compute_dtype"), config.get("accum_dtype")
    gate = lg._pallas_newton_applicable((n, d), cd, ad)
    w, b, _, loss = jax.device_get(
        lg._newton_fn(mesh, 1e-2, True, 3, 0.0, ad)(*args))
    tw, tb, _, tloss = jax.device_get(lg._newton_fn_cached(
        mesh, 1e-2, True, 3, 0.0, "float32", "float32", False)(*args))
    errs = {"w": float(np.linalg.norm(w - tw) / np.linalg.norm(tw)),
            "b": abs(float(b) - float(tb)),
            "loss": abs(float(loss) - float(tloss)) / abs(float(tloss))}
    # bf16 x and w put a relative noise floor of a few 2^-9 under every
    # Newton step (models/logistic_regression.py stops at 2^-8·‖w‖ for
    # that reason); three steps stay well inside 2^-5.
    return gate, ["_newton_stats_kernel"], errs, 2.0**-5


def _case_newton_fold(mesh, d: int, n: int):
    """newton_fold_pallas ← models.logistic_regression.fit_logistic_stream:
    a small streaming fit (two float32 batches, three Newton passes,
    ``tol=0``) at the benchmark's width, off the lane grid, against the
    same fit with ``use_pallas`` off; every fold of it counts under
    ``srml_logreg_fold_path_total{path=fused}``."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.utils import metrics

    rng = np.random.default_rng(69)
    x = rng.standard_normal((2 * n, d), dtype=np.float32)
    w_true = rng.standard_normal((d,), dtype=np.float32) / np.sqrt(d)
    y = (rng.uniform(size=2 * n) < 1.0 / (1.0 + np.exp(-(x @ w_true)))).astype(np.float32)
    passes = 3

    def fit():
        return lg.fit_logistic_stream(
            lambda: iter([(x[:n], y[:n]), (x[n:], y[n:])]), d, reg=1e-2,
            max_iter=passes, tol=0.0, mesh=mesh)

    counter = metrics.counter("srml_logreg_fold_path_total")
    before = counter.value(path="fused")
    got = fit()
    folds = counter.value(path="fused") - before
    with config.option("use_pallas", False):
        want = fit()
    # the fold took the kernel iff the gate said it would
    gate = (lg._fused_newton_fold_applicable((n, d), np.float32, config.get("accum_dtype"))
            and folds == 2 * passes)
    errs = {"w": float(np.linalg.norm(got.coefficients - want.coefficients)
                       / np.linalg.norm(want.coefficients)),
            "b": abs(float(got.intercept) - float(want.intercept)),
            "loss": abs(got.loss - want.loss) / abs(want.loss),
            "rows": abs(got.n_rows - want.n_rows)}
    # One arithmetic in two orders of addition: float32 gradient and loss,
    # a bfloat16 Hessian product on both sides.
    return gate, ["_newton_fold_kernel"], errs, TOL_F32


def _case_softmax(mesh, d: int, n_classes: int, n: int):
    """softmax_curvature_pallas ← models.logistic_regression.
    _stream_softmax_stats_fn (one donated streaming update)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops.pallas_kernels import SOFTMAX_CURV_BLOCK_N
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding

    rng = np.random.default_rng(66)
    x = rng.standard_normal((n, d), dtype=np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[n - 50:] = 0.0
    W = jnp.asarray(rng.standard_normal((d, n_classes), dtype=np.float32)
                    / np.sqrt(d))
    b = jnp.zeros((n_classes,), jnp.float32)
    args = (W, b, jax.device_put(x, row_sharding(mesh)),
            jax.device_put(y, row_sharding(mesh, 1)),
            jax.device_put(mask, row_sharding(mesh, 1)))
    ad = config.get("accum_dtype")
    gate = (gram_ops._pallas_backend_ok() and ad == "float32"
            and n % SOFTMAX_CURV_BLOCK_N == 0 and d % 128 == 0)
    got = jax.device_get(lg._stream_softmax_stats_fn(mesh, n_classes, ad)(
        lg.stream_softmax_zero_state(d, n_classes, ad), *args))
    twin = lg._stream_softmax_stats_cached(
        mesh, n_classes, "float32", "float32", False)
    want = jax.device_get(twin(
        lg.stream_softmax_zero_state(d, n_classes, "float32"), *args))
    names = ("gw", "gb", "hw", "hwb", "hbb", "loss", "n")
    errs = {name: rel_to_max(g, w) for name, g, w in zip(names, got, want)}
    # The curvature blocks are the MM preconditioner: the twin runs them
    # at DEFAULT precision (one bf16 pass) too, so both sides carry a
    # 2^-8 product error.
    return gate, ["_softmax_curv_kernel"], errs, 2.0**-6


def _case_softmax_fold(mesh, d: int, n_classes: int, n: int):
    """softmax_fold_pallas ← models.logistic_regression.
    _stream_softmax_stats_group_fn: the daemon's cached multinomial pass (two
    float32 batches, one dispatch) at the benchmark's width, off the lane
    grid, against the float32 XLA body; the dispatch counts under
    ``srml_logreg_softmax_fold_path_total{path=fused}``."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.parallel.sharding import row_sharding
    from spark_rapids_ml_tpu.utils import metrics

    rng = np.random.default_rng(70)
    W = jnp.asarray(rng.standard_normal((d, n_classes), dtype=np.float32) / np.sqrt(d))
    b = jnp.asarray(0.2 * rng.standard_normal((n_classes,), dtype=np.float32))
    xs, ys, ms = [], [], []
    for part in range(2):
        mask = (rng.uniform(size=n) < 0.9).astype(np.float32)  # holes, not a prefix
        xs.append(jax.device_put(rng.standard_normal((n, d), dtype=np.float32),
                                 row_sharding(mesh)))
        ys.append(jax.device_put(rng.integers(0, n_classes, size=n).astype(np.float32),
                                 row_sharding(mesh, 1)))
        ms.append(jax.device_put(mask, row_sharding(mesh, 1)))
    ad = config.get("accum_dtype")
    counter = metrics.counter("srml_logreg_softmax_fold_path_total")
    before = counter.value(path="fused")
    got = jax.device_get(lg._stream_softmax_stats_group_fn(mesh, n_classes, ad)(
        lg.stream_softmax_zero_state(d, n_classes, ad), W, b,
        tuple(xs), tuple(ys), tuple(ms)))
    folds = counter.value(path="fused") - before
    twin = lg._stream_softmax_stats_group_cached(
        mesh, n_classes, "float32", "float32", False)
    want = jax.device_get(twin(
        lg.stream_softmax_zero_state(d, n_classes, "float32"), W, b,
        tuple(xs), tuple(ys), tuple(ms)))
    # the fold took the kernel iff the gate said it would
    gate = (lg._fused_softmax_fold_applicable(
        (n, d), np.float32, ad, n_classes) and folds == 1)
    names = ("gw", "gb", "hw", "hwb", "hbb", "loss", "n")
    errs = {name: rel_to_max(g, w) for name, g, w in zip(names, got, want)}
    # One arithmetic in two orders of addition: float32 logits, gradient,
    # loss and borders; the curvature a bfloat16 product on both sides.
    # (Where the gate says no, the XLA body under a bfloat16 compute dtype
    # weights the rows in bfloat16 before the border's sum.)
    return gate, ["_softmax_fold_kernel"], errs, {
        **dict.fromkeys(names, TOL_F32), "hw": TOL_BF16,
        "hwb": TOL_F32 if folds else TOL_BF16}


def _neighbor_errors(db: np.ndarray, queries: np.ndarray, source: np.ndarray,
                     d2: np.ndarray, ids: np.ndarray, k: int) -> Dict[str, float]:
    """Neighbour answers against exact float64 distances: the planted
    nearest neighbour comes first, returned distances are the true ones,
    and every returned id is within the noise of the exact k-th distance."""
    check(ids.shape == (queries.shape[0], k), f"ids shape {ids.shape}")
    check(bool((ids >= 0).all()), "missing neighbours (-1 ids)")
    q64, db64 = queries.astype(np.float64), db.astype(np.float64)
    exact = ((q64 ** 2).sum(1)[:, None] + (db64 ** 2).sum(1)[None, :]
             - 2.0 * q64 @ db64.T)
    kth = np.partition(exact, k - 1, axis=1)[:, k - 1]
    picked = np.take_along_axis(exact, ids, axis=1)
    scale = (q64 ** 2).sum(1)[:, None] + (db64 ** 2).sum(1)[ids]
    return {
        "planted_top1_miss": float(np.mean(ids[:, 0] != source)),
        "distance": float(np.max(np.abs(d2 - picked) / scale)),
        "beyond_kth": float(np.max((picked - kth[:, None]) / scale)),
    }


def _case_exact_knn(mesh, d: int, k: int, n: int, n_query: int):
    """dist_topk_pallas ← NearestNeighborsModel.kneighbors."""
    import spark_rapids_ml_tpu as srml
    from spark_rapids_ml_tpu.models import knn
    from spark_rapids_ml_tpu.ops.distances import fused_topk_fits

    rng = np.random.default_rng(67)
    db = rng.standard_normal((n, d), dtype=np.float32)
    source = rng.choice(n, size=n_query, replace=False)
    queries = db[source] + 0.01 * rng.standard_normal((n_query, d), dtype=np.float32)
    gate = knn._exact_fused_enabled() and fused_topk_fits(n_query, n, d, k)
    model = srml.NearestNeighbors(mesh=mesh).setK(k).setMetric(
        "sqeuclidean").fit({"features": db})
    d2, ids = model.kneighbors(queries)
    errs = _neighbor_errors(db, queries, source, d2, ids, k)
    # ‖q‖² + ‖r‖² − 2q·r with bf16 operands: the product term carries
    # 2^-8·2‖q‖‖r‖ ≤ 2^-8·(‖q‖² + ‖r‖²).
    return gate, ["dist_topk"], errs, TOL_BF16


def _case_ivf(mesh, d: int, k: int, n: int, nlist: int, nprobe: int,
              n_query: int):
    """ivf_scan_select_pallas + probe_select_pallas ←
    ApproximateNearestNeighborsModel.kneighbors (``ann_fused_scan=auto``;
    the index build also runs the quantizer's Lloyd kernel and the
    spill-candidate dist_topk)."""
    import spark_rapids_ml_tpu as srml
    from spark_rapids_ml_tpu import config

    rng = np.random.default_rng(68)
    db, _ = _clustered(rng, n, d, nlist)
    source = rng.choice(n, size=n_query, replace=False)
    queries = db[source] + 0.01 * rng.standard_normal((n_query, d), dtype=np.float32)
    gate = (str(config.get("ann_fused_scan")) == "auto"
            and config.backend_is_tpu())
    model = (srml.ApproximateNearestNeighbors(mesh=mesh).setK(k)
             .setNlist(nlist).setNprobe(nprobe).setMetric("sqeuclidean")
             .fit({"features": db}))
    d2, ids = model.kneighbors(queries)
    errs = _neighbor_errors(db, queries, source, d2, ids, k)
    # Probing is approximate by design: a query may miss the list a true
    # neighbour sits in, so "beyond the exact k-th" is judged only through
    # the planted neighbour and the (exact, reranked) distances.
    errs.pop("beyond_kth")
    # Capacity-bounded lists and buckets may drop a (query, list) pair:
    # up to 2% of planted neighbours may be missed; a broken kernel
    # misses nearly all.
    return gate, ["ivf_scan_select", "ivf_probe_select"], errs, {
        "planted_top1_miss": 0.02, "distance": TOL_BF16}


def kernel_cases(mesh_one, mesh_all) -> List[Tuple[str, Callable[[], tuple]]]:
    """The twelve cases at each estimator's BASELINE.json width (the
    streaming Newton folds, binary and multinomial, at the benchmarks',
    d = 3000)."""
    return [
        ("gram_pallas", lambda: _case_gram(mesh_one, 2048, 8192)),
        ("gram_colsum_pallas[seeded]",
         lambda: _case_gram_colsum(mesh_one, 2048, 8192, seeded=True)),
        ("gram_colsum_pallas[unseeded]",
         lambda: _case_gram_colsum(mesh_all, 2048, 8192, seeded=False)),
        ("lloyd_step_pallas",
         lambda: _case_lloyd(mesh_one, 256, 100, 16384, f32=False)),
        ("assign_min_dist_pallas",
         lambda: _case_lloyd(mesh_one, 256, 100, 16384, f32=True)),
        ("linreg_stats_pallas", lambda: _case_linreg(mesh_one, 1024, 8192)),
        ("newton_stats_pallas", lambda: _case_newton(mesh_one, 1024, 8192)),
        ("newton_fold_pallas", lambda: _case_newton_fold(mesh_one, 3000, 4096)),
        ("softmax_curvature_pallas",
         lambda: _case_softmax(mesh_one, 1024, 32, 4096)),
        ("softmax_fold_pallas", lambda: _case_softmax_fold(mesh_one, 3000, 3, 4096)),
        ("dist_topk_pallas",
         lambda: _case_exact_knn(mesh_one, 768, 10, 16384, 256)),
        ("ivf_scan_select_pallas+probe_select_pallas",
         lambda: _case_ivf(mesh_one, 768, 10, 32768, 128, 16, 1024)),
    ]


def stage_kernels(cases: List[Tuple[str, Callable[[], tuple]]],
                  require_mosaic: bool = True) -> Dict[str, Any]:
    """Run every case; a verdict per kernel, and the stage fails if any
    case failed (all are attempted so one run names every refusal).
    ``require_mosaic=False`` is for checking this harness itself where no
    Mosaic compiler exists: the gates then say no and the XLA twin is
    compared with the reference; ``main`` never passes it."""
    verdicts: Dict[str, Any] = {}
    for name, case in cases:
        t0 = time.perf_counter()
        try:
            with PallasSpy() as spy:
                gate, kernels, errs, tol = case()
            if require_mosaic:
                check(gate, "its gate said no at the BASELINE shape")
                traced = {n for n, _ in spy.calls}
                check(set(kernels) <= traced,
                      f"kernel(s) {sorted(set(kernels) - traced)} were not "
                      f"traced into the program (saw {sorted(traced)})")
                check(not any(interp for _, interp in spy.calls),
                      "a pallas_call ran in interpret mode")
            tols = tol if isinstance(tol, dict) else dict.fromkeys(errs, tol)
            bad = {key: val for key, val in errs.items()
                   if not val <= tols[key]}
            check(not bad, f"off the plain-jnp path: {bad} (tolerances {tols})")
            verdicts[name] = {"ok": True, "errors": errs, "tolerances": tols,
                              "seconds": round(time.perf_counter() - t0, 2)}
            say(f"  {name}: compiled, agrees  " + "  ".join(
                f"{key}={val:.2e}(<={tols[key]:.1e})"
                for key, val in errs.items())
                + f"  ({verdicts[name]['seconds']} s)")
        except Exception as e:  # noqa: BLE001 - one verdict per kernel, then fail
            message = f"{type(e).__name__}: {e}"
            verdicts[name] = {"ok": False, "error": message[:4000]}
            say(f"  {name}: FAILED — {message[:4000]}")
    failed = [name for name, v in verdicts.items() if not v["ok"]]
    check(not failed, f"kernel case(s) failed: {failed}")
    return verdicts


# ---------------------------------------------------------------------------
# Stages 1–5 as one call (what tier-1 runs small on the CPU mesh)
# ---------------------------------------------------------------------------


#: The forest stage: `perf/configs/rf_reg_d3000.json` with fewer trees over
#: two of its six batches (every width as stated: a fit and its reference
#: then take seconds, and every program is the deployment's shape but for
#: the tree axis).
FOREST_TREES = 4
FOREST_PARAMS = {"batch_rows": 65536, "cached_batches": 2, "partitions": 2,
                 "compare_trees": 2}


def stage_forest(sizes: Optional[Dict[str, int]] = None,
                 params: Optional[Dict[str, int]] = None,
                 seed: int = 3000) -> Dict[str, Any]:
    """One forest-regressor fit through `serve/daemon.py` `_Job("rf", ...)`
    with its pass cached — driven as the benchmark's generator drives it —
    and the comparison a benchmark run makes of it."""
    from perf.harness import layout
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.utils import metrics

    bench = layout.load_benchmark(REPO)
    cfg = {**layout.load_config(REPO, bench, "rf_reg_d3000"),
           "num_trees": FOREST_TREES, **(sizes or {})}
    generator = layout.load_module(REPO, "generators", "levels_cached")
    agree = layout.load_module(REPO, "harness", "agree_rf")
    reference = layout.load_module(REPO, "reference", "rf")

    def counter(name: str, **labels: str) -> float:
        return sum(s["value"] for s in metrics.snapshot().get(name, {}).get("samples", [])
                   if all(s["labels"].get(k) == v for k, v in labels.items()))

    def cached_passes() -> float:
        return counter("srml_daemon_passes_total", source="cache")

    def frontier_nodes() -> Dict[str, float]:
        return {how: counter("srml_forest_frontier_nodes_total", how=how)
                for how in ("folded", "derived")}

    def fold_paths() -> Dict[str, float]:
        return {path: counter("srml_forest_fold_path_total", path=path)
                for path in ("fused", "xla")}

    before, nodes_before, paths_before = cached_passes(), frontier_nodes(), fold_paths()
    forest = generator.CachedForest(REPO, cfg, params or FOREST_PARAMS, seed, 1, say)
    passes, captured = forest.captured_fit()
    check(cached_passes() - before == len(passes) == cfg["max_depth"],
          f"{cached_passes() - before} of the fit's {len(passes)} level passes came "
          "from the pass cache")
    nodes = {how: n - nodes_before[how] for how, n in frontier_nodes().items()}
    check(nodes["derived"] > 0,
          f"the fit derived no node's histogram from its parent's ({nodes}): every level "
          "after the first should fold one child of a split and subtract for the other")
    # every fold of the fit, before the stage folds a level through the XLA body itself
    paths = {path: n - paths_before[path] for path, n in fold_paths().items()}
    fused_share = round(100.0 * paths["fused"] / max(sum(paths.values()), 1.0), 2)
    say(f"forest: srml_forest_fold_path_total {paths}: {fused_share}% of the fold's "
        "dispatches made their bin one-hot in VMEM (hist_onehot_matmul_pallas)")
    if config.backend_is_tpu():
        check(paths["fused"] > 0 and paths["xla"] == 0,
              f"not every dispatch of histogram.update_group took the fused body ({paths}): "
              "on the chip the `auto` profile's int8 operands at 128 bins and 65,536-row "
              "batches pass `_fused_hist_fold_applicable`")
    both_ways = forest_level_both_ways(forest.job, captured["levels"], depth=2)
    check(both_ways["derived_nodes"] > 0 and both_ways["count_cells_differ"] == 0,
          "the depth-2 level folded whole and folded by halves (one child of a pair "
          f"contracted, its sibling the parent less it) differ: {both_ways}")
    check(both_ways["xla_count_cells_differ"] == 0,
          "the depth-2 level folded through the kernel and through the XLA body "
          f"(`use_pallas` off) differ in the count channel: {both_ways}")
    compared = forest.compared(captured, [], agree, reference, say)
    problems = agree.problems(compared)
    check(not problems, f"the forest fit disagrees with perf/reference/rf.py: {problems}")
    return {"compared": compared, "both_ways": both_ways, "frontier_nodes": nodes,
            "fold_paths": paths, "fused_share": fused_share,
            "derived_share": round(100.0 * nodes["derived"] / sum(nodes.values()), 2),
            "level_seconds": [round(p["end"] - p["start"], 3) for p in passes]}


def forest_level_both_ways(job, levels, depth: int) -> Dict[str, Any]:
    """One level of the fit the job has just made, folded both ways from
    its cached batches under the fit's own tables: the whole frontier
    contracted, and (ISSUE 37) a state seeded from the complete histogram
    one depth up with one child of every pair contracted. The count channel
    is whole numbers either way, so it must agree cell for cell — on the
    chip, where a cast XLA elides has been seen only there (PR 36). And
    (ISSUE 39) the whole frontier once more with `use_pallas` off: the XLA
    body — `jax.nn.one_hot` and XLA's product in 16,384-row chunks — against
    the body the fit took (on the chip the kernel that makes the one-hot in
    VMEM, a 65,536-row chunk): the same counts cell for cell, the label
    sums to the digits' rounding."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import random_forest as forest
    from spark_rapids_ml_tpu.ops import histogram

    algo = job.algorithm
    spec, d = algo.spec, algo.n_cols
    xs, ms, ys, ks = zip(*job._cache.batches)

    def fold(tables, state, signs=None):
        return forest.accumulate_histogram(
            state, tables, xs, ys, ms, ks, spec, job.mesh, n_valid=job._cache.rows,
            signs=signs)

    def zeros(at):
        return histogram.zero_hist(
            spec.num_trees, at, d, spec.max_bins, spec.n_stats, algo.accum)

    parent = fold(levels[depth - 1], zeros(depth - 1))
    whole = fold(levels[depth], zeros(depth))
    state, signs = forest.open_pass(levels[depth], spec, d, parent=parent)
    halved = fold(levels[depth], state, signs)
    with config.option("use_pallas", False):
        xla = fold(levels[depth], zeros(depth))

    def label_rel(other):
        return [
            float(jnp.linalg.norm(other[..., s] - whole[..., s])
                  / jnp.maximum(jnp.linalg.norm(whole[..., s]), 1e-30))
            for s in range(1, spec.n_stats)]

    return {"depth": depth, "derived_nodes": int((signs < 0).sum()),
            "folded_nodes": int((signs > 0).sum()),
            "count_cells_differ": int(jnp.sum(halved[..., 0] != whole[..., 0])),
            "count_total": float(jnp.sum(whole[..., 0])),
            "label_sums_rel": label_rel(halved),
            "xla_count_cells_differ": int(jnp.sum(xla[..., 0] != whole[..., 0])),
            "xla_label_sums_rel": label_rel(xla)}


def run_main_path(
    watch: CompileWatch,
    record: Callable[[str, Callable[[], Any]], Any],
    d: int = D,
    k: int = K,
    partition_rows: Sequence[int] = PARTITION_ROWS,
    feed_rows: int = FEED_ROWS,
    ladder_rows: Sequence[int] = LADDER_ROWS,
    oversize_rows: Sequence[int] = OVERSIZE_ROWS,
    seed: int = 2048,
) -> Dict[str, Any]:
    """Stages 1–5. ``record(name, fn)`` runs one stage and books it."""
    from spark_rapids_ml_tpu.serve import DataPlaneDaemon

    x = planted_data(seed, sum(partition_rows), d, k)
    parts = np.split(x, np.cumsum(partition_rows)[:-1])
    say(f"data: {x.shape[0]} rows x {d} float32 in {len(parts)} partitions "
        f"{tuple(partition_rows)}, planted top-{k} spectrum, seed {seed}")
    with DataPlaneDaemon() as daemon:
        fitted = record("fit_daemon", lambda: stage_fit_daemon(
            daemon, parts, k, feed_rows))
        served = record("serve", lambda: stage_serve(
            daemon, fitted["fit"], x, watch, ladder_rows, oversize_rows))
    library = record("library", lambda: stage_library(
        x, k, transform_rows=max(ladder_rows)))
    agreement = record("agreement", lambda: stage_agreement(
        x, k, fitted["fit"], served, library, chunk_rows=max(partition_rows)))
    ledger = record("no_fallback", lambda: stage_no_fallback(d, feed_rows))
    served.pop("responses")
    fitted.pop("fit")
    return {"fit_daemon": fitted, "serve": served, "agreement": agreement,
            "no_fallback": ledger}


def result_line(device: Dict[str, Any]) -> str:
    """The last line of stdout, which the driver parses: exactly ``ok`` and
    ``device`` = platform, kind, count as ``stage_device`` read them from
    JAX. Everything else belongs in the summary line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def main() -> int:
    t_start = time.perf_counter()
    stages: Dict[str, Dict[str, Any]] = {}

    def record(name: str, fn: Callable[[], Any]) -> Any:
        say(f"[{name}]")
        t0 = time.perf_counter()
        result = fn()
        stages[name] = {"ok": True,
                        "seconds": round(time.perf_counter() - t0, 2)}
        say(f"[{name}] ok in {stages[name]['seconds']} s")
        return result

    try:
        device = record("device", stage_device)
        record("native_build", build_native_bridge)

        import jax

        from spark_rapids_ml_tpu import config
        from spark_rapids_ml_tpu.bridge import native
        from spark_rapids_ml_tpu.parallel.mesh import default_mesh, make_mesh
        from spark_rapids_ml_tpu.utils import metrics
        from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

        cache_dir = ensure_compile_cache()
        say(f"compile cache: {cache_dir}")
        watch = CompileWatch()
        for key in ("compute_dtype", "use_pallas", "ann_fused_scan"):
            check(config.get_raw(key) == "auto",
                  f"config {key!r} is {config.get_raw(key)!r}, not the shipped "
                  f"'auto' — unset SRML_TPU_{key.upper()}")
        native_on = native.get_lib() is not None
        say(f"native columnar bridge: {'on' if native_on else 'off (NumPy path)'}")
        check(native_on, "the library built above did not load")

        detail = run_main_path(watch, record)
        detail["kernels"] = record("kernels", lambda: stage_kernels(
            kernel_cases(make_mesh(devices=jax.devices()[:1]), default_mesh())))
        detail["forest"] = record("forest", stage_forest)
    except Exception as e:  # noqa: BLE001 - report, then exit non-zero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1

    hits = sum(
        sample["value"] for sample in metrics.snapshot().get(
            "srml_xla_persistent_cache_hits_total", {}).get("samples", [])
    )
    summary = {
        "ok": True,
        "device": device,
        "jax": jax.__version__,
        "stages": stages,
        "seconds": round(time.perf_counter() - t_start, 2),
        "compile_events": watch.count,  # builds and cache loads alike
        "compile_seconds": round(watch.seconds, 2),
        "persistent_cache_hits": int(hits),
        "compile_cache_dir": cache_dir,
        "native_bridge": native_on,
        "detail": detail,
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
