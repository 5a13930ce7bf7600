"""Test harness: virtual 8-device CPU mesh + float64 parity mode.

This is the "fake backend" testing capability the reference lacks
(SURVEY.md §4): multi-device sharding tests with no hardware, via
``--xla_force_host_platform_device_count``. Environment must be set before
jax import, hence the top-of-conftest placement.

float64 is enabled so differential tests against NumPy/sklearn oracles can
assert at the reference's absTol 1e-5 (PCASuite.scala:80-87); a separate
test exercises the float32 TPU-native mode with wider tolerance.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU, whatever the host offers
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "True")
# The persistent compilation cache is shared with every spawned worker
# process (daemon workers, multiproc ranks, forkserver tasks — each places
# it by the same rule, utils/compile_cache.py): the 2-OS-process tests
# compile identical programs in both workers, and a shared cache turns the
# twin's compile into a disk hit. Only programs that take ≥ 0.5 s to
# compile are kept, so the small programs whose compile EVENTS tests count
# always compile.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
# Package dtype defaults for parity testing (overridden per-test via
# config.option for float32-mode tests).
os.environ.setdefault("SRML_TPU_ACCUM_DTYPE", "float64")
os.environ.setdefault("SRML_TPU_COMPUTE_DTYPE", "float64")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from spark_rapids_ml_tpu.utils.compile_cache import (  # noqa: E402
    ensure_compile_cache,
)

ensure_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from spark_rapids_ml_tpu.parallel.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    return make_mesh(data=8, model=1)


@pytest.fixture(scope="session")
def mesh4x2(devices):
    return make_mesh(data=4, model=2)


@pytest.fixture(scope="session")
def mesh1(devices):
    return make_mesh(data=1, model=1, devices=devices[:1])


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def gram_fused_on_cpu(monkeypatch):
    """Steer `gram.streaming_update` onto its one-read kernel here: the gate
    is told the backend is a TPU and `gram_colsum_pallas` runs in interpret
    mode. The program has no option for this (ROADMAP D5): the test does
    it. Build the fold with `_streaming_update_cached(mesh, "bfloat16",
    "float32", True)` — the `auto` profile on the chip."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.ops import pallas_kernels as pk

    calls = []
    kernel = pk.gram_colsum_pallas

    def spy(x, n_valid, **kw):
        calls.append({"seeded": kw.get("state") is not None, "x_dtype": x.dtype,
                      "compute_dtype": kw.get("compute_dtype")})
        return kernel(x, n_valid, interpret=True, **kw)

    monkeypatch.setattr(config, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pk, "gram_colsum_pallas", spy)
    gram_ops._streaming_update_cached.cache_clear()
    yield calls
    gram_ops._streaming_update_cached.cache_clear()


# ---------------------------------------------------------------------------
# Shared subprocess daemon workers (VERDICT carry #7: test wall clock).
# The recovery/chaos/fleet/elastic flagships each need real OS-process
# daemons (tests/daemon_worker.py), and each spawn pays a ~4 s jax
# import. The helper centralizes the spawn env (f64 parity profile —
# bitwise contracts against the parent session's oracles need it) and
# the module-scoped pair fixture amortizes two long-lived workers across
# a module's flagships for the roles that are never killed: fault-free
# oracles and surviving peers. Tests that kill or restart a daemon still
# spawn their own victims.
# ---------------------------------------------------------------------------

import subprocess  # noqa: E402
import sys  # noqa: E402


def _launch_daemon_worker(port=0, state_dir=None, fault_spec=None,
                          extra_env=None):
    """Start one tests/daemon_worker.py subprocess WITHOUT waiting for
    its READY line (callers that spawn several overlap the ~4 s jax
    imports by deferring the reads). The ONE place the worker env is
    built: SRML_* stripped, then the parent session's f64 parity profile
    pinned — worker-side folds must be bitwise-comparable with
    in-session oracles, and a drift between two spawn sites would break
    every worker-vs-oracle contract silently. ``extra_env`` overlays
    LAST (telemetry tests configure SRML_SLO_*/SRML_INCIDENT_* knobs on
    the worker; the parity profile still wins unless overridden
    explicitly)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if not k.startswith("SRML_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "True"
    env["SRML_TPU_ACCUM_DTYPE"] = "float64"
    env["SRML_TPU_COMPUTE_DTYPE"] = "float64"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p
    )
    if fault_spec:
        env["SRML_FAULT_PLAN"] = fault_spec
    if extra_env:
        env.update({str(k): str(v) for k, v in extra_env.items()})
    argv = [
        sys.executable,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "daemon_worker.py"),
        str(port),
    ]
    if state_dir is not None:
        argv.append(str(state_dir))
    return subprocess.Popen(
        argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        cwd=repo_root, env=env, text=True,
    )


def _read_ready(proc) -> int:
    line = proc.stdout.readline().strip()
    assert line.startswith("READY "), line
    return int(line.split()[1])


def spawn_daemon_worker(port=0, state_dir=None, fault_spec=None,
                        extra_env=None):
    """One worker subprocess (READY <port> contract, stdin-close
    shutdown). Returns (proc, port)."""
    proc = _launch_daemon_worker(port, state_dir, fault_spec, extra_env)
    return proc, _read_ready(proc)


def stop_daemon_worker(proc) -> None:
    """Polite shutdown (stdin close); kill as the fallback."""
    try:
        if proc.poll() is None:
            proc.stdin.close()
            proc.wait(timeout=15)
    except Exception:
        proc.kill()


@pytest.fixture(scope="module")
def worker_daemon_pair():
    """Two long-lived subprocess daemons shared across a module's
    flagships for never-killed roles (oracle fits, surviving peers).
    Both spawn before either READY line is read so the jax imports
    overlap. Use UNIQUE job/model names per test — the daemons live for
    the whole module."""
    procs = [_launch_daemon_worker() for _ in range(2)]
    try:
        yield [(proc, _read_ready(proc)) for proc in procs]
    finally:
        for proc in procs:
            stop_daemon_worker(proc)
