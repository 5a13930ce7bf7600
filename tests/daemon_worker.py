"""Standalone data-plane daemon process for multi-host tests.

Spawned by tests/test_spark_multidaemon.py: each instance is one OS
process owning "its host's" daemon (the deployment unit of
spark/daemon_session.py), so the 2-daemon tests exercise real process
isolation — separate JAX runtimes, separate device state, TCP between
everything — not two registries in one interpreter.

Prints ``READY <port>`` on stdout once listening; serves until stdin
closes (the parent's handle drop is the shutdown signal, so an aborted
test never leaks the process).

``argv[1]`` (optional) pins the port — the chaos suite restarts a killed
daemon AT THE SAME ADDRESS, the way a supervised production daemon comes
back. ``argv[2]`` (optional) is a durable state directory: the recovery
suite SIGKILLs this worker and restarts a twin pointing at the same
directory, which must resurrect the jobs (serve/daemon.py crash
recovery). A ``SRML_FAULT_PLAN`` env spec is honored by the in-process
fault registry (utils/faults.py import-time activation), so a
crash-on-Nth-op rule makes this worker die the way a real daemon process
dies: abruptly, mid-traffic, exit code 17.
"""

import sys


def main() -> None:
    import jax

    # Tests run on the CPU: this worker must use the same backend as the
    # test session, whatever the host offers.
    jax.config.update("jax_platforms", "cpu")

    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    from spark_rapids_ml_tpu.serve.daemon import DataPlaneDaemon

    port = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    state_dir = sys.argv[2] if len(sys.argv) > 2 else None
    daemon = DataPlaneDaemon(
        host="127.0.0.1", port=port, ttl=600.0, state_dir=state_dir
    ).start()
    print(f"READY {daemon.address[1]}", flush=True)
    sys.stdin.read()  # block until the parent closes our stdin
    daemon.stop()


if __name__ == "__main__":
    main()
