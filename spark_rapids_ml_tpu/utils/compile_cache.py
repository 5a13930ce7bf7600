"""Where the persistent XLA compilation cache lives — one rule, one place.

The directory is part of the cache key, so a cache that moves never
hits. Hence: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set in code; otherwise the cache is
``<checkout>/.jax_cache`` — a fixed path, never one built from a temp
name, a pid or the time. Entry points that compile (``chip_smoke.py``,
``bench.py``, ``benchmarks/*``, ``examples/*``, ``tests/conftest.py`` and
the test workers) call :func:`ensure_compile_cache` before their first
compile; the package itself never places a cache at import. Disk hits are
counted by ``srml_xla_persistent_cache_hits_total`` (utils/xprof.py).

What is kept: JAX keeps programs that took a second or more to compile.
A Mosaic-kernel program compiles faster than the XLA program it replaces
(the streaming KMeans fold: 0.6-0.8 s against 3-4 s on a v5e's host,
PERF.md §6, PR 29) and would then be compiled again by every process —
and once per argument sharding it meets, four times for the grouped fold
— where the slower program was loaded in 0.07 s. So the floor is
:data:`MIN_COMPILE_SECS` unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
says otherwise.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


#: Compile seconds from which a program is kept: a few times the ~0.07 s a
#: load from the cache costs, well under the 0.6 s of the fastest program
#: that matters.
MIN_COMPILE_SECS = 0.25


def ensure_compile_cache() -> str:
    """Apply the rules above; returns the directory in effect. Call before
    the process's first compile — JAX binds its cache at first use."""
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECS
        )
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
