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
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def ensure_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect. Call before
    the process's first compile — JAX binds its cache at first use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
