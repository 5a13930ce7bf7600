"""Phase-named tracing spans — the NVTX-range idiom, TPU-native.

The reference wraps its two fit phases in NVTX ranges so they show up in
Nsight (``NvtxRange("compute cov", RED)`` / ``NvtxRange("cuSolver SVD",
BLUE)``, RapidsRowMatrix.scala:62,70, closed in ``finally``). The TPU
equivalent is ``jax.profiler.TraceAnnotation``, which names the span in
xprof/Perfetto traces. ``trace_span`` keeps the same phase-named-span
idiom: every span opens a ``TraceAnnotation`` (while no profile is being
recorded that is one atomic load; while one is, the span is an event on
the ``/host:CPU`` plane, on the clock the device events are on) and
additionally feeds the two always-on observability sinks:

* the process-wide metrics registry — every span's wall-clock lands in
  the ``srml_phase_duration_seconds{phase=...}`` histogram (so bench
  records and the daemon's ``metrics`` op carry per-phase breakdowns);
* the run journal (``utils/journal.py``, env ``SRML_RUN_JOURNAL``) —
  one JSON line per phase with run/span/parent ids.

With no profile recording, the journal unset, and metrics disabled, a
span is a Timer plus three cheap flag checks — safe on hot paths. Config
``tracing`` only adds the per-span debug log line. With the registry on
and the journal off — the production state — a span is an object, the
annotation's atomic load and one histogram observation: no generator is
made, the journal's is entered only while a sink is on (PR 38: a pass
boundary of 6 ms opens five).
"""

from __future__ import annotations

import time
from typing import Optional

import jax.profiler

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.utils import journal
from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.logging import get_logger

_logger = get_logger(__name__)

#: Every trace_span records here: the per-phase latency breakdown all
#: other layers (bench.py, docs/observability.md) read.
PHASE_SECONDS = metrics.histogram(
    "srml_phase_duration_seconds",
    "Wall-clock duration of trace_span phases, by phase name",
)


class Timer:
    """Wall-clock timer with a monotonic clock; used by spans and benches."""

    def __init__(self) -> None:
        self.start = time.perf_counter()
        self.elapsed: Optional[float] = None

    def stop(self) -> float:
        self.elapsed = time.perf_counter() - self.start
        return self.elapsed


class trace_span:
    """Context manager naming a phase in the JAX profiler timeline.

    Usage mirrors the reference's try/finally NvtxRange pattern::

        with trace_span("compute cov"):
            gram = compute_gram(...)

    ``as`` gives the span's :class:`Timer`; its ``elapsed`` is what the
    histogram took, set when the block is left."""

    __slots__ = ("name", "log", "timer", "_annotation", "_journal")

    def __init__(self, name: str, log: bool = False) -> None:
        self.name = name
        self.log = log

    def __enter__(self) -> Timer:
        self.timer = Timer()
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._journal = journal.span(self.name) if journal.active() else None
        if self._journal is not None:
            try:
                self._journal.__enter__()
            except BaseException as exc:  # unwind what was entered, as nested `with`s would
                self._annotation.__exit__(type(exc), exc, exc.__traceback__)
                raise
        return self.timer

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            elapsed = self.timer.stop()
            PHASE_SECONDS.observe(elapsed, phase=self.name)
            if self.log or config.peek("tracing"):
                _logger.debug("phase %s: %.3fs", self.name, elapsed)
        finally:
            if self._journal is not None:
                self._journal.__exit__(exc_type, exc, tb)
            self._annotation.__exit__(exc_type, exc, tb)
