"""What a generator hands to the metric readers, and the counters taken at
both ends of the measured window.

From the program the benchmark reads only what is sound today: the metrics
registry (`utils/metrics.py`: counts, bytes, host seconds by op), the jit
ledger (`utils/xprof.py`: calls and compiles) and JAX's own compile event.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from perf.harness import layout, stats
from perf.harness.device import WindowMemory


def process_start_wall() -> float:
    """When this process started, on the wall clock, from /proc: set-up is
    counted from here, interpreter start and imports included."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


class CompileWatch:
    """Counts every program the process builds or loads from the persistent
    cache (JAX fires the same event for both), ledgered or not."""

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


def take_counters(watch: CompileWatch) -> Dict[str, Any]:
    from spark_rapids_ml_tpu.utils import metrics, xprof

    ledger = xprof.snapshot()
    return {
        "at": time.monotonic(),
        "metrics": metrics.snapshot(),
        "ledger": {name: {"calls": a["calls"], "compiles": a["compiles"],
                          "compile_s": a["compile_s"]}
                   for name, a in ledger.items()},
        "compile_events": watch.count,
        "compile_seconds": watch.seconds,
    }


class Observation:
    """One run of one cell, as the readers see it. Times are seconds on
    `time.monotonic()` (one clock for every process of the machine)."""

    def __init__(self, config: Dict[str, Any], params: Dict[str, Any],
                 seconds: float, device: Dict[str, Any],
                 root: str = layout.REPO_ROOT) -> None:
        #: the tree the run is of: where a reader finds files by name
        #: (`perf/costs/<algo>.py`)
        self.root = root
        self.config = config
        self.params = params
        self.seconds = float(seconds)
        self.device = device
        self.setup_s: Optional[float] = None
        self.window: Optional[Tuple[float, float]] = None  # start, deadline
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}
        #: fit cells: {"fit", "pass", "rows", "start", "end"} per pass and
        #: {"fit", "finalize_s", "rows"} per finished fit
        self.passes: List[Dict[str, Any]] = []
        self.fits: List[Dict[str, Any]] = []
        #: (name, start, end) of the driver's own spans (Context.span)
        self.spans: List[Tuple[str, float, float]] = []
        self.fold_rows_per_chip: Optional[int] = None
        #: peak bytes in use on the fullest chip inside the window
        self.memory_peak_bytes = 0
        self.trace: Optional[Dict[str, Any]] = None
        self.attempted = 0
        self.failed = 0
        self.correct = True
        #: each number the comparison with the reference read, beside its
        #: limit: name → [number, limit]; the result line's last key
        self.compared: Dict[str, List[float]] = {}
        self.notes: Dict[str, Any] = {}

    # -- counters ---------------------------------------------------------

    def counter_delta(self, name: str, **labels: str) -> float:
        return stats.counter_delta(self.before["metrics"], self.after["metrics"],
                                   name, **labels)

    def hist_delta(self, name: str, **labels: str) -> Tuple[float, int]:
        return stats.hist_delta(self.before["metrics"], self.after["metrics"],
                                name, **labels)

    def hist_mean_ms(self, name: str, **labels: str) -> Optional[float]:
        return stats.hist_mean_ms(self.before["metrics"], self.after["metrics"],
                                  name, **labels)

    def compiles_in_window(self) -> int:
        """Programs built or loaded between the two counter readings, by
        JAX's own event."""
        return int(self.after["compile_events"] - self.before["compile_events"])

    def ledger_compiles_in_window(self) -> Dict[str, int]:
        """Compiles the jit ledger booked inside the window, by entry."""
        a, b = self.after["ledger"], self.before["ledger"]
        grew = {k: int(v["compiles"] - b.get(k, {}).get("compiles", 0))
                for k, v in a.items()}
        return {k: n for k, n in grew.items() if n}


class Context:
    """What the runner gives a generator."""

    def __init__(self, *, root: str, cell: Dict[str, Any], config: Dict[str, Any],
                 params: Dict[str, Any], seed: int, seconds: float, trace: bool,
                 device: Dict[str, Any], say: Callable[[str], None],
                 process_start: float, runtime_s: float,
                 out_dir: Optional[str]) -> None:
        self.root = root
        self.cell = cell
        self.config = config
        self.params = params
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.device = device
        self.say = say
        self.out_dir = out_dir
        self._process_start = process_start
        #: seconds the accelerator's runtime took to come up: not in setup_s
        self._runtime_s = float(runtime_s)
        self._memory = WindowMemory()
        self.watch = CompileWatch()
        self.obs = Observation(config, params, seconds, device, root)

    @contextlib.contextmanager
    def span(self, name: str):
        """A span of the driver thread: logged on the benchmark's clock for
        the idle-gap labels (a profiler annotation that opened before the
        trace started is not in the trace, and a pass outlasts the traced
        part of the window), and shown in the profile where it fits."""
        import jax

        start = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.obs.spans.append((name, start, time.monotonic()))

    def stage(self, name: str) -> None:
        """A line saying how far set-up has come."""
        now = time.time() - self._process_start
        self.say(f"  set-up +{now:7.2f} s: {name}")

    def begin_window(self) -> float:
        """End of set-up: read the counters, start the clock. `setup_s` is
        process start → now without the seconds the accelerator's runtime
        took to come up: those depend on what the machine ran just before
        (7.5 to 17 s for the same code, PERF.md §2), on nothing a PR can
        change, and are said on a line of their own."""
        self.obs.before = take_counters(self.watch)
        whole = time.time() - self._process_start
        self.obs.setup_s = whole - self._runtime_s
        self._memory.start()
        start = time.monotonic()
        self.obs.window = (start, start + self.seconds)
        self.say(f"set-up {self.obs.setup_s:.2f} s + {self._runtime_s:.2f} s for the "
                 f"accelerator's runtime to come up = {whole:.2f} s from process "
                 f"start ({self.obs.before['compile_events']} compile events, "
                 f"{self.obs.before['compile_seconds']:.2f} s in them); window "
                 f"of {self.seconds:g} s opens")
        return start

    def end_window(self) -> None:
        self.obs.after = take_counters(self.watch)
        self.obs.memory_peak_bytes = self._memory.stop()
        self.say(f"bytes in use on the fullest chip inside the window: at most "
                 f"{self.obs.memory_peak_bytes}")
