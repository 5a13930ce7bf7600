"""From the profiler's `.xplane.pb` to the numbers the per-layer readers use.

`python3 -m perf.harness.trace <file.xplane.pb>` prints what a trace holds
(planes, lines, the commonest event names): look at one by hand before
writing a reader against it.

What the reduction takes from a trace:

* device planes `/device:TPU:<n>`; in each, the line `XLA Modules` (one
  event per executed program, named `jit_<fn>(<id>)`) and the line
  `XLA Ops` (one event per executed HLO operation);
* busy time of a device = the union of its `XLA Ops` intervals (of its
  modules where a trace has no op line); idle share = 1 − busy ÷ window;
* collective time = the op events whose name says all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all; its exposed part is
  what no other op on that device covers;
Event times are seconds from the start of the profile; the plane
`Task Environment` carries that start on the wall clock, which is how the
spans the benchmark's driver thread logs on its own clock (`fold_loop`,
`finalize`) are laid over the trace to say what the host was doing in an
idle gap. The
two clocks agree to about a millisecond; the gaps that matter are tens of
milliseconds and more.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import sys
import tempfile
import threading
import time
from collections import Counter, defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Span = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


# -- interval arithmetic ----------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of `intervals`."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged: Sequence[Interval]) -> float:
    return float(sum(hi - lo for lo, hi in merged))


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of `a` (merged) that `b` (merged) does not cover."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < hi:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What of [lo, hi] the merged intervals leave uncovered."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


# -- reading ------------------------------------------------------------------


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def program_name(event_name: str) -> str:
    """`jit_update(1234)` → `jit_update`."""
    return _PROGRAM_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """`%fusion.1 = f32[2048,2048]{…} fusion(…)` → `fusion.1`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Dict[str, Any]:
    """{"start_wall_s", "stop_wall_s", "devices": {n: {"modules": [Span],
    "ops": [Span]}}} — times in seconds from the start of the profile."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "start_wall_s": None, "stop_wall_s": None}
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                out["start_wall_s"] = stats["profile_start_time"] * 1e-9
            if "profile_stop_time" in stats:
                out["stop_wall_s"] = stats["profile_stop_time"] * 1e-9
            continue
        match = DEVICE_PLANE.match(plane.name)
        if match:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    lo = ev.start_ns * 1e-9
                    name = op_name(ev.name) if key == "ops" else ev.name
                    dev[key].append((name, lo, lo + ev.duration_ns * 1e-9))
            out["devices"][int(match.group(1))] = dev
    return out


# -- reduction ----------------------------------------------------------------


def _label(mid: float, spans: Sequence[Span]) -> str:
    """What the host was doing at `mid`: the innermost (shortest) driver
    span that covers it."""
    covering = [s for s in spans if s[1] <= mid < s[2]]
    return min(covering, key=lambda s: s[2] - s[1])[0] if covering else "no_span"


def reduce_trace(raw: Dict[str, Any], window: Optional[Interval] = None,
                 spans: Sequence[Span] = (), top: int = 10) -> Dict[str, Any]:
    """Busy and idle time, per-program and per-op sums, collective time and
    its exposed part, and the idle gaps of device 0 by what the host was
    doing. `window` (seconds from the start of the profile) defaults to the
    span from the first to the last device event; `spans` (the driver
    thread's) are on the same clock."""
    devices = raw["devices"]
    if not devices:
        return {"devices": {}, "window_s": 0.0, "busy_s": 0.0}
    if window is None:
        every = [s for d in devices.values() for s in d["ops"] or d["modules"]]
        window = (min(s[1] for s in every), max(s[2] for s in every))
    lo, hi = window
    per_device: Dict[int, Dict[str, Any]] = {}
    for n, dev in sorted(devices.items()):
        events = dev["ops"] or dev["modules"]
        busy = clip(union((s[1], s[2]) for s in events), lo, hi)
        coll = [s for s in dev["ops"] if COLLECTIVE.search(s[0])]
        other = [s for s in dev["ops"] if not COLLECTIVE.search(s[0])]
        coll_u = clip(union((s[1], s[2]) for s in coll), lo, hi)
        other_u = clip(union((s[1], s[2]) for s in other), lo, hi)
        programs: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"count": 0, "seconds": 0.0})
        for name, a, b in dev["modules"]:
            if b > lo and a < hi:
                prog = programs[program_name(name)]
                prog["count"] += 1
                prog["seconds"] += min(b, hi) - max(a, lo)
        ops: Dict[str, float] = defaultdict(float)
        for name, a, b in dev["ops"]:
            if b > lo and a < hi:
                ops[name] += min(b, hi) - max(a, lo)
        per_device[n] = {
            "busy_s": total(busy),
            "busy": busy,
            "programs": {k: dict(v) for k, v in programs.items()},
            "ops": dict(ops),
            "collective_s": total(coll_u),
            "collective_exposed_s": total(subtract(coll_u, other_u)),
            "collective_events": sum(1 for s in coll if s[2] > lo and s[1] < hi),
        }
    first = per_device[min(per_device)]
    by_label: Dict[str, float] = defaultdict(float)
    for a, b in gaps(first["busy"], lo, hi):
        by_label[_label(0.5 * (a + b), spans)] += b - a
    ranked_ops = sorted(first["ops"].items() or
                        ((k, v["seconds"]) for k, v in first["programs"].items()),
                        key=lambda kv: -kv[1])
    for dev in per_device.values():
        del dev["busy"]
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "devices": per_device,
        "device_ops": [[name[:96], secs] for name, secs in ranked_ops[:top]],
        "idle_gaps": [[name, secs] for name, secs in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
    }


# -- recording ----------------------------------------------------------------


def start(logdir: str) -> None:
    """Start the profiler with the Python tracer and the HLO dump off: the
    one floods the host's threads with events, the other makes files of
    many megabytes; host `TraceAnnotation`s stay on."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


class TraceWindow:
    """Traces a steady part of the measured window from a thread of its
    own: `lead_s` after the window opens, for `length_s`. Off (and free)
    unless `enabled`."""

    def __init__(self, enabled: bool, lead_s: float, length_s: float,
                 out_dir: Optional[str] = None) -> None:
        self.enabled = enabled
        self.lead_s, self.length_s = lead_s, length_s
        self.out_dir = out_dir
        self.logdir: Optional[str] = None
        #: wall clock when start_trace had returned and when stop_trace was
        #: called: the device is traced between the two (stopping takes a
        #: second or more, and the profile's own stop time is after it)
        self.traced: Optional[Interval] = None
        #: monotonic clock before start_trace was called and after stop_trace
        #: had returned: what the host does in between carries the profiler
        #: (a pass of 11 ms takes 18, and stopping takes seconds), so the
        #: readers of host-clock pass times leave that interval out
        self.profiled: Optional[Interval] = None
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        #: wall clock minus monotonic clock, to lay the benchmark's spans
        #: (monotonic) over the profile (wall); good to well under 1 ms
        self.wall_minus_mono = time.time() - time.monotonic()

    def _run(self) -> None:
        try:
            time.sleep(self.lead_s)
            before = time.monotonic()
            start(self.logdir)
            began = time.time()
            try:
                time.sleep(self.length_s)
            finally:
                self.traced = (began, time.time())
                stop()
                self.profiled = (before, time.monotonic())
        except BaseException as e:  # noqa: BLE001 - surfaced by reduced()
            self.error = e

    def __enter__(self) -> "TraceWindow":
        if self.enabled:
            self.logdir = tempfile.mkdtemp(prefix="perf-trace-")
            self._thread = threading.Thread(target=self._run, name="perf-trace",
                                            daemon=True)
            self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        if self._thread is not None:
            self._thread.join(timeout=self.lead_s + self.length_s + 120)

    def reduced(self, spans: Sequence[Span] = ()) -> Optional[Dict[str, Any]]:
        """The reduced trace (None when tracing was off); `spans` on the
        monotonic clock. Removes the raw trace, or copies it to `out_dir`
        first."""
        if not self.enabled:
            return None
        try:
            if self.error is not None:
                raise self.error
            path = find_xplane(self.logdir)
            raw = read_xplane(path)
            window = None
            if raw["start_wall_s"] is not None and self.traced is not None:
                window = (self.traced[0] - raw["start_wall_s"],
                          self.traced[1] - raw["start_wall_s"])
            shift = self.wall_minus_mono - (raw["start_wall_s"] or 0.0)
            out = reduce_trace(
                raw, window,
                [(name, a + shift, b + shift) for name, a, b in spans])
            out["profiled"] = self.profiled
            if self.out_dir:
                os.makedirs(self.out_dir, exist_ok=True)
                shutil.copy(path, os.path.join(
                    self.out_dir, f"trace-{int(time.time())}.xplane.pb"))
            return out
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


def describe(path: str, top: int = 8) -> str:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in data.planes:
        lines.append(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            events = list(line.events)
            names = Counter(ev.name for ev in events)
            span = ""
            if events:
                lo = min(ev.start_ns for ev in events)
                hi = max(ev.start_ns + ev.duration_ns for ev in events)
                span = f" [{lo * 1e-9:.6f}s .. {hi * 1e-9:.6f}s]"
            lines.append(f"  LINE {line.name!r}: {len(events)} events{span}")
            for name, count in names.most_common(top):
                secs = sum(ev.duration_ns for ev in events if ev.name == name) * 1e-9
                lines.append(f"      {count:>7} x {name[:100]!r}  {secs:.6f}s")
    return "\n".join(lines)


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        print(describe(arg))
