"""The device a run is on: which it must be, its peaks, its memory."""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class NoDevice(Exception):
    """The accelerator the cell asks for is not there."""


class UnknownDevice(KeyError):
    """`device_kind` is not in peaks.json — an error, never a default."""


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(_PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in perf/harness/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


def require_device(platform: Optional[str], chips: int) -> Dict[str, Any]:
    """platform, kind and count as JAX reports them. With `platform` set
    (perf/run.py always passes "tpu") anything else, or another number of
    chips than the cell asks for, raises NoDevice: JAX falls back to the
    CPU with only a warning, and a CPU number must never get a device
    metric's name. `platform=None` is the tests' rehearsal."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if platform is not None:
        if backend != platform or info["platform"] != platform:
            raise NoDevice(
                f"this cell needs a {platform}: the default JAX backend here "
                f"is {backend!r} ({info['kind']!r})")
        if info["count"] != chips:
            raise NoDevice(
                f"this cell is for {chips} chip(s); JAX shows {info['count']}")
        peaks_for(info["kind"])
    return info


def _stat(key: str) -> List[int]:
    import jax

    out = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        out.append(int(stats.get(key, 0)))
    return out


class WindowMemory:
    """Peak bytes in use on the fullest chip INSIDE the measured window.

    JAX's `peak_bytes_in_use` is monotonic over the life of the process, so
    by itself it would also count what the benchmark did before the window
    (the seeded rows made on the device) and after it (the references). Where
    the peak grew inside the window, the window's peak is JAX's own figure at
    its end; where set-up's was higher, it is the most `bytes_in_use` a
    sampler saw. 0 where the backend reports nothing, as the CPU does not."""

    def __init__(self, period_s: float = 0.2) -> None:
        self.period_s = period_s
        self.peak_bytes = 0
        self._sampled = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="perf-mem",
                                        daemon=True)

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            self._sampled = max(self._sampled, max(_stat("bytes_in_use")))

    def start(self) -> None:
        self._before = max(_stat("peak_bytes_in_use"))
        self._sampled = max(_stat("bytes_in_use"))
        self._thread.start()

    def stop(self) -> int:
        self._stop.set()
        self._thread.join(timeout=5)
        after = max(_stat("peak_bytes_in_use"))
        self._sampled = max(self._sampled, max(_stat("bytes_in_use")))
        self.peak_bytes = after if after > self._before else self._sampled
        return self.peak_bytes
