"""Metric arithmetic of the benchmark (no JAX, no package imports)."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np


def rows_per_s(passes: Iterable[Dict[str, Any]], deadline: float) -> Optional[float]:
    """Rows scanned by the passes that ended by `deadline`, over the seconds
    those passes took (set-up, finalize and the gaps between fits are not in
    it). None when no pass completed."""
    done = [p for p in passes if p["end"] <= deadline]
    seconds = sum(p["end"] - p["start"] for p in done)
    if not done or seconds <= 0:
        return None
    return sum(p["rows"] for p in done) / seconds


def _series(snapshot: Dict[str, Any], name: str, labels: Dict[str, str]):
    for sample in snapshot.get(name, {}).get("samples", []):
        if all(sample["labels"].get(k) == v for k, v in labels.items()):
            yield sample


def counter_value(snapshot: Dict[str, Any], name: str, **labels: str) -> float:
    """Sum of a counter's series whose labels include `labels`."""
    return float(sum(s["value"] for s in _series(snapshot, name, labels)))


def counter_delta(before, after, name: str, **labels: str) -> float:
    return counter_value(after, name, **labels) - counter_value(before, name, **labels)


def hist_value(snapshot: Dict[str, Any], name: str, **labels: str) -> Tuple[float, int]:
    total, count = 0.0, 0
    for s in _series(snapshot, name, labels):
        total += float(s["sum"])
        count += int(s["count"])
    return total, count


def hist_delta(before, after, name: str, **labels: str) -> Tuple[float, int]:
    """(Δsum, Δcount) of a histogram across the window."""
    s1, c1 = hist_value(after, name, **labels)
    s0, c0 = hist_value(before, name, **labels)
    return s1 - s0, c1 - c0


def hist_mean_ms(before, after, name: str, **labels: str) -> Optional[float]:
    dsum, dcount = hist_delta(before, after, name, **labels)
    return None if dcount <= 0 else 1e3 * dsum / dcount


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run spread."""
    v = np.asarray(values, np.float64)
    q1, q2, q3 = np.percentile(v, [25, 50, 75])
    return float((q3 - q1) / q2)
