"""Counts from a registry histogram's buckets (PR 38): how many of the
window's samples lay above one of the histogram's own bucket bounds. A
snapshot keeps a series' buckets cumulative under the bound's decimal form
(`utils/metrics.py` `_cumulate`: "0.05"), so the samples above a bound are
count - buckets[bound], and a window's are that after less that before.

A reader loads this file from the tree its run is of
(`layout.load_module(obs.root, "harness", "buckets")`)."""

from __future__ import annotations

from typing import Any, Dict, Optional


def _over(snapshot: Dict[str, Any], name: str, le: str, labels: Dict[str, str]):
    """(samples above `le`, samples) summed over the series whose labels
    include `labels`; None where no such series holds the bound."""
    over, count, found = 0, 0, False
    for sample in snapshot.get(name, {}).get("samples", []):
        if all(sample["labels"].get(k) == v for k, v in labels.items()):
            if le not in sample["buckets"]:
                return None
            found = True
            over += int(sample["count"]) - int(sample["buckets"][le])
            count += int(sample["count"])
    return (over, count) if found else None


def over(obs, name: str, le: str, **labels: str) -> Optional[float]:
    """The window's samples of `name{labels}` above the bucket bound `le`:
    0.0 when none was, and nothing to read only from a program whose
    registry has no such series (or no such bound) at the window's end."""
    after = _over(obs.after["metrics"], name, le, labels)
    if after is None or after[1] <= 0:
        return None
    before = _over(obs.before["metrics"], name, le, labels) or (0, 0)
    return float(after[0] - before[0])
