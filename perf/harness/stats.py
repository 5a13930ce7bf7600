"""Metric arithmetic of the benchmark (no JAX, no package imports)."""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np


def completed(passes: Iterable[Dict[str, Any]], deadline: float):
    """(the passes that ended by `deadline`, their seconds as an array)."""
    done = [p for p in passes if p["end"] <= deadline]
    return done, np.asarray([p["end"] - p["start"] for p in done], np.float64)


def unprofiled(passes: Iterable[Dict[str, Any]], trace: Optional[Dict[str, Any]]):
    """The passes that lie wholly outside the interval in which the profiler
    was on (`trace["profiled"]` of a traced run's reduced trace, monotonic
    clock: start_trace called → stop_trace returned); all of them when
    nothing was traced. While it traces, and while it stops, the profiler
    takes the host's time: a number read from the host's clock pass by pass
    is read from the rest of the window — or from all of it, where the
    profiler left no pass of it alone (a rehearsal's window of a second)."""
    passes = list(passes)
    held = (trace or {}).get("profiled")
    if not held:
        return passes
    return [p for p in passes if p["end"] <= held[0] or p["start"] >= held[1]] or passes


def rows_per_s(passes: Iterable[Dict[str, Any]], deadline: float) -> Optional[float]:
    """Rows scanned by the passes that ended by `deadline`, over the seconds
    those passes took (set-up, finalize and the gaps between fits are not in
    it). None when no pass completed."""
    done, took = completed(passes, deadline)
    if not done or took.sum() <= 0:
        return None
    return sum(p["rows"] for p in done) / float(took.sum())


def fit_rows_per_s(passes: Iterable[Dict[str, Any]], fits: Iterable[Dict[str, Any]],
                   deadline: float) -> Optional[float]:
    """Rows of the fits that ended by `deadline`, over the seconds of their
    passes and of their finalizes (`finalize_s`): the whole fit, as the
    caller waits for it. None when no fit completed."""
    took: Dict[Any, float] = {}
    for p in passes:
        took[p["fit"]] = took.get(p["fit"], 0.0) + p["end"] - p["start"]
    done = [f for f in fits if f["end"] <= deadline and f["fit"] in took]
    seconds = sum(took[f["fit"]] + f["finalize_s"] for f in done)
    if not done or seconds <= 0:
        return None
    return sum(f["rows"] for f in done) / seconds


#: a pass is LATE when it took over this many times the median pass
LATE = 1.5


def median_pass_rows_per_s(passes: Iterable[Dict[str, Any]], deadline: float
                           ) -> Optional[float]:
    """The rate of the median pass: rows of the passes that ended by
    `deadline` over (their number x the median of their seconds), so passes
    that differ in rows still read right. What the passes' tail adds is not
    in it (`late_pass_share` reads that); `rows_per_s` is the rate over all
    their seconds. None when no pass completed."""
    done, took = completed(passes, deadline)
    if not done or np.median(took) <= 0:
        return None
    return sum(p["rows"] for p in done) / (len(done) * float(np.median(took)))


def late_pass_share(passes: Iterable[Dict[str, Any]], deadline: float
                    ) -> Optional[float]:
    """Per cent of the completed passes' seconds that lie beyond the median
    pass in the passes that took over LATE x the median. 0.0 when none was
    late, None when no pass completed."""
    done, took = completed(passes, deadline)
    if not done or took.sum() <= 0:
        return None
    median = float(np.median(took))
    return 100.0 * float((took[took > LATE * median] - median).sum()) / float(took.sum())


def say_passes(passes: Iterable[Dict[str, Any]], deadline: float,
               say: Callable[[str], None],
               trace: Optional[Dict[str, Any]] = None) -> None:
    """How long the window's completed passes took, and each LATE one by its
    two calls where the generator recorded `scanned`: `rescan` (the
    dispatch) and `step` (the wait for the folds, the boundary). What a
    run's rate over all the passes' seconds spreads by (PERF.md §2, §7). In
    a traced run, the passes outside the profiler's interval — what
    `median_pass_rows_per_s` and `late_pass_share` read."""
    if trace and trace.get("profiled"):
        a, b = trace["profiled"]
        say(f"the profiler was on for {b - a:.2f} s (it traced {trace.get('window_s', 0):.2f}"
            " s of them); the passes outside that interval:")
        passes = unprofiled(passes, trace)
    done, took = completed(passes, deadline)
    if not done:
        return
    p10, median, p90 = (float(q) for q in np.percentile(took, [10, 50, 90]))
    late = [p for p, t in zip(done, took) if t > LATE * median]
    say(f"a pass: p10 {1e3 * p10:.2f}, median {1e3 * median:.2f}, p90 {1e3 * p90:.2f}, "
        f"longest {1e3 * took.max():.2f} ms; {len(late)} of {len(done)} over {LATE:g} x "
        f"the median, {late_pass_share(done, deadline):.3f}% of the passes' seconds "
        f"beyond it; rows/s over all their seconds {rows_per_s(done, deadline):.6g}, of "
        f"the median pass {median_pass_rows_per_s(done, deadline):.6g}")
    for p in late[:12]:
        line = (f"  late: fit {p['fit']} pass {p['pass']}: "
                f"{1e3 * (p['end'] - p['start']):.1f} ms")
        if "scanned" in p:
            line += (f" = rescan {1e3 * (p['scanned'] - p['start']):.1f} + step "
                     f"{1e3 * (p['end'] - p['scanned']):.1f}")
        say(line)


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
    measure of run-to-run spread: the quartiles as Python's
    `statistics.quantiles(values, n=4)` gives them (numpy's lie closer
    together)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return float((q3 - q1) / statistics.median(values))


def spread_without_farthest(values: Sequence[float]) -> float:
    """`spread` of the runs without the one farthest from their median: how
    the driver reads a set when it asks whether a bound is too tight (it
    refuses one under twice the mean of the two sets' spreads so read, and
    one over eight times the widest `spread` of all the runs)."""
    median = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - median))[:-1]
    return spread(kept)
