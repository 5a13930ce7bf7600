"""Daemon: a cached pass's host time OUTSIDE the jit ledger's clock, in mean
milliseconds — (Δsum of the span `pass.rescan` −
Δ`srml_xla_dispatch_seconds_total{fn=kmeans.streaming_update_group}`) ÷ Δcount
of `pass.rescan`: what `_Job.rescan` spends around its dispatches — the two
locks, grouping the cached batches, and in `LedgeredJit.__call__` the
signature of the call's arrays (computed BEFORE its clock starts), the
counters and `on_dispatch` after it. `rescan_dispatch_ms` = this + the
dispatches of a pass × `lloyd_fold_dispatch_ms`. Nothing to read when no
cached pass was scanned or no dispatch was counted."""

FN = "kmeans.streaming_update_group"


def read(obs):
    whole, count = obs.hist_delta("srml_phase_duration_seconds", phase="pass.rescan")
    dispatched = obs.counter_delta("srml_xla_dispatch_seconds_total", fn=FN)
    return None if count <= 0 or dispatched <= 0 else 1e3 * (whole - dispatched) / count
