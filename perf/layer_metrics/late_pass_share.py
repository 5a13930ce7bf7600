"""Daemon: per cent of the completed passes' seconds that lie beyond the
median pass in the passes that took over `stats.LATE` (1.5) × the median —
the tail that `pass_rows_per_s` carries and `median_pass_rows_per_s` leaves
out; the run's `a pass:` line prints the same number and each late pass by
its two calls (`late:` lines). Such passes come 0–4 times a 40 s window,
+50 … +180 ms each, cause not known (PERF.md §7), so the share is judged by
no bound; it is on every ledger line so that a change which breeds late
passes shows. Read in the traced run from the passes outside the interval
in which the profiler was on (`stats.unprofiled`: under the profiler a
fifth of the KMeans passes are "late"). 0.0 when no pass was late; nothing
to read when no pass completed."""

from perf.harness import stats


def read(obs):
    return stats.late_pass_share(stats.unprofiled(obs.passes, obs.trace), obs.window[1])
