"""Rows folded by the passes that completed inside the window, over ALL the
seconds those passes took — `fold_rows_per_s`' arithmetic
(`stats.rows_per_s`) under a name and a bound of its own, for the cells
whose rows stay on the chip as a daemon job's cached pass. A pass there is
`rescan` → `step` returned (a cost scan: → the cost on the host): tens of
milliseconds, each ending in a read from the device, so the pass boundary —
and what the host adds to it — IS in the rate, and the few passes of a
window that come back late are in it too: a fit takes passes × rows ÷
pass_rows_per_s. Those late passes come 0–4 a window, so this rate spreads
by what a 1% bound cannot hold (PERF.md §2); the steadier statistic stands
beside it as the per-layer `median_pass_rows_per_s`, the tail as
`late_pass_share`."""

from perf.harness import stats


def read(obs):
    return stats.rows_per_s(obs.passes, obs.window[1])
