"""Rows folded by the passes that completed inside the window, over the
seconds those passes took. A pass is one fit's folds, from the first
dispatch until the state is ready on the device (`block_until_ready`): the
device's pace, with no daemon and no wire before it. Finalize and the gaps
between fits are not in it: finalize is a fixed cost of a fit whatever its
depth, and has its own per-layer metric, `finalize_s`; a fit takes
rows ÷ fold_rows_per_s + finalize_s, and the whole fit is the end-to-end
`fit_rows_per_s`. The cells on a daemon job's cached pass report the same
arithmetic as `pass_rows_per_s` (PR 35)."""

from perf.harness import stats


def read(obs):
    return stats.rows_per_s(obs.passes, obs.window[1])
