"""Rows of the fits whose model arrived inside the window, over ALL the
seconds those fits took: each fit's folds (first dispatch → the state ready
on the device) plus its finalize (`finalize_pca_stats` called → the model's
arrays on the host) — `stats.fit_rows_per_s`. What a Spark job pays for a
PCA fit once its rows are on the device, at the cell's depth: a fit takes
rows ÷ fit_rows_per_s = rows ÷ fold_rows_per_s + `finalize_s`. The
finalize is a fixed cost whatever the depth (23% of a fit here), and it is
the part that varies from run to run; the gaps between fits are not in
it."""

from perf.harness import stats


def read(obs):
    return stats.fit_rows_per_s(obs.passes, obs.fits, obs.window[1])
