"""Daemon: the rate of the window's MEDIAN pass — rows of the passes that
completed inside the window ÷ (their number × the median of their seconds;
`stats.median_pass_rows_per_s`). The steadier statistic beside the
end-to-end `pass_rows_per_s`, which is taken over all the passes' seconds:
this one leaves out what the few late passes of a window add
(`late_pass_share` reads that), so it repeats from run to run where the
end-to-end rate does not, and is judged by no bound. Host clock, read in
the traced run: the passes that touch the interval in which the profiler
was on (`stats.unprofiled`: a KMeans pass of 10.8 ms takes 18 there) are
left out. Nothing to read when no pass completed."""

from perf.harness import stats


def read(obs):
    return stats.median_pass_rows_per_s(stats.unprofiled(obs.passes, obs.trace), obs.window[1])
