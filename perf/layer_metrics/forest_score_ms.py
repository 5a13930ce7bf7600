"""Model programs: mean milliseconds of the program's `forest.score` span
(`models/random_forest.py` `grow_level`: the dispatch of
`histogram.best_splits` — every candidate split of the depth's frontier
scored on the device, tree by tree — until its six small results are on the
host). The scorer reads the histogram the pass's folds write, so the wait
for them is in it: against `pass_fold_device_ms` it says what the scorer
adds. Δsum ÷ Δcount of `srml_phase_duration_seconds{phase=forest.score}`
across the window's whole fits. Nothing to read from a program without the
span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="forest.score")
