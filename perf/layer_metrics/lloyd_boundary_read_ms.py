"""Daemon: mean milliseconds of the program's `lloyd.boundary.read` span — the
third of the four children `serve/daemon.py` `_Job.step` opens inside the
boundary span the algorithm names. It wraps the `float()` of the step's device
scalars (`moved2`, `cost`) alone and is WHERE THE JOB WAITS for the device:
whatever of the pass's folds and of the update's programs still runs, then one
device-to-host read a scalar; everything after the first read returns is time
the device stands idle. While the eager update and zero state keep the host
behind the device it reads two round trips (1.2 ms, PERF.md §5); a jitted
boundary would move the wait for the folds here. Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=lloyd.boundary.read}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="lloyd.boundary.read")
