"""Daemon: mean milliseconds of the program's `lloyd.boundary.update` span —
the first of the four children `serve/daemon.py` `_Job.step` opens inside the
boundary span the algorithm names. It wraps `algorithm.step` alone: the
host's time to DISPATCH `apply_lloyd_update`'s eager programs, which queue
behind the pass's folds; nothing in it waits for the device. Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=lloyd.boundary.update}` across the window.
The four children and `lloyd_boundary_self_ms` add up to `lloyd_boundary_ms`.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="lloyd.boundary.update")
