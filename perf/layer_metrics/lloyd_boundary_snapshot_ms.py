"""Daemon: mean milliseconds of the program's `lloyd.boundary.snapshot`
span — the last of the four children `serve/daemon.py` `_Job.step` opens
inside the boundary span the algorithm names. It wraps `_maybe_snapshot`
alone, the durability point before the ack; the cell's job has no snapshot
callback, so it reads what an empty span costs. Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=lloyd.boundary.snapshot}` across the
window. Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="lloyd.boundary.snapshot")
