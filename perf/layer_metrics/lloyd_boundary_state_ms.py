"""Daemon: mean milliseconds of the program's `lloyd.boundary.state` span —
the second of the four children `serve/daemon.py` `_Job.step` opens inside the
boundary span the algorithm names. It wraps `algorithm.next_pass_state`
alone: the dispatch of the next pass's zero statistics, in the same hold of
the device lock as the update. Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=lloyd.boundary.state}` across the window.
Nothing to read from a program without the span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="lloyd.boundary.state")
