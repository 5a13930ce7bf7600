"""Daemon: mean milliseconds of the program's `pass.rescan` span
(`serve/daemon.py` `_Job.rescan`: the dispatch of one cached pass — every
cached batch through the fold's program, a group of batches a dispatch,
under the job's lock and the device lock; host time, the device runs on
behind it) — Δsum ÷ Δcount of
`srml_phase_duration_seconds{phase=pass.rescan}` across the window. Near
the device time of a pass the runtime's queue holds the host back; far
under it the host is ahead. Nothing to read from a program without the
span."""


def read(obs):
    return obs.hist_mean_ms("srml_phase_duration_seconds", phase="pass.rescan")
