"""Daemon: the share of the rows folded inside the window that came from
the job's cached pass, in per cent —
Δ`srml_daemon_pass_rows_total{source=cache}` ÷ Δ of both sources (`wire`:
fed and committed; `cache`: `rescan`), bumped in `serve/daemon.py` `_Job`.
100 while every pass of the window is scanned from HBM. Nothing to read
from a program without the counter, or when no row was folded."""

NAME = "srml_daemon_pass_rows_total"


def read(obs):
    cache = obs.counter_delta(NAME, source="cache")
    total = cache + obs.counter_delta(NAME, source="wire")
    return None if total <= 0 else 100.0 * cache / total
