"""Model programs: host milliseconds one fold spends in the jit ledger's
wrapper (`utils/xprof.py` `LedgeredJit.__call__`: signature found →
dispatch returned) — Δ`srml_xla_dispatch_seconds_total` ÷
Δ`srml_xla_calls_total`, both `{fn=gram.streaming_update}`, across the
window. Far under `fold_device_ms` it is what a fold costs the host; near it
the runtime's queue is full and holds the host at the device's pace.
Nothing to read when no fold was called, or no second was counted (a
program that does not keep the counter)."""

FN = "gram.streaming_update"


def read(obs):
    calls = obs.counter_delta("srml_xla_calls_total", fn=FN)
    seconds = obs.counter_delta("srml_xla_dispatch_seconds_total", fn=FN)
    return None if calls <= 0 or seconds <= 0 else 1e3 * seconds / calls
