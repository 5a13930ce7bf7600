"""Model programs: host milliseconds one dispatch of the cached pass's fold
program spends in the jit ledger's wrapper (`utils/xprof.py`
`LedgeredJit.__call__`: signature found → dispatch returned) —
Δ`srml_xla_dispatch_seconds_total` ÷ Δ`srml_xla_calls_total`, both
`{fn=histogram.update_group}` (`ops/histogram.py` `hist_update_group_fn`:
the histogram fold over a run of cached batches in one program, one
signature a depth), across the window's whole fits: what
`newton_fold_dispatch_ms` is for the Newton fold. Far under
`pass_fold_device_ms` it is what a dispatch costs the host. Nothing to read
when no such program was called, or no second was counted."""

FN = "histogram.update_group"


def read(obs):
    calls = obs.counter_delta("srml_xla_calls_total", fn=FN)
    seconds = obs.counter_delta("srml_xla_dispatch_seconds_total", fn=FN)
    return None if calls <= 0 or seconds <= 0 else 1e3 * seconds / calls
