"""Model programs: host milliseconds one dispatch of the cached multinomial
pass's fold program spends in the jit ledger's wrapper (`utils/xprof.py`
`LedgeredJit.__call__`: signature found → dispatch returned) —
Δ`srml_xla_dispatch_seconds_total` ÷ Δ`srml_xla_calls_total`, both
`{fn=logreg.softmax_streaming_update_group}` (`models/logistic_regression.py`
`_stream_softmax_stats_group_fn`: the fold `logreg.softmax_streaming_update`
runs, over a group of cached batches in one program), across the window:
what `newton_fold_dispatch_ms` is for the binary fold. Far under
`pass_fold_device_ms` it is what a dispatch costs the host; near it the
runtime's queue is full. Nothing to read when no such program was called,
or no second was counted."""

FN = "logreg.softmax_streaming_update_group"


def read(obs):
    calls = obs.counter_delta("srml_xla_calls_total", fn=FN)
    seconds = obs.counter_delta("srml_xla_dispatch_seconds_total", fn=FN)
    return None if calls <= 0 or seconds <= 0 else 1e3 * seconds / calls
