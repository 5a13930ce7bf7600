"""Model programs: folds the jit ledger counted in the window over the
passes that ran in it — Δ`srml_xla_calls_total{fn=colsum.fold}` ÷
Δ`srml_xla_calls_total{fn=colsum.scale}`: the mix's `folds_per_pass` while
every pass folds all it should. Nothing to read when no pass ran."""


def read(obs):
    folds = obs.counter_delta("srml_xla_calls_total", fn="colsum.fold")
    passes = obs.counter_delta("srml_xla_calls_total", fn="colsum.scale")
    return None if passes <= 0 else folds / passes
