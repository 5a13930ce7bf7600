"""Model programs: programs built, or loaded from the persistent cache,
between the two ends of the window (JAX's own compile event, ledgered or
not). Must read 0; a run in which it does not is not correct."""


def read(obs):
    return obs.compiles_in_window()
