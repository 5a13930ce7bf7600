"""Process start → the first timed operation: interpreter and imports, the
ring of rows made on the device from the seed, warm-up of the cell's own
shapes; compile is inside it. The seconds the accelerator's runtime takes
to come up (the first `jax.devices()`) are left out and said on a line of
their own: for the same code they lay between 7 and 17 s with what the
machine had run just before, and everything else in set-up repeated to
0.1 s (PERF.md §2)."""


def read(obs):
    return obs.setup_s
