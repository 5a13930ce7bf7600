"""Daemon: the self time of the program's `lloyd.boundary` span, in mean
milliseconds — (Δsum of `lloyd.boundary` − Σ Δsum of its four children
`.update`, `.state`, `.read`, `.snapshot`) ÷ Δcount of `lloyd.boundary`, all
from `srml_phase_duration_seconds`, as `finalize_self_ms` for the finalize.
What `_Job.step` does under the boundary span outside a child: `_close_pass`,
the counters, the ack's bookkeeping and the spans' own cost — near 0, and it
grows when someone adds work there outside a child. Nothing to read while
the parent or any child has no new sample (a program without the children)."""

NAME = "srml_phase_duration_seconds"
CHILDREN = ("lloyd.boundary.update", "lloyd.boundary.state", "lloyd.boundary.read",
            "lloyd.boundary.snapshot")


def read(obs):
    whole, count = obs.hist_delta(NAME, phase="lloyd.boundary")
    parts = [obs.hist_delta(NAME, phase=child) for child in CHILDREN]
    if count <= 0 or any(n <= 0 for _, n in parts):
        return None
    return 1e3 * (whole - sum(s for s, _ in parts)) / count
