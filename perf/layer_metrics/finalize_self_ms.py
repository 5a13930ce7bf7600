"""Finalize: the self time of the program's `eig finalize` span, in mean
milliseconds — (Δsum of `eig finalize` − Σ Δsum of its five host-path
children) ÷ Δcount of `eig finalize`, all from
`srml_phase_duration_seconds`. What the split leaves unexplained: near 0
while every piece of the finalize runs under a child span, and it grows when
someone adds work there outside one. Nothing to read when the parent or any
child has no new sample (a program without the children, the device path)."""

NAME = "srml_phase_duration_seconds"
CHILDREN = ("finalize.wait", "finalize.fetch", "finalize.center",
            "finalize.lapack", "finalize.post")


def read(obs):
    whole, count = obs.hist_delta(NAME, phase="eig finalize")
    parts = [obs.hist_delta(NAME, phase=child) for child in CHILDREN]
    if count <= 0 or any(n <= 0 for _, n in parts):
        return None
    return 1e3 * (whole - sum(s for s, _ in parts)) / count
