"""Daemon: mean milliseconds of the depth-0 level passes (`rescan` → `step`
returned) of the window's whole fits, on the benchmark's own clock
(`obs.passes`, which the forest generator lists with their `depth`). Beside
`levels_deepest_pass_ms` it says whether a level's time follows the
frontier's width (32 nodes a tree at depth 5 against 1) or the rows alone:
what a faster fold is up against. Nothing to read where no listed pass
carries a depth of 0."""


def mean_ms(obs, depth):
    took = [p["end"] - p["start"] for p in obs.passes
            if p.get("depth") == depth and p["end"] <= obs.window[1]]
    return 1e3 * sum(took) / len(took) if took else None


def read(obs):
    return mean_ms(obs, 0)
