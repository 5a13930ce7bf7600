"""CPU rehearsal of the resident fold at a tiny size (d=64) on one device
and on a mesh over 4 of the 8 virtual devices, and a cell, a mix, a generator
and two metrics added as new files to a copy of the tree — the way a later
PR adds them."""

import json
import os

import pytest

import perf_rehearse


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
END_TO_END = {"fold_rows_per_s", "finalize_s", "setup_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return perf_rehearse.tiny_root(tmp_path_factory.mktemp("fold"))


@pytest.mark.parametrize("cell,mesh,rows_per_fit", [
    ("tiny_pca.fold_resident", "{'data': 1, 'model': 1}", 4 * 256),
    ("tiny_pca.fold_resident_x4", "{'data': 4, 'model': 1}", 4 * 512),
    # a mix that is one data file: the same generator, a deeper fit
    ("tiny_pca.fold_resident_deep", "{'data': 1, 'model': 1}", 16 * 128),
])
def test_fold_resident_end_to_end(root, cell, mesh, rows_per_fit):
    result, lines = perf_rehearse.run(root, cell, seconds=1.0)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert set(result) == RESULT_KEYS and set(result["device"]) == DEVICE_KEYS
    assert f"mesh {mesh}" in text and f"= {rows_per_fit} rows" in text
    assert set(result["metrics"]) == END_TO_END
    assert all(m["value"] > 0 and set(m) == {"value", "unit"}
               for m in result["metrics"].values())
    assert result["failed"] == 0 and result["attempted"] > 4
    assert "compiles in window: 0" in text and "agreement over" in text
    assert result["device"]["platform"] == "cpu"  # named for what it is


def test_fold_resident_per_layer_reads_spans_and_leaves_out_the_trace(root):
    result, lines = perf_rehearse.run(root, "tiny_pca.fold_resident_x4", seconds=1.0,
                                      trace=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    got = result["metrics"]
    # the program's own span and the compile count; the rest needs a TPU trace
    assert set(got) == {"finalize_eig_ms", "compiles_in_window"}
    assert got["compiles_in_window"]["value"] == 0 and got["finalize_eig_ms"]["value"] > 0
    for name in ("fold_device_ms", "fold_roofline", "collective_ms_per_fold",
                 "device_idle_share"):
        assert f"metric {name}: nothing to read, left out" in text
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_a_fit_that_lost_a_fold_is_not_correct(root, monkeypatch):
    """The models would still agree (the ring's batches are alike): the
    state's own row count is what catches work that was not done."""
    from spark_rapids_ml_tpu.ops import gram

    real = gram.streaming_update

    def lossy(mesh):
        update, calls = real(mesh), [0]

        def skipping(state, x, mask):
            calls[0] += 1
            return state if calls[0] % 4 == 0 else update(state, x, mask)

        return skipping

    monkeypatch.setattr(gram, "streaming_update", lossy)
    result, lines = perf_rehearse.run(root, "tiny_pca.fold_resident", seconds=0.5)
    assert result["correct"] is False
    assert any("the state counts" in line for line in lines)


GENERATOR = '''
"""A generator a later PR brought: counts loop turns for the window."""
import time


def run(ctx):
    obs = ctx.obs
    start = ctx.begin_window()
    while time.monotonic() < obs.window[1]:
        obs.attempted += ctx.params["turns"]
        obs.passes.append({"rows": ctx.params["turns"], "start": start,
                           "end": time.monotonic()})
    ctx.end_window()
    return obs
'''
READER = '''
"""Turns of the loop in a second."""


def read(obs):
    return obs.attempted / obs.seconds
'''
LAYER_READER = '''
"""How many passes the loop recorded."""


def read(obs):
    return len(obs.passes)
'''


def test_a_later_pr_adds_a_cell_a_mix_and_metrics_as_files_of_their_own(tmp_path):
    root = perf_rehearse.tiny_root(tmp_path)
    before = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name != "BENCHMARK.json":
                before[path] = open(path, "rb").read()
    perf = os.path.join(root, "perf")
    files = {
        "generators/turns.py": GENERATOR,
        "end_to_end/turns_per_s.py": READER,
        "layer_metrics/turns_passes.py": LAYER_READER,
        "traffic/turns.json": json.dumps({"generator": "turns", "params": {"turns": 3}}),
        "cells/tiny_pca.turns.json": json.dumps({
            "config": "tiny_pca", "traffic": "turns", "chips": 1, "why": "added"}),
    }
    for rel, content in files.items():
        with open(os.path.join(perf, rel), "w", encoding="utf-8") as f:
            f.write(content)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = "tiny_pca.turns"
    bench["workloads"].append({"name": cell, "config": "tiny_pca", "traffic": "turns",
                               "chips": 1, "why": "added"})
    bench["end_to_end"].append({"name": "turns_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": [cell]})
    bench["per_layer"].append({"name": "turns_passes", "unit": "passes",
                               "better": "higher", "source": "program_counter",
                               "layer": "loop", "moves": "turns_per_s",
                               "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(bench, f)

    result, _ = perf_rehearse.run(root, cell, seconds=0.2)
    assert set(result["metrics"]) == {"turns_per_s", "setup_s"}
    assert result["metrics"]["turns_per_s"] == {
        "value": result["attempted"] / 0.2, "unit": "1/s"}
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    result, _ = perf_rehearse.run(root, cell, seconds=0.2, trace=True)
    assert set(result["metrics"]) == {"turns_passes", "compiles_in_window"}
    # ... and no file that was there was edited
    assert all(open(path, "rb").read() == content for path, content in before.items())
