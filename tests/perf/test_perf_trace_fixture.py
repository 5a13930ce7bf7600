"""The trace reduction on a trace recorded on the chip: half a second of
`pca_d2048_k32.fold_resident_x4` on four TPU v5e chips (PR 22; jax 0.9.0).
The expected values were taken by hand from what `python3 -m
perf.harness.trace` prints for the file: per plane and line, each event
name's count and summed duration."""

import gzip
import os
import shutil

import pytest

from perf.harness import cost, device, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "fold_resident_x4.xplane.pb.gz")


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "fold.xplane.pb")
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.read_xplane(path), path


def test_planes_lines_and_the_wall_clock_are_found(raw):
    raw, _ = raw
    assert sorted(raw["devices"]) == [0, 1, 2, 3]
    assert raw["start_wall_s"] == pytest.approx(1790443004.4155357)
    assert raw["stop_wall_s"] - raw["start_wall_s"] == pytest.approx(2.5589142)
    assert [len(raw["devices"][n]["modules"]) for n in range(4)] == [124, 124, 124, 125]
    assert [len(raw["devices"][n]["ops"]) for n in range(4)] == [2097, 2097, 2097, 2107]
    names = {name for name, _, _ in raw["devices"][0]["ops"]}
    assert {"fusion", "convert_reduce_fusion", "all-reduce", "add.17"} <= names
    assert not any(" = " in name or name.startswith("%") for name in names)


def test_whole_trace_per_program_sums_and_collective_time(raw):
    raw, _ = raw
    out = trace.reduce_trace(raw)  # window: first to last device event
    dev0 = out["devices"][0]
    # 124 x 'jit_update(8837122662416470448)' 0.518466 s on /device:TPU:0
    assert dev0["programs"] == {"jit_update": {"count": 124,
                                               "seconds": pytest.approx(0.518466, abs=1e-6)}}
    assert out["devices"][3]["programs"]["jit_update"]["count"] == 125
    # 123 x '%all-reduce = (f32[], f32[2048], f32[2048,2048]) all-reduce(...)' 0.035082 s
    # (the last fold was cut by the end of the trace: 123 x '%fusion' too)
    assert dev0["collective_events"] == 123
    assert dev0["collective_s"] == pytest.approx(0.035082, abs=1e-6)
    # the all-reduce follows the dot in the same program: nothing covers it
    assert dev0["collective_exposed_s"] == pytest.approx(dev0["collective_s"])
    top = dict((name, secs) for name, secs in out["device_ops"])
    assert list(top)[:3] == ["fusion", "convert_reduce_fusion", "all-reduce"]
    assert top["fusion"] == pytest.approx(0.390564, abs=1e-6)
    assert top["convert_reduce_fusion"] == pytest.approx(0.088091, abs=1e-6)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_a_steady_part_busy_union_folds_and_roofline(raw):
    raw, _ = raw
    spans = [("fold_loop", 0.0, 1.0)]
    out = trace.reduce_trace(raw, (0.2, 0.6), spans)
    assert out["window_s"] == pytest.approx(0.4)
    dev0 = out["devices"][0]
    # inside a fold loop the device is busy but for the gaps between programs
    assert dev0["busy_s"] == pytest.approx(0.399458, abs=1e-6)
    assert out["busy_s"] == pytest.approx(0.399386, abs=1e-6)  # mean of four chips
    assert dev0["programs"]["jit_update"]["count"] == 96  # the two at the edges clipped
    assert dev0["programs"]["jit_update"]["seconds"] == pytest.approx(0.399905, abs=1e-6)
    assert dev0["collective_events"] == 95
    assert dev0["collective_s"] == pytest.approx(0.027107, abs=1e-6)
    assert out["idle_gaps"] == [["fold_loop", pytest.approx(0.000542, abs=1e-6)]]
    # what the readers make of it: 4.17 ms a fold, 67% of the compute roofline
    fold_s = dev0["programs"]["jit_update"]["seconds"] / 96
    flops, nbytes = cost.pca_fold(65536, 2048)
    line = cost.roofline(flops, nbytes, fold_s, device.peaks_for("TPU v5 lite"))
    assert fold_s == pytest.approx(4.1657e-3, rel=1e-4)
    assert line["bound"] == "compute" and line["share"] == pytest.approx(0.670, abs=1e-3)


def test_describe_prints_what_the_trace_holds(raw):
    _, path = raw
    text = trace.describe(path)
    assert "PLANE '/device:TPU:0'" in text and "LINE 'XLA Modules': 124 events" in text
    assert "124 x 'jit_update(8837122662416470448)'  0.518466s" in text
