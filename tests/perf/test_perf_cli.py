"""perf/run.py as the driver starts it: without a TPU, or without the
program, it exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perf.harness import layout

ROOT = layout.REPO_ROOT
ARGS = ["--workload", "pca_d2048_k32.fold_resident", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if not k.startswith("SRML_")}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, os.path.join("perf", "run.py")] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _result_lines(stdout: str):
    found = []
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                found.append(line)
        except ValueError:
            pass
    return found


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "needs a tpu" in proc.stderr and "'cpu'" in proc.stderr


def test_run_py_alone_with_benchmark_json_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: the program is not there."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in layout.load_benchmark(ROOT)["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(tmp_path, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "the program is not here" in proc.stderr


def test_run_py_refuses_an_unknown_cell_and_a_bad_option(capsys):
    from perf import run as run_py

    assert run_py.main(["--workload", "no_such.cell"] + ARGS[2:]) == 1
    out = capsys.readouterr()
    assert not _result_lines(out.out) and "no workload named" in out.err
    with pytest.raises(SystemExit):
        run_py.main(ARGS[:-1] + ["2"])  # --trace takes 0 or 1
    with pytest.raises(SystemExit):
        run_py.main(ARGS + ["--cpu"])  # there is no way to ask for another platform
