"""chip_smoke.py on the CPU mesh: stages 1–5 small, and the two
properties the chip check leans on — the script itself refuses to run
without a TPU, and importing the package (or holding a client) never
takes the chip from the process that owns it."""

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_main_path_stages_on_the_cpu_mesh():
    """Stages 1–5 at d=64, k=4 through the same stage functions ``main``
    runs at d=2048. Only the test skips stage 0 (the chip demand); the
    multi-device checks run too — the CPU mesh has 8 devices."""
    out = chip_smoke.run_main_path(
        chip_smoke.CompileWatch(),
        lambda name, stage: stage(),
        d=64, k=4, partition_rows=(2048, 2048, 2048, 1500), feed_rows=1024,
    )
    assert out["fit_daemon"]["rows"] == 7644
    assert out["fit_daemon"]["mesh_data"] == 8
    assert out["fit_daemon"]["vs_one_device"]["min_cos"] >= chip_smoke.TOL_COS_MESH
    serve = out["serve"]
    assert serve["ladder_requests"] >= 20 and serve["ladder_compiles"] == 0
    # The default ladder is three device programs: the 64 bucket dedupes
    # onto the 256-row floor.
    assert serve["aot"]["compiled"] == 3 and serve["aot"]["ladder_misses"] == 0
    assert serve["oversize_requests"] == 4 and serve["oversize_compiles"] <= 1
    assert out["agreement"]["daemon_fit"]["min_cos"] >= chip_smoke.TOL_COS
    assert out["agreement"]["serve_worst"] <= chip_smoke.TOL_SERVE
    assert out["no_fallback"]["ledger"]["gram.streaming_update"]["calls"] > 0
    json.dumps(out)  # the summary must serialize


def test_the_forest_stage_on_the_cpu_mesh():
    """Stage 7 small (d=64, 16 bins, depth 3) through the function ``main``
    runs at the deployment's widths: every level from the job's pass cache,
    the fit inside the deployment's tolerances of the plain reference."""
    out = chip_smoke.stage_forest(
        sizes={"n_cols": 64, "num_trees": 3, "max_bins": 16, "max_depth": 3,
               "daemon_pass_cache_mb": 4, "forest_hist_budget_mb": 4},
        params={"batch_rows": 512, "cached_batches": 2, "partitions": 2,
                "compare_trees": 2})
    assert len(out["level_seconds"]) == 3
    assert out["compared"]["count_mismatch"] == [0.0, 0.0]
    assert out["compared"]["split_equal_share"][0] == 1.0
    # the depth-2 level folded whole and by halves: the same counts
    ways = out["both_ways"]
    assert ways["count_cells_differ"] == 0 and ways["count_total"] > 0
    assert ways["derived_nodes"] > 0 and ways["folded_nodes"] >= ways["derived_nodes"]
    assert 0 < out["derived_share"] < 50 and out["frontier_nodes"]["folded"] >= 3
    # here every fold is the XLA body's (on the chip the stage fails unless all are fused)
    # (the two feeds' folds and the three levels' rescans)
    assert out["fold_paths"] == {"fused": 0.0, "xla": 5.0} and out["fused_share"] == 0.0
    assert ways["xla_count_cells_differ"] == 0 and max(ways["xla_label_sums_rel"]) < 1e-6
    json.dumps(out)


def test_result_line_is_exactly_the_drivers_contract():
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(ROOT), capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "chip_smoke needs a TPU" in proc.stderr
    assert "'cpu'" in proc.stderr  # names the backend it found
    assert '"ok"' not in proc.stdout  # no result without the chip


def test_import_client_and_cache_placement_initialise_no_backend():
    """A Spark task or a load-generating client on the TPU host imports
    the package and must not take the chip from the daemon. Placing the
    compile cache does not either — and without JAX_COMPILATION_CACHE_DIR
    it lands in <checkout>/.jax_cache, a fixed path."""
    prog = (
        "import spark_rapids_ml_tpu, spark_rapids_ml_tpu.serve\n"
        "import spark_rapids_ml_tpu.serve.fleet\n"
        "import spark_rapids_ml_tpu.spark.estimator\n"
        "from spark_rapids_ml_tpu.serve import DataPlaneClient\n"
        "from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache\n"
        "client = DataPlaneClient('127.0.0.1', 1)\n"
        "print(ensure_compile_cache())\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", prog], cwd=str(ROOT), capture_output=True,
        text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == str(ROOT / ".jax_cache")
