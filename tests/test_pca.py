"""PCA differential tests — the reference's oracle pattern, extended.

The reference's one integration test compares the accelerated path against
Spark MLlib CPU PCA element-wise on absolute values at absTol 1e-5
(PCASuite.scala:42-88; abs values because eigenvector sign is arbitrary).
Here the oracle is NumPy/sklearn; plus the coverage the reference lacks
(SURVEY.md §4): multi-device runs on a virtual mesh, shard-count invariance,
2-D (feature-sharded) parity, streaming parity, and float32-mode sanity.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA, PCAModel, config
from spark_rapids_ml_tpu.models.pca import fit_pca, fit_pca_stream
from spark_rapids_ml_tpu.ops.eigh import sign_flip
from spark_rapids_ml_tpu.parallel.mesh import make_mesh

ABS_TOL = 1e-5  # reference tolerance, PCASuite.scala:87


def _oracle(x, k, mean_center=True):
    """NumPy oracle replicating the reference pipeline exactly."""
    x = np.asarray(x, dtype=np.float64)
    if mean_center:
        xc = x - x.mean(axis=0)
    else:
        xc = x
    gram = xc.T @ xc
    w, v = np.linalg.eigh(gram)
    w, v = w[::-1], v[:, ::-1]
    # reference sign flip: max-|x| element of each column made positive
    idx = np.argmax(np.abs(v), axis=0)
    signs = np.where(v[idx, np.arange(v.shape[1])] < 0, -1.0, 1.0)
    v = v * signs
    s = np.sqrt(np.clip(w, 0, None))
    ev = s / s.sum()
    return v[:, :k], ev[:k], s


@pytest.fixture
def data(rng):
    # Anisotropic data so principal directions are well separated.
    n, d = 500, 24
    basis = rng.normal(size=(d, d))
    scales = np.logspace(0, -2, d)
    return rng.normal(size=(n, d)) @ (basis * scales)


def test_fit_matches_oracle(data, mesh8):
    k = 5
    sol = fit_pca(data, k=k, mesh=mesh8)
    pc_ref, ev_ref, s_ref = _oracle(data, k)
    np.testing.assert_allclose(np.abs(sol.pc), np.abs(pc_ref), atol=ABS_TOL)
    np.testing.assert_allclose(sol.explained_variance, ev_ref, atol=ABS_TOL)
    np.testing.assert_allclose(sol.mean, data.mean(axis=0), atol=ABS_TOL)
    assert sol.n_rows == data.shape[0]


def test_sign_flip_matches_reference_semantics(data, mesh8):
    # Signs should agree exactly with the oracle (not just up to sign),
    # because both implement rapidsml_jni.cu:35-61 semantics.
    k = 5
    sol = fit_pca(data, k=k, mesh=mesh8)
    pc_ref, _, _ = _oracle(data, k)
    np.testing.assert_allclose(sol.pc, pc_ref, atol=ABS_TOL)


def test_no_mean_centering_raw_gram(data, mesh8):
    # meanCentering=False must reproduce the reference's raw-Gram path
    # (RapidsRowMatrix.scala:139 — no centering applied on device).
    k = 4
    shifted = data + 3.0  # make centering matter
    sol = fit_pca(shifted, k=k, mean_center=False, mesh=mesh8)
    pc_ref, ev_ref, _ = _oracle(shifted, k, mean_center=False)
    np.testing.assert_allclose(np.abs(sol.pc), np.abs(pc_ref), atol=ABS_TOL)
    np.testing.assert_allclose(sol.explained_variance, ev_ref, atol=ABS_TOL)


def test_shard_count_invariance(data):
    # Property test from SURVEY.md §4: 1 vs N shards -> identical result.
    k = 3
    sols = [
        fit_pca(data, k=k, mesh=make_mesh(data=n, model=1))
        for n in (1, 2, 8)
    ]
    for sol in sols[1:]:
        np.testing.assert_allclose(sol.pc, sols[0].pc, atol=1e-10)
        np.testing.assert_allclose(
            sol.explained_variance, sols[0].explained_variance, atol=1e-12
        )


def test_2d_feature_sharded_parity(data, mesh8, mesh4x2):
    # Feature-sharded (model-axis) Gram must equal the 1-D path.
    k = 6
    a = fit_pca(data, k=k, mesh=mesh8)
    b = fit_pca(data, k=k, mesh=mesh4x2)
    np.testing.assert_allclose(b.pc, a.pc, atol=1e-8)
    np.testing.assert_allclose(b.explained_variance, a.explained_variance, atol=1e-10)


def test_ring_gram_parity(data, mesh8, mesh4x2):
    # The ppermute ring must produce the same Gram as the all_gather path.
    k = 6
    a = fit_pca(data, k=k, mesh=mesh8)
    with config.option("gram_algorithm", "ring"):
        b = fit_pca(data, k=k, mesh=mesh4x2)
    np.testing.assert_allclose(b.pc, a.pc, atol=1e-8)
    np.testing.assert_allclose(b.explained_variance, a.explained_variance, atol=1e-10)


def test_ring_gram_stats_direct(rng, mesh4x2):
    # Direct op-level parity: ring vs all_gather vs single-device numpy.
    from spark_rapids_ml_tpu.ops.gram import sharded_stats_2d, sharded_stats_ring
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = rng.normal(size=(64, 16))
    mask = np.ones((64,), dtype=np.float32)
    xs = jax.device_put(x, NamedSharding(mesh4x2, P("data", "model")))
    ms = jax.device_put(mask, NamedSharding(mesh4x2, P("data")))
    c1, s1, g1 = sharded_stats_2d(mesh4x2)(xs, ms)
    c2, s2, g2 = sharded_stats_ring(mesh4x2)(xs, ms)
    assert float(c1) == float(c2) == 64
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-10)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-10)
    np.testing.assert_allclose(np.asarray(g2), x.T @ x, atol=1e-9)


def test_uneven_rows_padding(mesh8, rng):
    # Row counts not divisible by the mesh must be exact (mask correctness).
    x = rng.normal(size=(101, 7))
    sol = fit_pca(x, k=2, mesh=mesh8)
    pc_ref, ev_ref, _ = _oracle(x, 2)
    np.testing.assert_allclose(np.abs(sol.pc), np.abs(pc_ref), atol=ABS_TOL)


def test_streaming_matches_batch(data, mesh8):
    k = 4
    batches = [data[i : i + 128] for i in range(0, len(data), 128)]
    a = fit_pca_stream(batches, k=k, n_cols=data.shape[1], mesh=mesh8)
    b = fit_pca(data, k=k, mesh=mesh8)
    np.testing.assert_allclose(a.pc, b.pc, atol=1e-8)
    np.testing.assert_allclose(a.explained_variance, b.explained_variance, atol=1e-10)
    assert a.n_rows == b.n_rows == data.shape[0]


def test_float32_mode(data, mesh8):
    # The TPU-native dtype mode: looser tolerance, same structure.
    with config.option("compute_dtype", "float32"), config.option(
        "accum_dtype", "float32"
    ):
        sol = fit_pca(data, k=3, mesh=mesh8)
    pc_ref, ev_ref, _ = _oracle(data, 3)
    np.testing.assert_allclose(np.abs(sol.pc), np.abs(pc_ref), atol=5e-2)
    np.testing.assert_allclose(sol.explained_variance, ev_ref, atol=1e-3)


def test_host_finalize_parity(data, mesh8):
    # The TPU path (device stats + host LAPACK eig) must equal the fully
    # fused device path.
    k = 4
    a = fit_pca(data, k=k, mesh=mesh8)
    with config.option("finalize", "host"):
        b = fit_pca(data, k=k, mesh=mesh8)
    np.testing.assert_allclose(a.pc, b.pc, atol=1e-8)
    np.testing.assert_allclose(a.explained_variance, b.explained_variance, atol=1e-10)


def test_randomized_solver_matches_full(data, mesh8):
    # The on-device subspace-iteration solver must recover the same top-k
    # subspace as the exact eigh on decaying-spectrum data (the regime it
    # exists for), including explained variance (tail estimated via trace).
    k = 4
    a = fit_pca(data, k=k, mesh=mesh8, solver="full")
    b = fit_pca(data, k=k, mesh=mesh8, solver="randomized")
    np.testing.assert_allclose(np.abs(a.pc), np.abs(b.pc), atol=1e-6)
    np.testing.assert_allclose(
        a.explained_variance, b.explained_variance, rtol=2e-2
    )
    np.testing.assert_allclose(a.mean, b.mean, atol=1e-8)


def test_randomized_solver_truncated_subspace(rng, mesh8):
    # d > k + oversample, so the solver runs genuinely rank-truncated:
    # subspace iteration never sees the full spectrum and the trace-based
    # tail estimate (n_tail > 0) feeds the explained-variance denominator.
    n, d, k = 2000, 80, 4  # default oversample=32 → m=36 < d
    basis = rng.normal(size=(d, d)) * np.logspace(0, -2, d)
    x = rng.normal(size=(n, d)) @ basis
    a = fit_pca(x, k=k, mesh=mesh8, solver="full")
    b = fit_pca(x, k=k, mesh=mesh8, solver="randomized")
    np.testing.assert_allclose(np.abs(a.pc), np.abs(b.pc), atol=1e-5)
    # tail is approximated (concave upper bound on Σσ) → looser ev bound,
    # and the estimate must err low, never high.
    np.testing.assert_allclose(a.explained_variance, b.explained_variance, rtol=5e-2)
    assert np.all(b.explained_variance <= a.explained_variance * 1.0 + 1e-12)


def test_solver_validation(data, mesh8):
    # A typo'd solver must raise, not silently pick the slow exact path.
    with pytest.raises(ValueError):
        fit_pca(data, k=3, mesh=mesh8, solver="randomised")
    with pytest.raises(ValueError):
        fit_pca_stream(
            iter([data]), k=3, n_cols=data.shape[1], mesh=mesh8, solver="Full"
        )


def test_randomized_solver_estimator_param(data, mesh8):
    k = 3
    m_full = PCA(mesh=mesh8).setK(k).setSolver("full").fit({"features": data})
    m_rand = PCA(mesh=mesh8).setK(k).setSolver("randomized").fit({"features": data})
    np.testing.assert_allclose(np.abs(m_full.pc), np.abs(m_rand.pc), atol=1e-6)


def test_randomized_solver_streaming(data, mesh8):
    k = 3
    ref = fit_pca(data, k=k, mesh=mesh8)
    with config.option("solver", "randomized"):
        sol = fit_pca_stream(
            np.array_split(data, 4), k=k, n_cols=data.shape[1], mesh=mesh8
        )
    np.testing.assert_allclose(np.abs(ref.pc), np.abs(sol.pc), atol=1e-6)


def test_k_validation(data, mesh8):
    with pytest.raises(ValueError):
        fit_pca(data, k=0, mesh=mesh8)
    with pytest.raises(ValueError):
        fit_pca(data, k=data.shape[1] + 1, mesh=mesh8)
    # Regression: the streaming path must validate k identically.
    with pytest.raises(ValueError):
        fit_pca_stream([data], k=0, n_cols=data.shape[1], mesh=mesh8)
    with pytest.raises(ValueError):
        fit_pca_stream([data], k=data.shape[1] + 1, n_cols=data.shape[1], mesh=mesh8)


def test_dtype_config_change_recompiles(data, mesh8):
    # Regression: flipping dtype config must not silently reuse the cached
    # float64 program (the lru_cache key now includes the dtypes).
    a = fit_pca(data, k=3, mesh=mesh8)
    with config.option("compute_dtype", "float32"), config.option(
        "accum_dtype", "float32"
    ):
        b = fit_pca(data, k=3, mesh=mesh8)
    # float32 result must differ at fine precision (else the cache lied)...
    assert np.max(np.abs(a.pc - b.pc)) > 0
    # ...but agree loosely (same algorithm).
    np.testing.assert_allclose(np.abs(a.pc), np.abs(b.pc), atol=5e-2)


# ---------------------------------------------------------------------------
# Estimator / Model API (PCASuite params + read/write tests equivalents)
# ---------------------------------------------------------------------------


def test_estimator_fit_transform_dict(data, mesh8):
    ds = {"features": data}
    pca = PCA(mesh=mesh8).setInputCol("features").setOutputCol("out").setK(3)
    model = pca.fit(ds)
    out = model.transform(ds)
    assert out["out"].shape == (len(data), 3)
    pc_ref, _, _ = _oracle(data, 3)
    np.testing.assert_allclose(out["out"], data @ pc_ref, atol=1e-4)


def test_estimator_fit_arrow(data, mesh8):
    pa = pytest.importorskip("pyarrow")
    from spark_rapids_ml_tpu.bridge.arrow import matrix_to_list_column

    table = pa.table({"features": matrix_to_list_column(data)})
    model = PCA(mesh=mesh8).setK(2).fit(table)
    out = model.transform(table)
    assert "pca_features" in out.column_names
    mat = np.stack(out.column("pca_features").to_pylist())
    assert mat.shape == (len(data), 2)


def test_model_persistence_roundtrip(data, mesh8, tmp_path):
    # testDefaultReadWrite equivalent (PCASuite.scala:91-105): params and
    # fitted data must survive save/load, asserting pc equality (:104).
    path = str(tmp_path / "pca_model")
    model = PCA(mesh=mesh8).setK(3).setInputCol("features").fit({"features": data})
    model.save(path)
    loaded = PCAModel.load(path)
    assert loaded.uid == model.uid
    np.testing.assert_allclose(loaded.pc, model.pc, atol=1e-12)
    np.testing.assert_allclose(
        loaded.explainedVariance, model.explainedVariance, atol=1e-12
    )
    assert loaded.getK() == 3
    assert loaded.getInputCol() == "features"
    # loaded model must transform identically
    a = model.transform({"features": data})["pca_features"]
    b = loaded.transform({"features": data})["pca_features"]
    np.testing.assert_allclose(a, b, atol=1e-7)


def test_estimator_persistence_roundtrip(mesh8, tmp_path):
    path = str(tmp_path / "pca_est")
    est = PCA().setK(7).setMeanCentering(False)
    est.save(path)
    loaded = PCA.load(path)
    assert loaded.getK() == 7
    assert loaded.getMeanCentering() is False
    assert loaded.uid == est.uid


def test_params_contract():
    # ParamsSuite.checkParams equivalent (PCASuite.scala:33-39).
    pca = PCA()
    assert pca.getMeanCentering() is True  # default, RapidsPCA.scala:45-46
    assert pca.hasParam("k") and pca.hasParam("inputCol")
    pca.setK(4)
    copied = pca.copy()
    assert copied.getK() == 4 and copied.uid == pca.uid
    copied2 = pca.copy({pca.getParam("k"): 9})
    assert copied2.getK() == 9 and pca.getK() == 4
    text = pca.explainParams()
    assert "meanCentering" in text and "principal components" in text


def test_sign_flip_unit():
    u = np.array([[0.1, -0.9], [-0.8, 0.2]])
    out = np.asarray(sign_flip(u))
    # col0: max-|x| is -0.8 -> flip; col1: max-|x| is -0.9 -> flip
    np.testing.assert_allclose(out, -u)


def test_load_tolerates_missing_explained_variance(rng, mesh8):
    # Reference parity: its reader loads pre-Spark-1.6 models that carry no
    # explainedVariance (RapidsPCA.scala:209-213) — transform needs only pc.
    from spark_rapids_ml_tpu.models.pca import PCA, PCAModel

    x = rng.normal(size=(200, 12))
    model = PCA(mesh=mesh8).setInputCol("features").setK(3).fit({"features": x})
    data = model._model_data()
    del data["explainedVariance"]  # simulate a legacy save
    legacy = PCAModel._from_model_data(model.uid, data)
    assert legacy.explainedVariance is None
    out = legacy.transform({"features": x})
    np.testing.assert_allclose(
        out["pca_features"], model.transform({"features": x})["pca_features"]
    )


def test_legacy_model_resave_roundtrip(rng, mesh8, tmp_path):
    # A legacy-loaded model (no explainedVariance) re-saved and re-loaded
    # must keep explainedVariance None — not decay into a 0-d nan.
    from spark_rapids_ml_tpu.models.pca import PCA, PCAModel

    x = rng.normal(size=(100, 8))
    model = PCA(mesh=mesh8).setInputCol("features").setK(2).fit({"features": x})
    data = model._model_data()
    del data["explainedVariance"]
    legacy = PCAModel._from_model_data(model.uid, data)
    path = str(tmp_path / "legacy")
    legacy.save(path)
    again = PCAModel.load(path)
    assert again.explainedVariance is None
    np.testing.assert_allclose(again.pc, model.pc)


# ---------------------------------------------------------------------------
# The fold's one-read kernel (ops/gram.streaming_update): gate, counter, and
# the mask contract of the two fit paths that call it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tpu,use_pallas,cd,rows,d,want", [
    (False, True, "bfloat16", 65536, 2048, False),  # the CPU, as every other test runs
    (True, True, "bfloat16", 65536, 2048, True),    # the benchmark's fold on the chip
    (True, True, "bfloat16", 16384, 2048, True),    # Spark's 10,000 rows in their bucket
    (True, True, "bfloat16", 4096, 4096, True),     # the widest accumulator VMEM holds
    (True, False, "bfloat16", 65536, 2048, False),  # use_pallas off
    (True, True, "float32", 65536, 2048, False),    # float32 compute keeps gram_pallas / XLA
    (True, True, "bfloat16", 65536, 200, False),    # d off the 128-lane grid
    (True, True, "bfloat16", 65536, 8192, False),   # a (d, d) accumulator over VMEM
    (True, True, "bfloat16", 1000, 2048, False),    # shard rows not in whole blocks
], ids=["cpu", "chip", "chip_small_fold", "chip_d4096", "pallas_off", "float32_compute",
        "odd_width", "d8192", "ragged_rows"])
def test_fused_fold_gate(monkeypatch, tpu, use_pallas, cd, rows, d, want):
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    monkeypatch.setattr(config, "backend_is_tpu", lambda: tpu)
    assert gram_ops._fused_fold_applicable((rows, d), cd, use_pallas) is want


def _fold_paths():
    from spark_rapids_ml_tpu.utils import metrics

    c = metrics.counter("srml_gram_fold_path_total")
    return {p: c.value(path=p) for p in ("fused", "xla")}


@pytest.mark.parametrize("d,path", [(128, "fused"), (72, "xla")])
def test_fold_path_counter_counts_one_a_dispatch(gram_fused_on_cpu, rng, mesh1, d, path):
    """`srml_gram_fold_path_total{path}`: one a dispatch of
    `gram.streaming_update`, under the body the program was built with."""
    import jax.numpy as jnp
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    m = 1024
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    mask = jnp.ones((m,), jnp.float32)
    upd = gram_ops._streaming_update_cached(mesh1, "bfloat16", "float32", True)
    s = gram_ops.init_stats(d, accum_dtype="float32")
    before = _fold_paths()
    s = upd(s, x, mask)
    mid = _fold_paths()
    s = upd(s, x, mask)
    after = _fold_paths()
    other = "xla" if path == "fused" else "fused"
    assert mid[path] - before[path] == 1 and after[path] - mid[path] == 1
    assert after[other] == before[other]
    assert bool(gram_fused_on_cpu) is (path == "fused")
    assert float(s[0]) == 2 * m


def _assert_prefix_masks(seen, n_data):
    """Every shard's mask is ones, then zeros: the fused body's contract."""
    assert seen
    for xs, ms in seen:
        for shard in np.asarray(ms).reshape(n_data, -1):
            assert set(np.unique(shard)) <= {0.0, 1.0}
            assert np.all(np.diff(shard) <= 0), "a valid row after a padded one"


def _recording(update, seen):
    def call(state, xs, ms):
        seen.append((xs, ms))
        return update(state, xs, ms)
    return call


@pytest.mark.parametrize("path", ["job", "stream"])
def test_fit_paths_keep_the_mask_contract(gram_fused_on_cpu, monkeypatch, rng, devices, path):
    """The daemon's `PCAJob.fold` (bucket padding) and `fit_pca_stream`
    (`shard_rows`) pad at the batch's tail, so every shard's valid rows are
    a prefix of the shard, and the fold through the one-read kernel gives
    the state (the model) its XLA body gives."""
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    mesh = make_mesh(data=2, model=1, devices=devices[:2])
    d, k = 128, 4
    scales = np.logspace(0.5, -1.5, d)
    batches = [(rng.normal(size=(n, d)) * scales + 0.25).astype(np.float32)
               for n in (700, 1023, 1024)]  # padded to 1,024: 512 rows a shard
    results = {}
    for use_pallas in (True, False):
        seen, kernel_calls = [], len(gram_fused_on_cpu)
        real = gram_ops.streaming_update
        monkeypatch.setattr(gram_ops, "streaming_update",
                            lambda mesh, *a, **kw: _recording(real(mesh, *a, **kw), seen))
        with config.option("use_pallas", use_pallas), \
                config.option("compute_dtype", "bfloat16"), \
                config.option("accum_dtype", "float32"):
            if path == "job":
                from spark_rapids_ml_tpu.serve.daemon import _Job

                job = _Job("pca", d, mesh, {})
                for b in batches:
                    job.fold(b, None)
                results[use_pallas] = [np.asarray(a, np.float64) for a in job.state]
            else:
                sol = fit_pca_stream(batches[1:], k=k, n_cols=d, mesh=mesh)
                results[use_pallas] = [np.abs(sol.pc), sol.explained_variance, sol.mean]
        monkeypatch.setattr(gram_ops, "streaming_update", real)
        _assert_prefix_masks(seen, 2)
        assert (len(gram_fused_on_cpu) > kernel_calls) is use_pallas
    assert not any(c["seeded"] for c in gram_fused_on_cpu)  # two data devices: psum, then add
    fused, xla = results[True], results[False]
    if path == "job":
        assert fused[0] == xla[0] == 700 + 1023 + 1024
        np.testing.assert_allclose(fused[1], xla[1], rtol=0, atol=1e-3)
        np.testing.assert_allclose(fused[2], xla[2], rtol=0, atol=2e-2)
    else:
        np.testing.assert_allclose(fused[0], xla[0], atol=2e-4)
        np.testing.assert_allclose(fused[1], xla[1], atol=1e-6)
        np.testing.assert_allclose(fused[2], xla[2], atol=1e-6)
