"""Pallas kernel parity tests (interpret mode — no TPU needed)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops.pallas_kernels import assign_min_dist_pallas, gram_pallas


def test_gram_parity(rng):
    n, d = 1024, 256
    x = rng.normal(size=(n, d)).astype(np.float32)
    mask = np.ones((n,), dtype=np.float32)
    mask[-37:] = 0.0  # padding rows
    out = np.asarray(gram_pallas(x, mask, block_n=256, block_d=128, interpret=True))
    xm = x * mask[:, None]
    ref = xm.T @ xm
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-2)


def test_gram_block_validation(rng):
    x = rng.normal(size=(100, 64)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        gram_pallas(x, np.ones(100, np.float32), block_n=64, block_d=64, interpret=True)


def test_gram_colsum_parity(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

    n, d = 1024, 256
    x = rng.normal(size=(n, d)).astype(np.float32)
    for n_valid in (n, 700):  # full batch + boundary-straddling partial block
        g, cs, cnt = gram_colsum_pallas(x, n_valid, block_n=256, interpret=True)
        xv = x[:n_valid]
        np.testing.assert_allclose(np.asarray(g), xv.T @ xv, rtol=1e-5, atol=1e-2)
        np.testing.assert_allclose(
            np.asarray(cs), xv.sum(axis=0), rtol=1e-5, atol=1e-2
        )
        assert float(cnt) == float(n_valid)


@pytest.mark.kernels
def test_gram_colsum_seeded_state(rng):
    """The one-dispatch streaming update: accumulators SEEDED from the
    donated (gram, colsum, count) state must equal state + batch stats —
    the fusion that removes the per-batch XLA state add."""
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

    n, d = 512, 128
    x = rng.normal(size=(n, d)).astype(np.float32)
    g0 = rng.normal(size=(d, d)).astype(np.float32)
    cs0 = rng.normal(size=(d,)).astype(np.float32)
    state = (jnp.asarray(g0), jnp.asarray(cs0), jnp.asarray(37.0, jnp.float32))
    g, cs, cnt = gram_colsum_pallas(
        x, 300, block_n=256, state=state, interpret=True
    )
    xv = x[:300]
    np.testing.assert_allclose(np.asarray(g), g0 + xv.T @ xv, rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(cs), cs0 + xv.sum(0), rtol=1e-5, atol=1e-2)
    assert float(cnt) == 37.0 + 300


@pytest.mark.kernels
def test_gram_colsum_bf16_vs_f32_tolerance(rng):
    """bf16-input/f32-accumulate golden for the fused streaming kernel:
    the intended TPU speed mode must stay within GEMM-rounding tolerance
    of the f32 oracle on the SAME (bf16-rounded) data."""
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

    n, d = 512, 128
    x16 = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    x = np.asarray(x16, np.float32)  # the rounded values ARE the data
    g, cs, cnt = gram_colsum_pallas(x16, 300, block_n=256, interpret=True)
    xv = x[:300]
    np.testing.assert_allclose(np.asarray(g), xv.T @ xv, rtol=2e-2, atol=5e-1)
    np.testing.assert_allclose(np.asarray(cs), xv.sum(0), rtol=2e-2, atol=2e-1)
    assert float(cnt) == 300.0
    # PCA-components golden: the top-k eigenvectors of the bf16-kernel
    # centered Gram must span the f64 oracle's subspace (sign-invariant
    # |cos| per column — the PCASuite tolerance philosophy).
    k = 4
    n_v, mean = 300, xv.mean(0)
    gc = np.asarray(g, np.float64) - n_v * np.outer(mean, mean)
    ref = np.cov(xv.T.astype(np.float64))
    w1, v1 = np.linalg.eigh(gc / (n_v - 1))
    w2, v2 = np.linalg.eigh(ref)
    dots = np.abs(np.sum(v1[:, ::-1][:, :k] * v2[:, ::-1][:, :k], axis=0))
    assert np.all(dots > 1 - 5e-2), dots


def test_gram_colsum_block_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

    x = rng.normal(size=(100, 128)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        gram_colsum_pallas(x, 100, block_n=64, interpret=True)


def _bf16_stats(x, n_valid):
    """The plain statistic the fold is held to: rows rounded to bfloat16,
    everything after that in float64."""
    xv = np.asarray(jnp.asarray(x[:n_valid]).astype(jnp.bfloat16).astype(jnp.float32),
                    np.float64)
    return float(n_valid), xv.sum(axis=0), xv.T @ xv


def _assert_stats(got, want, scale_rows):
    """(count, colsum, gram) against `_bf16_stats`: the count exactly, the
    sums to float32 accumulation order of the same bfloat16 operands."""
    assert float(got[0]) == want[0]
    np.testing.assert_allclose(np.asarray(got[1]), want[1], rtol=0,
                               atol=2e-6 * max(scale_rows, 1) ** 0.5 * 8)
    np.testing.assert_allclose(np.asarray(got[2]), want[2], rtol=0,
                               atol=2e-6 * max(scale_rows, 1) * 4)


@pytest.mark.kernels
@pytest.mark.parametrize("seeded", [False, True], ids=["unseeded", "seeded"])
@pytest.mark.parametrize("n_valid", [1024, 700, 512, 300, 0],
                         ids=["all", "boundary", "edge", "padding_block", "none"])
def test_gram_colsum_casts_float32_rows_in_the_kernel(rng, seeded, n_valid):
    """float32 rows, bfloat16 compute: the tile is cast inside the kernel,
    the Gram AND the column sums are of the rounded rows — all rows, a
    boundary inside a block, on a block's edge, a whole block of padding,
    no rows — onto zeros and onto a seeded state."""
    from spark_rapids_ml_tpu.ops.pallas_kernels import gram_colsum_pallas

    n, d = 1024, 128
    x = (rng.normal(size=(n, d)) * 3 + 1).astype(np.float32)
    x[n_valid:] = 7.0  # padding that would show in every statistic
    c0, cs0, g0 = 37.0, rng.normal(size=(d,)).astype(np.float32), \
        rng.normal(size=(d, d)).astype(np.float32)
    state = (jnp.asarray(g0), jnp.asarray(cs0), jnp.asarray(c0, jnp.float32))
    g, cs, cnt = gram_colsum_pallas(
        jnp.asarray(x), n_valid, block_n=256, compute_dtype="bfloat16",
        state=state if seeded else None, interpret=True)
    want = _bf16_stats(x, n_valid)
    if seeded:
        want = (want[0] + c0, want[1] + cs0, want[2] + g0)
    _assert_stats((cnt, cs, g), want, n_valid)
    # ...and NOT of the unrounded rows: the cast happened
    if n_valid >= 300:
        xv = x[:n_valid].astype(np.float64)
        assert np.abs(np.asarray(g) - (g0 if seeded else 0) - xv.T @ xv).max() > 0.05


@pytest.mark.parametrize("n_dev", [1, 8], ids=["one_device", "eight_devices"])
def test_streaming_update_fused_matches_its_xla_body(gram_fused_on_cpu, rng, devices, n_dev):
    """`streaming_update` with the gate held (the kernel in interpret mode)
    against its XLA body over several accumulating batches, the padding
    straddling the last-but-one shard: seeded on one data device, unseeded
    with the three psums across eight."""
    from spark_rapids_ml_tpu.ops import gram as gram_ops
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=n_dev, model=1, devices=devices[:n_dev])
    m, d = 512 * max(n_dev, 3), 128
    n_valid = m - 512 - 100  # a part block (shard), then a block (shard) of padding
    mask = (np.arange(m) < n_valid).astype(np.float32)
    assert gram_ops._fused_fold_applicable((m // n_dev, d), "bfloat16", True)
    fused = gram_ops._streaming_update_cached(mesh, "bfloat16", "float32", True)
    xla = gram_ops._streaming_update_cached(mesh, "bfloat16", "float32", False)
    s_f = gram_ops.init_stats(d, accum_dtype="float32")
    s_x = gram_ops.init_stats(d, accum_dtype="float32")
    want = np.zeros(()), np.zeros((d,)), np.zeros((d, d))
    for i in range(3):
        x = (rng.normal(size=(m, d)) + 0.5).astype(np.float32)
        x[n_valid:] = 7.0
        s_f = fused(s_f, jnp.asarray(x), jnp.asarray(mask))
        s_x = xla(s_x, jnp.asarray(x), jnp.asarray(mask))
        want = [a + b for a, b in zip(want, _bf16_stats(x, n_valid))]
    # traced through the kernel (once for a fresh state, once for a folded one)
    assert gram_fused_on_cpu and all(
        c["seeded"] is (n_dev == 1) and c["x_dtype"] == np.float32  # cast in the kernel
        for c in gram_fused_on_cpu)
    assert s_f[2].dtype == jnp.float32
    _assert_stats(s_f, want, 3 * n_valid)
    # the XLA body (the CPU keeps the cast there and back) gives the same
    np.testing.assert_array_equal(np.asarray(s_f[0]), np.asarray(s_x[0]))
    np.testing.assert_allclose(np.asarray(s_f[1]), np.asarray(s_x[1]), rtol=0, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_f[2]), np.asarray(s_x[2]), rtol=0, atol=2e-2)


@pytest.mark.kernels
def test_dist_topk_parity(rng):
    # Exact fused distance+top-k vs a lexsort oracle: true clipped
    # distances, ascending order, (distance, id) tie-breaking on crafted
    # duplicate rows, masked rows -> (+inf, -1), non-multiple-of-8 shapes.
    from spark_rapids_ml_tpu.ops.pallas_kernels import dist_topk_pallas

    q, m, d, k = 65, 300, 24, 7
    qs = rng.normal(size=(q, d)).astype(np.float32)
    db = rng.normal(size=(m, d)).astype(np.float32)
    db[50] = db[201]  # duplicate rows straddling blocks: exact tie
    mask = np.ones(m, np.float32)
    mask[-17:] = 0.0
    ids = np.arange(m, dtype=np.int32)
    dk, ik = dist_topk_pallas(
        jnp.asarray(qs), jnp.asarray(db), ids, mask, k,
        block_m=64, block_q=32, interpret=True,
    )
    d2 = np.maximum(
        (qs**2).sum(1)[:, None] + (db**2).sum(1)[None, :] - 2 * qs @ db.T, 0
    )
    d2[:, mask == 0] = np.inf
    order = np.lexsort((np.broadcast_to(ids, d2.shape), d2), axis=1)[:, :k]
    np.testing.assert_array_equal(
        np.asarray(ik), np.take_along_axis(np.broadcast_to(ids, d2.shape), order, 1)
    )
    np.testing.assert_allclose(
        np.asarray(dk), np.take_along_axis(d2, order, 1), rtol=1e-4, atol=1e-3
    )
    assert np.all(np.diff(np.asarray(dk), axis=1) >= 0)


@pytest.mark.kernels
def test_dist_topk_missing_slots(rng):
    # Fewer valid rows than k: the tail must carry the documented
    # (+inf, -1) missing contract, exactly like the XLA masked path.
    from spark_rapids_ml_tpu.ops.pallas_kernels import dist_topk_pallas

    qs = rng.normal(size=(8, 16)).astype(np.float32)
    db = rng.normal(size=(10, 16)).astype(np.float32)
    mask = np.zeros(10, np.float32)
    mask[:4] = 1.0
    dk, ik = dist_topk_pallas(
        jnp.asarray(qs), jnp.asarray(db), np.arange(10, dtype=np.int32),
        mask, 7, block_m=8, block_q=8, interpret=True,
    )
    assert np.all(np.asarray(ik)[:, 4:] == -1)
    assert np.all(np.isinf(np.asarray(dk)[:, 4:]))
    assert np.all(np.asarray(ik)[:, :4] >= 0)


@pytest.mark.kernels
@pytest.mark.parametrize("q", [1, 63, 64, 65])
def test_dist_topk_bucket_boundary_dtype_ladder(rng, q):
    """kneighbors-index goldens at the serve bucket ladder boundaries
    (b=64: 1, b-1, b, b+1 — the PR 5 scheduler-test shape grid), per rung
    of the compute_dtype ladder: at EACH dtype the fused kernel's indices
    must equal the unfused sq_euclidean→top_k two-step's (same rounding,
    same (distance, id) tie order), and bf16 distances must stay within
    GEMM-rounding tolerance of the f32 ones. bf16-vs-f32 INDEX swaps at
    near-ties are the documented precision trade, not a kernel bug."""
    from spark_rapids_ml_tpu.ops.distances import sq_euclidean
    from spark_rapids_ml_tpu.ops.pallas_kernels import dist_topk_pallas

    m, d, k = 96, 32, 5
    qs = rng.normal(size=(q, d)).astype(np.float32)
    db = rng.normal(size=(m, d)).astype(np.float32)
    ids = np.arange(m, dtype=np.int32)
    mask = np.ones(m, np.float32)
    by_dtype = {}
    for dt in (jnp.float32, jnp.bfloat16):
        qd, dbd = jnp.asarray(qs, dt), jnp.asarray(db, dt)
        fd, fi = dist_topk_pallas(
            qd, dbd, ids, mask, k, block_m=32, block_q=32, interpret=True
        )
        d2 = sq_euclidean(qd, dbd, accum_dtype=jnp.float32)
        neg, pos = jax.lax.top_k(-d2, k)
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(pos))
        np.testing.assert_allclose(
            np.asarray(fd), np.maximum(-np.asarray(neg), 0), rtol=1e-5, atol=1e-4
        )
        by_dtype[np.dtype(dt).name] = np.asarray(fd)
    np.testing.assert_allclose(
        by_dtype["bfloat16"], by_dtype["float32"], rtol=5e-2, atol=0.5
    )


@pytest.mark.kernels
def test_streaming_update_seeds_the_donated_state_into_the_kernel(gram_fused_on_cpu, rng, mesh1):
    """The donated one-dispatch fold on a single data device: the state
    goes INTO the kernel (no separate add of the (d, d) state after it),
    and the donated state's buffers are given up."""
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    m, d = 512, 128
    x = rng.normal(size=(m, d)).astype(np.float32)
    n_valid = m - 100
    mask = (np.arange(m) < n_valid).astype(np.float32)
    upd = gram_ops._streaming_update_cached(mesh1, "bfloat16", "float32", True)
    s = gram_ops.init_stats(d, accum_dtype="float32")
    for _ in range(3):
        donated = s
        s = upd(s, jnp.asarray(x), jnp.asarray(mask))
    assert gram_fused_on_cpu and all(c["seeded"] for c in gram_fused_on_cpu), \
        "the seeded one-dispatch branch never ran"
    assert donated[2].is_deleted()
    want = _bf16_stats(x, n_valid)
    _assert_stats(s, [3 * w for w in want], 3 * n_valid)


def test_assign_parity(rng):
    m, d, k = 512, 32, 128
    x = rng.normal(size=(m, d)).astype(np.float32)
    centers = rng.normal(size=(k, d)).astype(np.float32)
    idx, part_d = assign_min_dist_pallas(
        x, centers, block_m=128, block_k=64, interpret=True
    )
    d2 = (
        np.sum(x**2, 1)[:, None]
        - 2 * x @ centers.T
        + np.sum(centers**2, 1)[None, :]
    )
    ref_idx = np.argmin(d2, axis=1)
    np.testing.assert_array_equal(np.asarray(idx), ref_idx)
    # partial distance + ||x||^2 == true min distance
    full = np.asarray(part_d) + np.sum(x**2, 1)
    np.testing.assert_allclose(full, d2.min(axis=1), rtol=1e-4, atol=1e-2)


def test_lloyd_step_parity(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import lloyd_step_pallas

    m, d, k, k_pad = 1024, 128, 60, 128
    # well-separated clusters: argmin margins >> f32 GEMM error
    centers = (rng.normal(size=(k, d)) * 10).astype(np.float32)
    lab = rng.integers(0, k, size=m)
    x = (centers[lab] + 0.01 * rng.normal(size=(m, d))).astype(np.float32)
    cpad = np.zeros((k_pad, d), np.float32)
    cpad[:k] = centers
    for n_valid in (m, 700):  # full + boundary-straddling partial block
        sums, counts, cost = lloyd_step_pallas(
            x, cpad, n_valid, k=k, block_n=256, interpret=True
        )
        ref_cost = np.sum((x[:n_valid] - centers[lab[:n_valid]]) ** 2, dtype=np.float64)
        # the Gram trick cancels ‖x‖² ≈ 12,800 a row in float32: ~1e-4 a row
        np.testing.assert_allclose(float(cost), ref_cost, rtol=0, atol=5e-4 * n_valid)
        ref_sums = np.zeros((k, d))
        ref_counts = np.zeros(k)
        np.add.at(ref_sums, lab[:n_valid], x[:n_valid])
        np.add.at(ref_counts, lab[:n_valid], 1)
        np.testing.assert_allclose(np.asarray(counts)[:k], ref_counts)
        np.testing.assert_allclose(np.asarray(sums)[:k], ref_sums, rtol=1e-4, atol=1e-2)
        # Dead-lane contract: invalid rows of processed blocks are routed
        # to lane k (cheaper than a (bn, k_pad) row mask); that lane's
        # sums/counts carry their garbage and are DISCARDED by callers
        # (models/kmeans slices [:k]). Other padded lanes never win.
        processed = -(-min(n_valid, m) // 256) * 256
        assert float(np.asarray(counts)[k]) == float(processed - n_valid)
        assert float(np.asarray(counts)[k + 1:].sum()) == 0.0
        np.testing.assert_allclose(np.asarray(sums)[k + 1:], 0.0, atol=1e-6)


def test_lloyd_step_block_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import lloyd_step_pallas

    x = rng.normal(size=(100, 128)).astype(np.float32)
    c = rng.normal(size=(128, 128)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        lloyd_step_pallas(x, c, 100, k=100, block_n=64, interpret=True)


def test_newton_stats_parity(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import newton_stats_pallas

    n, d = 1024, 256
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-100:] = 0.0  # arbitrary masked rows, not a block boundary
    w = (rng.normal(size=(d,)) / np.sqrt(d)).astype(np.float32)
    b = np.float32(0.3)
    gw, gb, hww, hwb, hbb = newton_stats_pallas(
        x, y, mask, w, b, block_n=256, interpret=True
    )
    z = x @ w + b
    p = 1.0 / (1.0 + np.exp(-z))
    r = (p - y) * mask
    wgt = np.maximum(p * (1.0 - p), 1e-10) * mask
    np.testing.assert_allclose(np.asarray(gw), x.T @ r, rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(float(gb), r.sum(), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(
        np.asarray(hww), (x * wgt[:, None]).T @ x, rtol=1e-4, atol=1e-2
    )
    np.testing.assert_allclose(np.asarray(hwb), x.T @ wgt, rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(float(hbb), wgt.sum(), rtol=1e-4, atol=1e-2)


def test_newton_stats_parity_bf16(rng):
    """The production mode: the fused fit path only engages the kernel at
    compute_dtype=bfloat16 (models/logistic_regression._pallas_newton_applicable),
    so parity must hold for bf16-stored x with its own rounding."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.pallas_kernels import newton_stats_pallas

    n, d = 512, 256
    x16 = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    x = np.asarray(x16, np.float32)  # the rounded values ARE the data
    y = (rng.random(n) > 0.5).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-60:] = 0.0
    w = (rng.normal(size=(d,)) / np.sqrt(d)).astype(np.float32)
    b = np.float32(-0.2)
    gw, gb, hww, hwb, hbb = newton_stats_pallas(
        x16, y, mask, w, b, block_n=256, interpret=True
    )
    # Oracle mirrors the kernel's bf16 rounding points: w and the
    # residual/weight operands round to bf16 before their GEMMs.
    w16 = np.asarray(jnp.asarray(w, jnp.bfloat16), np.float32)
    z = x @ w16 + b
    p = 1.0 / (1.0 + np.exp(-z))
    r16 = np.asarray(jnp.asarray((p - y) * mask, jnp.bfloat16), np.float32)
    wgt = np.maximum(p * (1.0 - p), 1e-10) * mask
    wgt16 = np.asarray(jnp.asarray(wgt, jnp.bfloat16), np.float32)
    np.testing.assert_allclose(np.asarray(gw), x.T @ r16, rtol=2e-2, atol=2e-1)
    np.testing.assert_allclose(float(gb), ((p - y) * mask).sum(), rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(
        np.asarray(hww), (x * wgt16[:, None]).T @ x, rtol=2e-2, atol=5e-1
    )
    np.testing.assert_allclose(np.asarray(hwb), x.T @ wgt16, rtol=2e-2, atol=2e-1)
    np.testing.assert_allclose(float(hbb), wgt.sum(), rtol=1e-3, atol=1e-2)


def test_newton_stats_block_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import newton_stats_pallas

    x = rng.normal(size=(100, 128)).astype(np.float32)
    with pytest.raises(ValueError, match="not divisible"):
        newton_stats_pallas(
            x, np.ones(100, np.float32), np.ones(100, np.float32),
            np.zeros(128, np.float32), 0.0, block_n=64, interpret=True,
        )


def test_ivf_scan_select_parity(rng):
    # Exact per-slot top-k vs a sort-based oracle, including: ties
    # (first-occurrence/lowest-position contract), padded-row 1e30
    # sentinels, maxlen and blk_k not multiples of 8, and adversarial
    # ascending/descending score orderings.
    from spark_rapids_ml_tpu.ops.pallas_kernels import ivf_scan_select_pallas

    nlist, C, d, maxlen, blk_k = 6, 24, 32, 19, 7
    qv = rng.normal(size=(nlist, C, d)).astype(np.float32)
    rows = rng.normal(size=(nlist, maxlen, d)).astype(np.float32)
    r2 = (rows**2).sum(-1).astype(np.float32)
    r2[2, 10:] = 1e30
    rows[2, 10:] = 0  # list with fewer valid rows than... still >= blk_k
    r2[4, 3:] = 1e30
    rows[4, 3:] = 0  # FEWER valid rows than blk_k: sentinels must emit
    rows[3, 5] = rows[3, 6]
    r2[3, 5] = r2[3, 6]  # exact tie -> lowest position wins
    # Adversarial orderings: make list 5's scores monotone per slot by
    # zeroing qv (scores = r2 alone) with ascending then descending r2.
    qv[5] = 0
    r2[5] = np.linspace(1.0, 2.0, maxlen, dtype=np.float32)

    bd, bp = ivf_scan_select_pallas(
        jnp.asarray(qv), jnp.asarray(rows), jnp.asarray(r2), blk_k,
        interpret=True,
    )
    scores = r2[:, None, :] - 2 * np.einsum("lcd,lmd->lcm", qv, rows)
    ref_p = np.argsort(scores, axis=2, kind="stable")[:, :, :blk_k]
    ref_d = np.take_along_axis(scores, ref_p, axis=2)
    np.testing.assert_allclose(
        np.transpose(np.asarray(bd), (0, 2, 1)), ref_d, rtol=1e-5, atol=1e-4
    )
    np.testing.assert_array_equal(np.transpose(np.asarray(bp), (0, 2, 1)), ref_p)
    # Ascending per-slot output contract.
    assert np.all(np.diff(np.asarray(bd), axis=1) >= 0)


def test_ivf_scan_select_blk_k_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import ivf_scan_select_pallas

    qv = np.zeros((2, 8, 16), np.float32)
    rows = np.zeros((2, 5, 16), np.float32)
    r2 = np.zeros((2, 5), np.float32)
    with pytest.raises(ValueError, match="blk_k"):
        ivf_scan_select_pallas(qv, rows, r2, 6, interpret=True)


def test_probe_select_parity(rng):
    # Exact per-query top-nprobe centroid probe vs a sort oracle: true
    # distances (the per-query norm term is included), ascending order,
    # first-occurrence ties, non-multiple-of-8 nlist.
    from spark_rapids_ml_tpu.ops.pallas_kernels import probe_select_pallas

    nlist, d, q, nprobe = 37, 24, 128, 5
    cent = rng.normal(size=(nlist, d)).astype(np.float32)
    qs = rng.normal(size=(q, d)).astype(np.float32)
    cent[7] = cent[11]  # duplicate centroid -> tie resolves to lower id
    ids, d2 = probe_select_pallas(
        jnp.asarray(cent), jnp.asarray(qs), nprobe, block_q=64, interpret=True
    )
    ref = ((qs[:, None, :] - cent[None]) ** 2).sum(-1)
    ref_ids = np.argsort(ref, axis=1, kind="stable")[:, :nprobe]
    np.testing.assert_array_equal(np.asarray(ids), ref_ids)
    np.testing.assert_allclose(
        np.asarray(d2), np.take_along_axis(ref, ref_ids, axis=1),
        rtol=1e-3, atol=1e-3,
    )
    assert np.all(np.diff(np.asarray(d2), axis=1) >= 0)


def test_probe_select_block_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import probe_select_pallas

    cent = np.zeros((8, 16), np.float32)
    with pytest.raises(ValueError, match="divisible"):
        probe_select_pallas(
            cent, np.zeros((600, 16), np.float32), 2, block_q=512,
            interpret=True,
        )


def test_linreg_stats_parity(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import linreg_stats_pallas

    n, d = 1024, 256
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-100:] = 0.0
    xtx, xty, sx, sy, syy, cnt = linreg_stats_pallas(
        x, y, mask, block_n=256, interpret=True
    )
    xm = x * mask[:, None]
    ym = y * mask
    np.testing.assert_allclose(np.asarray(xtx), xm.T @ xm, rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(xty), xm.T @ ym, rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(sx), xm.sum(0), rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(float(sy), ym.sum(), rtol=1e-5)
    np.testing.assert_allclose(float(syy), (ym**2).sum(), rtol=1e-5)
    assert float(cnt) == float(mask.sum())


def test_linreg_stats_fn_pallas_matches_xla(rng):
    # The sharded stats fn with the fused kernel forced on (interpret on
    # CPU) must match the XLA path to bf16-GEMM tolerance.
    from spark_rapids_ml_tpu.models.linear_regression import _normal_eq_stats_fn
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=4, model=1)
    n, d = 2048, 128
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    mask = np.ones((n,), np.float32)
    a = _normal_eq_stats_fn(mesh, "float32", "float32", False)(x, y, mask)
    b = _normal_eq_stats_fn(mesh, "float32", "float32", True)(x, y, mask)
    for va, vb in zip(a, b):
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(vb), rtol=1e-4, atol=1e-2
        )


def test_softmax_curvature_parity(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import softmax_curvature_pallas

    n, d, C = 1024, 128, 5  # C not a block_c multiple: exercises padding
    x = rng.normal(size=(n, d)).astype(np.float32)
    logits = rng.normal(size=(n, C))
    p = (np.exp(logits) / np.exp(logits).sum(1, keepdims=True)).astype(
        np.float32
    )
    mask = np.ones((n,), np.float32)
    mask[-200:] = 0.0
    pm = p * mask[:, None]
    hw, hwb = softmax_curvature_pallas(
        x, pm, block_n=256, block_c=2, interpret=True
    )
    assert hw.shape == (C, d, d) and hwb.shape == (C, d)
    for c in range(C):
        xw = x * pm[:, c : c + 1]
        np.testing.assert_allclose(
            np.asarray(hw[c]), xw.T @ x, rtol=1e-5, atol=1e-2
        )
        np.testing.assert_allclose(
            np.asarray(hwb[c]), xw.sum(0), rtol=1e-5, atol=1e-2
        )


def test_softmax_curvature_block_validation(rng):
    from spark_rapids_ml_tpu.ops.pallas_kernels import softmax_curvature_pallas

    with pytest.raises(ValueError, match="divisible"):
        softmax_curvature_pallas(
            np.zeros((600, 128), np.float32), np.zeros((600, 3), np.float32),
            block_n=512, interpret=True,
        )


def test_softmax_stats_fn_kernel_matches_xla(rng, mesh8):
    """The streamed multinomial stats with the shared-tile kernel forced
    on (interpret, CPU) must match the XLA per-class loop."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models.logistic_regression import (
        _stream_softmax_stats_cached,
        stream_softmax_zero_state,
    )
    from spark_rapids_ml_tpu.ops import pallas_kernels as pk
    from spark_rapids_ml_tpu.ops import gram as gram_ops

    n, d, C = 8192, 128, 4  # 8-way shard = 1024 rows: block-divisible
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, C, size=n).astype(np.float32)
    mask = np.ones((n,), np.float32)
    W = jnp.asarray(rng.normal(size=(d, C)) * 0.1, jnp.float32)
    b = jnp.zeros((C,), jnp.float32)
    with config.option("accum_dtype", "float32"), \
            config.option("compute_dtype", "float32"):
        ref_fn = _stream_softmax_stats_cached(
            mesh8, C, "float32", "float32", False
        )
        ref = ref_fn(
            stream_softmax_zero_state(d, C, jnp.float32), W, b,
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
        )
        # Force the kernel branch: pretend the backend gate passes and run
        # the kernel in interpret mode (CPU); record that it actually ran.
        ran = {"kernel": False}
        orig_ok = gram_ops._pallas_backend_ok
        orig_kernel = pk.softmax_curvature_pallas

        def spy_kernel(xx, pp, block_n=512, block_c=8, interpret=False):
            ran["kernel"] = True
            return orig_kernel(xx, pp, block_n=block_n, block_c=block_c,
                               interpret=True)

        gram_ops._pallas_backend_ok = lambda use=None: True
        pk.softmax_curvature_pallas = spy_kernel
        try:
            _stream_softmax_stats_cached.cache_clear()
            kern_fn = _stream_softmax_stats_cached(
                mesh8, C, "float32", "float32", True
            )
            got = kern_fn(
                stream_softmax_zero_state(d, C, jnp.float32), W, b,
                jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
            )
        finally:
            gram_ops._pallas_backend_ok = orig_ok
            pk.softmax_curvature_pallas = orig_kernel
            _stream_softmax_stats_cached.cache_clear()
    assert ran["kernel"], "gate did not select the shared-tile kernel"
    for va, vb in zip(ref, got):
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(vb), rtol=1e-4, atol=1e-2
        )


# ---------------------------------------------------------------------------
# The multinomial fold at a width the chip keeps rows minor:
# softmax_fold_pallas, its gate, and the programs that run it
# ---------------------------------------------------------------------------

SOFTMAX_STATE = ("gw", "gb", "hw", "hwb", "hbb", "loss", "n")


def _softmax_batch(seed: int, n: int, d: int, n_classes: int):
    """float32 rows, class labels, a mask with HOLES (not a prefix), a
    non-zero iterate and a non-zero running state."""
    from spark_rapids_ml_tpu.models.logistic_regression import stream_softmax_zero_state

    r = np.random.default_rng(seed)
    x = r.normal(size=(n, d)).astype(np.float32)
    W = (r.normal(size=(d, n_classes)) / np.sqrt(d)).astype(np.float32)
    b = (0.2 * r.normal(size=n_classes)).astype(np.float32)
    y = r.integers(0, n_classes, size=n).astype(np.float32)
    mask = (r.random(n) < 0.8).astype(np.float32)
    state = tuple(r.normal(size=z.shape).astype(np.float32)
                  for z in stream_softmax_zero_state(d, n_classes, "float32"))
    return state, W, b, x, y, mask


def _softmax_xla(mesh, n_classes, state, W, b, x, y, mask):
    """The XLA body of the shard function, float32, `use_pallas` off."""
    from spark_rapids_ml_tpu.models.logistic_regression import _stream_softmax_shard_fn

    f = _stream_softmax_shard_fn(mesh, n_classes, "float32", "float32", False)
    return [np.asarray(a) for a in f(*(jnp.asarray(a) for a in (*state, W, b, x, y, mask)))]


def _softmax_kernel(state, W, b, x, y, mask, **kw):
    from spark_rapids_ml_tpu.ops.pallas_kernels import softmax_fold_pallas

    out = softmax_fold_pallas(jnp.asarray(x).T, jnp.asarray(y), jnp.asarray(mask),
                              jnp.asarray(W), jnp.asarray(b), interpret=True, **kw)
    return [np.asarray(a) for a in out]


def _assert_same_softmax_stats(got, want, state):
    """The batch's ADDITION to each leaf against the XLA body's: the
    gradient, its intercept, Σ p_c, the border and n to the order of
    float32 additions (the loss: two float32 sums of the same terms, a few
    units in the last place apart), the curvature to one bfloat16
    product's (the CPU's XLA body multiplies in float32, the kernel as the
    chip does)."""
    for name, g, w_, s in zip(SOFTMAX_STATE, got, want, state):
        g, w_, s = (np.asarray(a, np.float64) for a in (g, w_, s))
        scale = max(np.abs(w_ - s).max(), 1e-30)
        tol = {"hw": 4e-3, "loss": 3e-7}.get(name, 2e-6)
        assert np.abs(g - w_).max() <= tol * scale, (name, np.abs(g - w_).max() / scale)


@pytest.mark.parametrize("d", [200, 1000])
@pytest.mark.parametrize("n_classes", [2, 3, 5])
def test_softmax_fold_matches_the_xla_body(mesh1, d, n_classes):
    """Widths off the lane grid (200: whole sublane tiles past d; 1000: a
    block of 24 zeroed features); the kernel's sums are the batch's own,
    its curvature lane-padded with zeros."""
    n = 1024
    batch = _softmax_batch(d + n_classes, n, d, n_classes)
    zero = tuple(np.zeros_like(s) for s in batch[0])
    want = _softmax_xla(mesh1, n_classes, zero, *batch[1:])
    got = _softmax_kernel(*batch, block_n=256)
    dp = -(-d // 128) * 128
    assert got[2].shape == (n_classes, dp, dp) and got[2].dtype == np.float32
    assert not got[2][:, d:].any() and not got[2][:, :, d:].any()
    got[2] = got[2][:, :d, :d]
    assert [g.shape for g in got] == [w_.shape for w_ in want]
    assert all(g.dtype == np.float32 for g in got)
    _assert_same_softmax_stats(got, want, zero)
    assert got[6] == batch[5].sum() and 0 < got[6] < n  # the mask has holes
    # The loss is XLA's reduction over the kernel's logits: within 1e-7 of
    # the float64 loss, or as close as the XLA body's own float32 sum.
    _, W, b, x, y, mask = batch
    z = x.astype(np.float64) @ W.astype(np.float64) + b
    top = z.max(axis=1)
    lse = top + np.log(np.exp(z - top[:, None]).sum(axis=1))
    loss = float(((lse - z[np.arange(n), y.astype(int)]) * mask).sum())
    assert abs(got[5] - loss) <= max(1e-7, 1.01 * abs(want[5] - loss) / loss) * loss


def test_softmax_fold_seeded_adds_to_the_running_blocks_alone():
    d, n, C = 200, 512, 3
    batch = _softmax_batch(1, n, d, C)
    plain = _softmax_kernel(*batch, block_n=256)
    seed = np.zeros((C, 256, 256), np.float32)
    seed[:, :d, :d] = batch[0][2]
    seeded = _softmax_kernel(*batch, block_n=256, hw=jnp.asarray(seed))
    np.testing.assert_allclose(seeded[2], seed + plain[2], rtol=0, atol=1e-4)
    assert not seeded[2][:, d:].any() and not seeded[2][:, :, d:].any()
    for i in (0, 1, 3, 4, 5, 6):
        np.testing.assert_array_equal(seeded[i], plain[i])


@pytest.mark.parametrize("what,kw,match", [
    ("rows", {"block_n": 384}, "not divisible"),
    ("block", {"block_n": 64}, "multiple of 128"),
    ("dtype", {}, "float32 rows"),
    ("seed", {"hw": np.zeros((3, 200, 200), np.float32)}, "lane-padded"),
    ("classes", {"hw": np.zeros((2, 256, 256), np.float32)}, "lane-padded"),
    ("budget", {}, "exceed the budget"),
])
def test_softmax_fold_refuses_what_it_cannot_fold(what, kw, match):
    d = 4200 if what == "budget" else 200
    state, W, b, x, y, mask = _softmax_batch(2, 512, d, 3)
    if what == "dtype":
        x = x.astype(np.float64)
    with pytest.raises(ValueError, match=match):
        _softmax_kernel(state, W, b, x, y, mask, **kw)


@pytest.fixture
def softmax_fused_on_cpu(monkeypatch):
    """Steer the multinomial fold onto its kernels here: the gates are told
    the backend is a TPU and both kernels run in interpret mode. The
    program has no option for this: the test does it."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(config, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pk, "softmax_fold_pallas",
                        functools.partial(pk.softmax_fold_pallas, interpret=True))
    monkeypatch.setattr(pk, "softmax_curvature_pallas",
                        functools.partial(pk.softmax_curvature_pallas, interpret=True))
    caches = (lg._stream_softmax_stats_cached, lg._stream_softmax_stats_group_cached)
    for c in caches:
        c.cache_clear()
    yield
    for c in caches:
        c.cache_clear()


@pytest.mark.parametrize("tpu,d,n,dtype,ad,path", [
    (True, 3000, 65536, np.float32, "float32", "fold"),  # the cell's batch
    (True, 200, 512, np.float32, "float32", "fold"),     # 200 < 256: rows minor on the chip
    (True, 1000, 1024, np.float32, "float32", "fold"),   # 1000 < 1024
    (True, 256, 1024, np.float32, "float32", "curvature"),  # on the lane grid: row-major
    (True, 1020, 1024, np.float32, "float32", "xla"),    # 1024 = 1024: a tie, row-major
    (False, 200, 512, np.float32, "float32", "xla"),     # the CPU backend
    (True, 200, 512, np.float64, "float32", "xla"),      # rows not float32
    (True, 200, 512, np.float32, "float64", "xla"),      # accumulator not float32
    (True, 200, 500, np.float32, "float32", "xla"),      # ragged shard rows
    (True, 4200, 512, np.float32, "float32", "xla"),     # the block over the VMEM budget
    (True, 3000, 262144, np.float32, "float32", "xla"),  # the shard's logits over it
])
def test_fused_softmax_fold_gate(softmax_fused_on_cpu, monkeypatch, tpu, d, n, dtype, ad, path):
    """Rows-minor widths take `softmax_fold_pallas`, widths on the lane grid
    keep `softmax_curvature_pallas`, everything else the XLA body; the
    binary gate answers these shapes as it did before the multinomial one
    existed (its own table: tests/test_logreg_fused_fold.py)."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg

    if not tpu:
        monkeypatch.setattr(config, "backend_is_tpu", lambda: False)
    fold = lg._fused_softmax_fold_applicable((n, d), dtype, ad, 3, True)
    curvature = lg._softmax_curv_kernel_applicable((n, d), ad, True)
    assert (fold, curvature) == (path == "fold", path == "curvature")
    assert not lg._fused_softmax_fold_applicable((n, d), dtype, ad, 3, False)
    binary = (tpu and dtype == np.float32 and ad == "float32" and n % 512 == 0
              and -(-d // 8) * 8 < -(-d // 128) * 128 and d <= 4096)
    assert lg._fused_newton_fold_applicable((n, d), dtype, ad, True) is binary


@pytest.mark.parametrize("d", [200, 1000])
def test_fused_softmax_body_matches_the_xla_body_from_a_running_state(
        softmax_fused_on_cpu, mesh1, d):
    from spark_rapids_ml_tpu.models import logistic_regression as lg

    n, C = 1024, 3
    batch = _softmax_batch(10 + d, n, d, C)
    assert lg._fused_softmax_fold_applicable((n, d), np.float32, "float32", C, True)
    f = lg._stream_softmax_shard_fn(mesh1, C, "float32", "float32", True)
    got = [np.asarray(a) for a in f(*(jnp.asarray(a) for a in (*batch[0], *batch[1:])))]
    assert [g.shape for g in got] == [s.shape for s in batch[0]]
    _assert_same_softmax_stats(got, _softmax_xla(mesh1, C, *batch), batch[0])


def test_fused_softmax_four_shards_give_the_one_device_fold(softmax_fused_on_cpu, mesh1, devices):
    """On four data devices the kernel is unseeded and the blocks meet the
    state after the psum; the mask's holes fall in every shard."""
    from spark_rapids_ml_tpu.models import logistic_regression as lg
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    n, d, C = 2048, 200, 3  # 512 rows a shard
    mesh4 = make_mesh(data=4, model=1, devices=devices[:4])
    batch = _softmax_batch(33, n, d, C)
    assert lg._fused_softmax_fold_applicable((n // 4, d), np.float32, "float32", C, True)
    f = lg._stream_softmax_shard_fn(mesh4, C, "float32", "float32", True)
    got = [np.asarray(a) for a in f(*(jnp.asarray(a) for a in (*batch[0], *batch[1:])))]
    _assert_same_softmax_stats(got, _softmax_xla(mesh1, C, *batch), batch[0])


def _softmax_path_counts():
    from spark_rapids_ml_tpu.utils import metrics

    c = metrics.counter("srml_logreg_softmax_fold_path_total")
    return {p: c.value(path=p) for p in ("fused", "xla")}


@pytest.mark.parametrize("d", [200, 1000])
def test_fused_softmax_group_is_its_batches_fed_singly_and_counts_fused(
        softmax_fused_on_cpu, mesh1, d):
    """The group pads the running blocks once and slices them once; a call
    pads and slices every time (a zero pad and its slice are exact), so a
    group of three is three calls bit for bit — and each dispatch of either
    program counts once under `path=fused`."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.models import logistic_regression as lg

    n, C = 512, 3
    batches = [_softmax_batch(20 + i, n, d, C) for i in range(3)]
    state, W, b = batches[0][:3]
    with config.option("use_pallas", True):
        update = lg._stream_softmax_stats_cached(mesh1, C, "float32", "bfloat16", True)
        group = lg._stream_softmax_stats_group_cached(mesh1, C, "float32", "bfloat16", True)
    before = _softmax_path_counts()
    one = tuple(jnp.asarray(s) for s in state)
    for bt in batches:
        one = update(one, jnp.asarray(W), jnp.asarray(b), *(jnp.asarray(a) for a in bt[3:]))
    mid = _softmax_path_counts()
    xs, ys, ms = (tuple(jnp.asarray(bt[i]) for bt in batches) for i in (3, 4, 5))
    three = group(tuple(jnp.asarray(s) for s in state), jnp.asarray(W), jnp.asarray(b),
                  xs, ys, ms)
    after = _softmax_path_counts()
    assert mid["fused"] - before["fused"] == 3 and after["fused"] - mid["fused"] == 1
    assert after["xla"] == before["xla"]
    for name, a, c in zip(SOFTMAX_STATE, one, three):
        assert a.shape == c.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c), err_msg=name)


def _hist_product(lhs, bins, n_bins):
    """The XLA body's product: the block's one-hot, then a matrix product."""
    c, db = bins.shape
    one_hot = jax.nn.one_hot(jnp.asarray(bins), n_bins, dtype=jnp.int8)
    return np.asarray(jnp.matmul(
        jnp.asarray(lhs), one_hot.reshape(c, db * n_bins),
        preferred_element_type=jnp.int32))


@pytest.mark.parametrize("m,c,db,n_bins,row_tile,out_tile_rows", [
    (7, 512, 8, 128, None, None),       # under int8's 32-row tile: padded, sliced
    (32, 512, 8, 128, None, None),      # one whole tile: no pad
    (210, 1024, 11, 128, 512, None),    # the root's height; 11 features: three padded to 16
    (420, 2048, 16, 128, None, None),   # one 2,048-row tile, two feature steps
    (63, 1536, 5, 256, None, None),     # two lane tiles of bins; 1,536 rows: 512-row tiles
    (64, 512, 8, 128, None, 64),        # the output tile's cap: exactly one part
    (65, 512, 8, 128, None, 64),        # one row over it: two parts of 64, the one-hot made twice
    (200, 1024, 9, 128, 512, 64),       # four parts (ceil(224 / 64)), ragged features, two row tiles
])
def test_hist_onehot_matmul_parity_bit_for_bit(rng, monkeypatch, m, c, db, n_bins,
                                               row_tile, out_tile_rows):
    """lhs x one_hot(bins) with the one-hot made in VMEM is XLA's product
    of the written-out one-hot to the bit (int8 into int32: whole numbers),
    on both sides of every line the kernel draws — the 32-row pad of the
    left operand, the 8-feature step, the output tile's cap on the rows a
    grid step holds, the row tile — with what the fold hands it: blanked
    columns (bin id -1: the ragged last feature block's), masked rows
    (an all-zero column of lhs), bin ids at 0 and at n_bins - 1."""
    from spark_rapids_ml_tpu.ops import pallas_kernels as pk

    if out_tile_rows:
        monkeypatch.setattr(
            pk, "HIST_ONEHOT_OUT_TILE_BYTES",
            4 * pk.HIST_ONEHOT_FEATURES * n_bins * out_tile_rows)
        assert pk.hist_onehot_tiles(m, c, n_bins)[0] <= out_tile_rows
    lhs = rng.integers(-128, 128, size=(m, c)).astype(np.int8)
    lhs[:, rng.random(c) < 0.3] = 0  # masked rows weigh nothing in any channel
    bins = rng.integers(0, n_bins, size=(c, db)).astype(np.int32)
    bins[::7, 0], bins[1::7, 0] = 0, n_bins - 1
    bins[:, -2:] = -1  # blanked columns: an all-zero one-hot
    got = np.asarray(pk.hist_onehot_matmul_pallas.__wrapped__(
        jnp.asarray(lhs), jnp.asarray(bins.T.copy()), n_bins=n_bins,
        row_tile=row_tile, interpret=True))
    want = _hist_product(lhs, bins, n_bins)
    assert got.dtype == np.int32 and got.shape == want.shape == (m, db * n_bins)
    np.testing.assert_array_equal(got, want)
    assert not got[:, -2 * n_bins:].any() and got[:, :n_bins].any()


@pytest.mark.parametrize("what,kw,match", [
    ("lhs", {"lhs": np.zeros((32, 512), np.float32)}, "int8"),
    ("bins", {"bins_t": np.zeros((8, 512), np.int8)}, "int32"),
    ("bins", {"bins_t": np.zeros((8, 256), np.int32)}, "int32"),
    ("n_bins", {"n_bins": 32}, "multiple of 128"),
    ("row_tile", {"row_tile": 384}, "not divisible"),
])
def test_hist_onehot_matmul_refuses_what_it_cannot_multiply(what, kw, match):
    from spark_rapids_ml_tpu.ops.pallas_kernels import hist_onehot_matmul_pallas

    args = {"lhs": np.zeros((32, 512), np.int8), "bins_t": np.zeros((8, 512), np.int32),
            "n_bins": 128, **kw}
    with pytest.raises(ValueError, match=match):
        hist_onehot_matmul_pallas(
            jnp.asarray(args.pop("lhs")), jnp.asarray(args.pop("bins_t")),
            interpret=True, **args)


@pytest.mark.parametrize("m,c,n_bins,want", [
    (210, 65536, 128, (224, 2048)),    # the cell's root
    (3360, 65536, 128, (3360, 2048)),  # its halved depth 5: 105 whole 32-row tiles
    (3360, 1536, 128, (3360, 512)),    # the largest power of two that divides the chunk
    (6720, 65536, 128, (3360, 2048)),  # over 4,096 rows: two equal parts
    (3360, 65536, 256, (1696, 2048)),  # 256 bins: 2,048 rows a part
])
def test_hist_onehot_tiles_from_the_height_the_chunk_and_the_bins(m, c, n_bins, want):
    from spark_rapids_ml_tpu.ops.pallas_kernels import hist_onehot_tiles, hist_onehot_vmem_limit

    assert hist_onehot_tiles(m, c, n_bins) == want
    # the kernel claims what its tiles take, never the 96 MiB and over under
    # which XLA's own VMEM-resident arrays were overwritten on the chip
    limit = hist_onehot_vmem_limit(*want, n_bins)
    assert 16 * 2**20 <= limit <= 72 * 2**20
    assert limit == 16 * 2**20 or limit >= 1.25 * want[0] * (2 * 8 * n_bins * 4 + 2 * want[1])


def test_hist_onehot_vmem_limit_at_the_largest_tiles_the_chooser_gives():
    from spark_rapids_ml_tpu.ops.pallas_kernels import hist_onehot_tiles, hist_onehot_vmem_limit

    for n_bins in (128, 256):
        worst = max(
            hist_onehot_vmem_limit(*hist_onehot_tiles(m, 65536, n_bins), n_bins)
            for m in range(32, 20000, 32))
        assert worst <= 72 * 2**20
