"""`pca_from_gram_host`: the partial solve (one tridiagonalisation, all d
eigenvalues, k eigenvectors) and the full `np.linalg.eigh` it gives way to
above the k/d crossover, both held to the plain computation they replaced:
full-spectrum `eigh`, then order, sign flip and σ over all d columns."""

import functools

import numpy as np
import pytest
import scipy.linalg.lapack

from spark_rapids_ml_tpu.ops import eigh as eigh_ops
from spark_rapids_ml_tpu.ops.eigh import pca_from_gram_host
from spark_rapids_ml_tpu.utils import metrics

SHAPES = [(64, 1), (64, 64), (257, 5), (512, 32), (2048, 32)]
SPECTRA = ["decaying", "dead_features", "leading_cluster", "negative_rounding"]
#: eigenvalues closer than this (relative to the largest) are compared as
#: one spanned subspace: a vector's error is eps * |A| / gap
GAP = 1e-7


def _reference(gram, k):
    """The parent commit's `pca_from_gram_host`, line for line."""
    w, v = np.linalg.eigh(np.asarray(gram, dtype=np.float64))
    w, v = w[::-1], v[:, ::-1]
    idx = np.argmax(np.abs(v), axis=0)
    signs = np.where(v[idx, np.arange(v.shape[1])] < 0, -1.0, 1.0)
    v = v * signs
    s = np.sqrt(np.clip(w, 0, None))
    ev = s / max(s.sum(), 1e-300)
    return v[:, :k], ev[:k], s


@functools.lru_cache(maxsize=None)
def _basis(d):
    q, _ = np.linalg.qr(np.random.default_rng(d).normal(size=(d, d)))
    return q


def _gram(d, k, spectrum):
    """A symmetric (d, d) float64 matrix with the named spectrum, at the
    scale of a Gram of ~1e4 rows."""
    lam = np.geomspace(1.0, 1e-5, d)
    live = np.arange(d)
    if spectrum == "dead_features":
        # the last quarter of the features are constant zero: their rows
        # and columns of the Gram are exactly zero, and (a Householder
        # reduction never mixes a trailing zero block in) so are d // 4
        # eigenvalues, in either route
        live = np.arange(d - d // 4)
        lam = np.geomspace(1.0, 1e-5, len(live))
    elif spectrum == "leading_cluster":
        c = min(k, 3)
        lam = np.concatenate([1.0 - 1e-13 * np.arange(c), np.geomspace(0.5, 1e-5, d - c)])
    elif spectrum == "negative_rounding":
        lam[-3:] = [-1e-9, -2e-9, -3e-9]  # what rounding leaves of a zero
    q = _basis(len(live))
    a = np.zeros((d, d))
    a[np.ix_(live, live)] = (q * (1e4 * lam)) @ q.T
    return 0.5 * (a + a.T)


def _groups(w, k):
    """Runs of the first k (descending) eigenvalues that lie within GAP of
    their neighbour, as index arrays; a run that k cuts through is left
    out, for the subspace its kept part spans is not defined."""
    near = np.diff(w) > -GAP * w[0]  # near[i]: w[i] and w[i + 1] are one cluster
    out, start = [], 0
    for i in range(k):
        if i == len(w) - 1 or not near[i]:
            out.append(np.arange(start, i + 1))
            start = i + 1
    return out


def _solves(path):
    return metrics.counter("srml_pca_finalize_solves_total").value(path=path)


@pytest.mark.parametrize("spectrum", SPECTRA)
@pytest.mark.parametrize("d,k", SHAPES)
def test_agrees_with_the_full_width_computation(d, k, spectrum):
    g = _gram(d, k, spectrum)
    g0 = g.copy()
    pc, ev, s = pca_from_gram_host(g, k)
    assert np.array_equal(g, g0)
    ref_pc, ref_ev, ref_s = _reference(g0, k)
    for out, shape in ((pc, (d, k)), (ev, (k,)), (s, (d,))):
        assert out.shape == shape and out.dtype == np.float64
    # pc is the model's: an array of its own, not a view into a d x d parent
    assert pc.base is None and pc.flags.c_contiguous
    assert np.abs(s - ref_s).max() <= 1e-10 * ref_s[0]
    assert np.all(np.diff(s) <= 0) and s[-1] >= 0
    assert np.array_equal(s == 0, ref_s == 0)  # clipped where the reference clips
    if spectrum == "dead_features":
        assert np.count_nonzero(s == 0) >= d // 4
    elif spectrum == "negative_rounding":
        assert np.count_nonzero(s == 0) == 3
    assert np.abs(ev - ref_ev).max() <= 1e-10
    assert np.array_equal(ev, s[:k] / s.sum())  # the denominator runs over all d
    groups = _groups(ref_s**2, k)
    assert groups, "nothing was compared"
    for grp in groups:
        cos = np.linalg.svd(ref_pc[:, grp].T @ pc[:, grp], compute_uv=False)
        assert cos.min() >= 1 - 1e-10, (grp, cos.min())
    # the reference's sign rule, on every returned column
    top = pc[np.argmax(np.abs(pc), axis=0), np.arange(k)]
    assert np.all((top > 0) | np.all(pc == 0, axis=0))
    assert np.abs(np.linalg.norm(pc, axis=0) - 1).max() < 1e-12


def _as_input(g, kind):
    if kind == "float32":
        return g.astype(np.float32)
    if kind == "float64_fortran":
        return np.asfortranarray(g)
    if kind == "float64_readonly":
        g = g.copy()
        g.setflags(write=False)
        return g
    return g.copy()


@pytest.mark.parametrize("k", [4, 64])
@pytest.mark.parametrize(
    "kind", ["float32", "float64_c", "float64_fortran", "float64_readonly"])
def test_a_callers_array_is_never_written(kind, k):
    g = _as_input(_gram(64, k, "decaying"), kind)
    g0, flags0 = g.copy(), (g.flags.c_contiguous, g.flags.writeable)
    pc, ev, s = pca_from_gram_host(g, k)
    assert np.array_equal(g, g0) and g.dtype == g0.dtype
    assert (g.flags.c_contiguous, g.flags.writeable) == flags0
    assert not np.shares_memory(pc, g)
    ref_pc, _, ref_s = _reference(g0, k)
    assert np.abs(s - ref_s).max() <= 1e-10 * ref_s[0]
    assert np.all(np.abs(np.sum(pc * ref_pc, axis=0)) >= 1 - 1e-10)


def test_an_array_handed_over_gives_the_same_model():
    g = _gram(257, 5, "decaying")
    kept = pca_from_gram_host(g, 5)
    given = pca_from_gram_host(g.copy(), 5, overwrite_gram=True)
    for a, b in zip(kept, given):
        assert np.array_equal(a, b)


def test_the_route_is_chosen_by_k_over_d_and_counted():
    d = 64
    g = _gram(d, 1, "decaying")
    k_partial = int(eigh_ops.PARTIAL_SOLVE_MAX_K_OVER_D * d)
    assert 1 <= k_partial < d
    before = _solves("partial"), _solves("full")
    pca_from_gram_host(g, k_partial)
    assert (_solves("partial"), _solves("full")) == (before[0] + 1, before[1])
    pca_from_gram_host(g, k_partial + 1)
    assert (_solves("partial"), _solves("full")) == (before[0] + 1, before[1] + 1)
    pca_from_gram_host(g, d)
    assert (_solves("partial"), _solves("full")) == (before[0] + 1, before[1] + 2)


@pytest.mark.parametrize("wrapper", ["dsytrd", "dsterf", "dstemr", "dormqr"])
@pytest.mark.parametrize("overwrite", [False, True])
def test_a_lapack_step_that_reports_info_falls_back_to_the_full_solve(
        monkeypatch, wrapper, overwrite):
    """Whatever step fails, the array the partial solve worked in still
    holds the matrix `np.linalg.eigh` reads: the result is the full
    solve's, bit for bit, and the finalize counts as `full`."""
    real = getattr(scipy.linalg.lapack, wrapper)
    calls = []

    def failing(*args, **kwargs):
        calls.append(wrapper)
        return real(*args, **kwargs)[:-1] + (1,)  # info is last

    monkeypatch.setattr(scipy.linalg.lapack, wrapper, failing)
    d, k = 257, 5
    g = _gram(d, k, "decaying")
    g0 = g.copy()
    before = _solves("partial"), _solves("full")
    pc, ev, s = pca_from_gram_host(g.copy() if overwrite else g, k,
                                   overwrite_gram=overwrite)
    assert calls
    assert (_solves("partial"), _solves("full")) == (before[0], before[1] + 1)
    assert np.array_equal(g, g0)
    ref_pc, ref_ev, ref_s = _reference(g0, k)
    assert np.array_equal(s, ref_s) and np.array_equal(ev, ref_ev)
    assert np.array_equal(pc, ref_pc) and pc.base is None
