"""Eigendecomposition finalize stage: the reference's ``calSVD`` in XLA.

The reference's native ``calSVD`` (rapidsml_jni.cu:215-269) runs, on one GPU:
cuSOLVER ``eigDC`` on the n×n Gram → column/row reversal to descending order
→ ``seqRoot`` (σ = √λ) → ``signFlip``. This module is the XLA equivalent —
``jnp.linalg.eigh`` plus pure-functional reorder/sqrt/sign-flip, all fused
under one jit. Where the reference serializes this to a dedicated
single-task Spark job shipping the matrix over the wire
(RapidsRowMatrix.scala:74-86), here the Gram is already on device and the
finalize compiles into the same program as the reduction.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.utils import metrics
from spark_rapids_ml_tpu.utils.profiling import trace_span


def eigh_descending(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric eigendecomposition, eigenvalues descending.

    Equivalent of eigDC + colReverse/rowReverse (rapidsml_jni.cu:251-253);
    ``jnp.linalg.eigh`` returns ascending order, so flip.
    """
    w, v = jnp.linalg.eigh(a)
    return w[::-1], v[:, ::-1]


def sign_flip(u: jax.Array) -> jax.Array:
    """Deterministic eigenvector signs: flip any column whose largest-|x|
    element is negative.

    Exact semantics of the reference's Thrust kernel (rapidsml_jni.cu:35-61):
    scan for the max absolute value with strict ``>`` (first occurrence wins,
    matching ``argmax``), flip the column iff that element is < 0 (an
    all-zero column is left alone).
    """
    idx = jnp.argmax(jnp.abs(u), axis=0)
    vals = u[idx, jnp.arange(u.shape[1])]
    signs = jnp.where(vals < 0, -1.0, 1.0).astype(u.dtype)
    return u * signs[None, :]


def explained_variance_reference(eigvals: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Reference semantics: σ = √λ (clipped at 0), ratio = σᵢ / Σσ.

    The reference normalizes the *square roots* of the Gram eigenvalues
    (seqRoot at rapidsml_jni.cu:254, then ``s.data.map(_ / eigenSum)`` at
    RapidsRowMatrix.scala:91-93). Note this differs from Spark MLlib's CPU
    PCA, which normalizes covariance eigenvalues; we reproduce the reference
    exactly and expose the eigenvalue ratio separately.
    """
    s = jnp.sqrt(jnp.clip(eigvals, 0.0))
    return s, s / jnp.sum(s)


def explained_variance_ratio(eigvals: jax.Array) -> jax.Array:
    """Spark MLlib / sklearn semantics: λᵢ / Σλ (for cross-checking)."""
    w = jnp.clip(eigvals, 0.0)
    return w / jnp.sum(w)


def pca_from_gram(gram: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full calSVD-equivalent finalize: Gram → (pc (n,k), explained_var (k,), σ (n,)).

    Output contract matches computePrincipalComponentsAndExplainedVariance
    (RapidsRowMatrix.scala:59-102): top-k eigenvector columns, sign-flipped;
    explained variance = σ/Σσ sliced to k.
    """
    w, v = eigh_descending(gram)
    v = sign_flip(v)
    s, ev = explained_variance_reference(w)
    return v[:, :k], ev[:k], s


def topk_eig_subspace(
    gram: jax.Array,
    k: int,
    oversample: int = 32,
    iters: int = 12,
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Top-(k+p) eigenpairs of a PSD matrix by blocked subspace iteration
    (randomized PCA, Halko et al. 2011, alg. 4.4 specialized to a Gram).

    TPU-native alternative to the d×d ``eigh``: the only O(d²·m) work is
    ``G @ V`` — a large dense matmul the MXU runs at full rate — plus a thin
    (d, m) QR re-orthonormalization per iteration and one m×m Rayleigh–Ritz
    ``eigh`` at the end. Nothing larger than (d, m) is ever factorized, and
    the full decomposition the reference serialized to one GPU
    (``calSVD``, rapidsml_jni.cu:215-269) never runs.

    Convergence is (λ_{m}/λ_k)^iters for the k-th eigenvector — fast for
    the decaying spectra PCA users have, inaccurate for a flat spectrum
    (where principal directions are ill-defined anyway). Returns
    ``(ritz_vals (m,) descending, vectors (d, m))`` with m = k+oversample
    clamped to d.
    """
    from spark_rapids_ml_tpu.ops.gram import mm_precision

    d = gram.shape[0]
    m = min(k + oversample, d)
    v0 = jax.random.normal(jax.random.key(seed), (d, m), dtype=gram.dtype)

    with mm_precision(gram.dtype):

        def body(_, v):
            w = gram @ v
            q, _ = jnp.linalg.qr(w)
            return q

        v = jax.lax.fori_loop(0, iters, body, jnp.linalg.qr(v0)[0])
        gv = gram @ v
        b = v.T @ gv
        b = 0.5 * (b + b.T)
        wb, qb = jnp.linalg.eigh(b)  # m×m — tiny
        wb, qb = wb[::-1], qb[:, ::-1]
        return wb, v @ qb


def pca_from_gram_randomized(
    gram: jax.Array,
    k: int,
    oversample: int = 32,
    iters: int = 12,
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`pca_from_gram` contract via :func:`topk_eig_subspace`.

    Stays entirely on device and computes only a rank-(k+p) decomposition,
    so on TPU the finalize is a handful of MXU matmuls instead of a host
    round-trip carrying the d×d Gram. The reference-semantics explained
    variance (σᵢ/Σσ over ALL d values, rapidsml_jni.cu:254 +
    RapidsRowMatrix.scala:91-93) needs the unseen tail of the spectrum; it
    is estimated from the trace — residual Σλ spread uniformly over the
    d−m tail, a concave (upper-bound) approximation that vanishes for
    decaying spectra. Returned σ is (d,) with the tail filled by that
    uniform estimate.
    """
    d = gram.shape[0]
    wb, u = topk_eig_subspace(gram, k, oversample=oversample, iters=iters, seed=seed)
    m = wb.shape[0]
    u = sign_flip(u)
    w_top = jnp.clip(wb, 0.0)
    s_top = jnp.sqrt(w_top)
    resid = jnp.clip(jnp.trace(gram) - jnp.sum(w_top), 0.0)
    n_tail = max(d - m, 0)
    tail_each = jnp.where(n_tail > 0, jnp.sqrt(resid / jnp.maximum(n_tail, 1)), 0.0)
    sigma_sum = jnp.sum(s_top) + n_tail * tail_each
    ev = s_top / jnp.maximum(sigma_sum, jnp.finfo(gram.dtype).tiny)
    s_full = jnp.concatenate(
        [s_top, jnp.full((n_tail,), tail_each, dtype=s_top.dtype)]
    )
    return u[:, :k], ev[:k], s_full


def pca_from_gram_model_sharded(
    gram: jax.Array,
    k: int,
    mesh,
    oversample: int = 32,
    iters: int = 12,
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Model-parallel finalize (2112.09017-style distributed linear
    algebra): the (d, d) Gram stays sharded over the ``model`` mesh axis
    through the WHOLE eigensolve. Each device holds a (d/n_model, d)
    horizontal slab (exactly what ``gram.sharded_stats_2d``/``_ring``
    produce), ``G @ V`` runs as slab matmuls whose (d, k+p) results are
    the only full-width panels ever replicated, and the Rayleigh–Ritz
    system is m×m. This is how a d ≥ 8192 PCA fits where the replicated
    accumulator busts the per-device budget
    (:data:`~spark_rapids_ml_tpu.ops.gram.GRAM_DEVICE_BUDGET_BYTES`, the
    fit-path generalization of the Pallas ``GRAM_COLSUM_VMEM_BUDGET``
    ceiling) — sharding instead of rejection.

    Must run under jit (the sharding constraint is a trace-time
    annotation); same contract as :func:`pca_from_gram_randomized`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import MODEL_AXIS

    gram = jax.lax.with_sharding_constraint(
        gram, NamedSharding(mesh, P(MODEL_AXIS, None))
    )
    return pca_from_gram_randomized(
        gram, k, oversample=oversample, iters=iters, seed=seed
    )


#: Largest k/d at which the host finalize takes the partial solve (one
#: tridiagonalisation, all d eigenvalues, k eigenvectors). Above it LAPACK's
#: full ``dsyevd`` (``np.linalg.eigh``) is the faster route to the same
#: answer. Set on the safe side of the crossover measured on the chip's host
#: (PERF.md §6, PR 25).
PARTIAL_SOLVE_MAX_K_OVER_D = 0.125

_M_SOLVES = metrics.counter(
    "srml_pca_finalize_solves_total",
    "Host PCA finalizes by the LAPACK route they took: path=partial "
    "(tridiagonalise once, k eigenvectors) or path=full (np.linalg.eigh, "
    "also the fallback when a partial step reports info != 0)",
)


def _eigh_topk_partial(a, k: int):
    """All d eigenvalues (ascending) and the eigenvectors of the k largest
    (as columns, ascending) of the symmetric float64 C-ordered ``a``, which
    is OVERWRITTEN: Householder tridiagonalisation once (``dsytrd``),
    eigenvalues of the tridiagonal (``dsterf``), its k top eigenvectors
    (``dstemr``), back-transformed by the stored reflectors (``dormqr`` on
    the sub-block: scipy has no ``dormtr``).

    LAPACK works in ``a``'s own memory: a symmetric C-ordered array is its
    own Fortran-ordered transpose. ``lower=1`` there is ``a``'s upper
    triangle, so the strict lower triangle is never referenced; if any step
    reports ``info != 0`` the diagonal is put back and None returned, and
    ``np.linalg.eigh`` (which reads the lower triangle) still finds the
    matrix it was given.
    """
    import numpy as np
    from scipy.linalg import lapack

    d = a.shape[0]
    diag = a.diagonal().copy()
    lwork, info = lapack.dsytrd_lwork(d, lower=1)
    if info == 0:
        qt, td, te, tau, info = lapack.dsytrd(
            a.T, lower=1, lwork=int(lwork), overwrite_a=1
        )
    if info == 0:
        w, info = lapack.dsterf(td, te)
    if info == 0:
        te_n = np.zeros(d)  # dstemr wants e with n entries
        te_n[:-1] = te
        select = (2, 0.0, 0.0, d - k + 1, d)  # range 'I', il..iu from 1
        lwork, liwork, info = lapack.dstemr_lwork(td, te_n, *select)
    if info == 0:
        z, info = lapack.dstemr(
            td, te_n, *select, lwork=int(lwork), liwork=int(liwork)
        )[2:]
    if info == 0:
        # Q = H(1)..H(d-1) leaves row 0 alone; on rows 1: it is the Q of a
        # QR factorisation whose reflectors sit below the first subdiagonal
        refl = np.asfortranarray(qt[1:, :-1])
        zq = np.asfortranarray(z[1:, :k])
        work, info = lapack.dormqr("L", "N", refl, tau, zq, -1)[1:]
    if info == 0:
        zq, _, info = lapack.dormqr(
            "L", "N", refl, tau, zq, int(work[0]), overwrite_c=1
        )
    if info != 0:
        a.flat[:: d + 1] = diag
        return None
    v = np.empty((d, k))
    v[0] = z[0, :k]
    v[1:] = zq
    return w, v


def pca_from_gram_host(gram, k: int, overwrite_gram: bool = False):
    """Host (LAPACK, float64) version of :func:`pca_from_gram`.

    Used when the mesh's devices execute eigh poorly (TPU: eigh is an
    iterative algorithm that XLA compiles/executes badly for large d, while
    the d×d Gram is tiny to fetch). Architecturally this matches the
    reference, where the eig ran as its own single-device stage separate
    from the distributed reduction (RapidsRowMatrix.scala:70-86).

    The contract needs all d eigenvalues (σ is returned whole, Σσ is the
    ratio's denominator) but k eigenvectors, so while k ≤
    :data:`PARTIAL_SOLVE_MAX_K_OVER_D` · d the solve stops there
    (:func:`_eigh_topk_partial`); above it, and whenever a partial step
    reports ``info != 0``, it is ``np.linalg.eigh``. Both are exact direct
    float64 solves; ``srml_pca_finalize_solves_total{path}`` says which ran.
    The partial solve works in place: on a copy, unless ``overwrite_gram``
    hands over a float64 array the caller made for this call.

    Two child spans of the caller's ``eig finalize``: ``finalize.lapack``
    (every LAPACK call and nothing else) and ``finalize.post`` (on the k
    kept columns: descending order, sign flip; σ over all d, ratio). ``pc``
    owns its (d, k) memory.
    """
    import numpy as np

    a = np.asarray(gram, dtype=np.float64)
    d = a.shape[0]
    try_partial = k <= PARTIAL_SOLVE_MAX_K_OVER_D * d
    if try_partial and not (
        overwrite_gram and a.flags.c_contiguous and a.flags.writeable
    ):
        a = a.copy(order="C")
    with trace_span("finalize.lapack"):
        solved = _eigh_topk_partial(a, k) if try_partial else None
        path = "full" if solved is None else "partial"
        if solved is None:
            w, v = np.linalg.eigh(a)
            solved = w, v[:, d - k:]
    _M_SOLVES.inc(path=path)
    with trace_span("finalize.post"):
        w, v = solved
        pc = np.array(v[:, ::-1], order="C")
        idx = np.argmax(np.abs(pc), axis=0)
        pc *= np.where(pc[idx, np.arange(k)] < 0, -1.0, 1.0)
        s = np.sqrt(np.clip(w[::-1], 0, None))
        ev = s / max(s.sum(), 1e-300)
        return pc, ev[:k], s
