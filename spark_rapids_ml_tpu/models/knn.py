"""Nearest neighbors: exact brute-force (distributed) and IVF-Flat (approx).

BASELINE.json config #5: "Approx-KNN IVF-Flat on 10M×768 SBERT embeddings
(Pallas distance kernel, multi-host v5e-64)". TPU-first design:

* **Exact** (``NearestNeighbors``): the database is row-sharded over the
  ``data`` mesh axis. Each device computes its local (q, m_local) distance
  tile via the Gram trick (one MXU GEMM), takes a local top-k with
  ``lax.top_k``, then candidates (k per device per query) are all-gathered
  over ICI and merged with a second top-k. Communication is O(q·k·devices),
  independent of database size — the same "reduce a small partial, not the
  data" bet as the reference's Gram-partials design (SURVEY.md §3.1).
* **Approx** (``ApproximateNearestNeighbors``, IVF-Flat): a KMeans coarse
  quantizer (reusing models/kmeans.py) partitions the database into nlist
  inverted lists, padded dense to (nlist, maxlen, d) so everything is
  static-shaped — XLA-friendly, no ragged structures. Query execution is
  two-strategy (see ``_ivf_query_fn``): a dense masked block scan (exact
  within probed lists) when a large fraction of lists is probed, else
  ScaNN-style capacity-bucketed query grouping — batched per-list GEMMs
  over only the assigned queries (residual-encoded against the list
  centroids), a 4k-wide approximate shortlist, and an exact f32 rerank.

Output convention follows spark-rapids-ml's NearestNeighbors:
``kneighbors(queries) -> (distances, indices)`` with Euclidean distances.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.core.dataset import as_matrix
from spark_rapids_ml_tpu.core.params import (
    Estimator,
    HasFeaturesCol,
    HasSeed,
    Model,
    ParamDecl,
    ParamValidators,
    TypeConverters,
)
from spark_rapids_ml_tpu.core.persistence import MLReadable, MLWritable
from spark_rapids_ml_tpu.models.job_protocol import JobAlgorithm
from spark_rapids_ml_tpu.ops.distances import fused_topk_fits, sq_euclidean
from spark_rapids_ml_tpu.ops.pallas_kernels import (
    ivf_scan_select_pallas,
    probe_select_pallas,
)
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, default_mesh
from spark_rapids_ml_tpu.parallel import mapreduce as mr
from spark_rapids_ml_tpu.parallel.sharding import (
    bucket_rows,
    pad_rows,
    row_sharding,
)
from spark_rapids_ml_tpu.utils.profiling import trace_span
from spark_rapids_ml_tpu.utils.xprof import ledgered_jit


# ---------------------------------------------------------------------------
# Exact brute-force
# ---------------------------------------------------------------------------


def _exact_fused_enabled() -> bool:
    """The production gate for the fused exact-kneighbors kernel: the
    ``use_pallas`` config on a TPU backend, f32 accumulation (the kernel
    emits f32 scores). Tests force the kernel off-backend by passing
    ``use_pallas=True`` to :func:`_exact_knn_fn` directly (the kernel then
    runs in interpret mode — the same pattern as ``ann_fused_scan="on"``)."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    return bool(
        _pallas_backend_ok()
        and jnp.dtype(config.get("accum_dtype")) == jnp.float32
    )


@functools.lru_cache(maxsize=32)
def _exact_knn_fn(mesh: Mesh, k: int, cd: str, ad: str, metric: str = "l2",
                  use_pallas: bool = False):
    """metric "l2": ascending squared-Euclidean (callers post-process to
    euclidean/sqeuclidean/cosine — the latter two are monotone transforms
    on appropriately normalized inputs). metric "ip": descending inner
    product (MIPS); returned "distances" are the similarities.

    ``use_pallas``: route the l2 shard scan through the fused streaming
    distance+top-k kernel (ops/pallas_kernels.dist_topk_pallas) — the
    (q, m_local) distance matrix never reaches HBM and the per-shard
    selection is exact with (distance, id) tie-breaking, bitwise the
    ``merge_topk`` order. Off-TPU the kernel runs in interpret mode
    (goldens); infeasible shapes fall back to the XLA two-step in-trace."""
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)

    def shard(db, mask, row_ids, queries):
        # db: (m_local, d) this device's database shard; queries replicated;
        # row_ids: (m_local,) the shard's rows' ORIGINAL indices (-1 = pad).
        # An explicit id map rather than shard_id*m_local + local arithmetic:
        # multi-process ingestion pads at each process's tail, so padded
        # positions are interleaved and arithmetic ids would be wrong.
        m_local = db.shape[0]
        # A shard can hold fewer rows than k; its local candidate list is
        # then all of its rows. The union of per-shard top-min(k, m_local)
        # still contains the global top-k (k <= n total valid rows).
        kl = min(k, m_local)
        if (
            use_pallas
            and metric == "l2"
            and fused_topk_fits(
                queries.shape[0], m_local, db.shape[1], kl, accum_dtype
            )
        ):
            from spark_rapids_ml_tpu.ops.pallas_kernels import dist_topk_pallas

            fd, fi = dist_topk_pallas(
                queries.astype(compute_dtype), db.astype(compute_dtype),
                row_ids, mask, kl,
                interpret=not config.backend_is_tpu(),
            )
            return mr.reduce_topk(fd.astype(accum_dtype), fi, k, DATA_AXIS)
        if metric == "ip":
            from spark_rapids_ml_tpu.ops.gram import mm_precision

            with mm_precision(compute_dtype):
                d2 = -jnp.einsum(
                    "qd,md->qm",
                    queries.astype(compute_dtype),
                    db.astype(compute_dtype),
                    preferred_element_type=accum_dtype,
                )  # negated: the shared min-merge machinery then applies
        else:
            d2 = sq_euclidean(
                queries.astype(compute_dtype), db.astype(compute_dtype),
                accum_dtype=accum_dtype,
            )  # (q, m_local)
        # Masked-out (padding) rows get +inf so they never win.
        d2 = jnp.where(mask[None, :] > 0, d2, jnp.inf)
        neg, local_idx = jax.lax.top_k(-d2, kl)  # (q, kl)
        global_idx = row_ids[local_idx]
        # Merge candidates from all shards: the pool holds >= k valid
        # entries because padding is tail-only.
        return mr.reduce_topk(-neg, global_idx, k, DATA_AXIS)

    f = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,  # gathered candidates are value-replicated
    )
    return ledgered_jit("knn.exact_topk", f)


# APPEND-ONLY: ANN model payloads persist the fit metric as an ordinal into
# this tuple (_model_data "fit_metric"), so existing positions are an
# on-disk contract — add new metrics at the END.
KNN_METRICS = ("euclidean", "sqeuclidean", "cosine", "inner_product")


def merge_topk(
    dists, ids, k: int, descending: bool = False
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side top-k merge of per-shard kneighbors results — the
    daemon-level twin of the O(q·k·devices) ``all_gather`` merges inside
    ``_exact_knn_fn`` / ``_ivf_query_fn_sharded``, for shards that live in
    DIFFERENT processes/hosts (one entry per daemon serving a slice of the
    database). Exactness: as long as each shard returns its local
    top-min(k, shard_rows), the union of candidates contains the global
    top-k, so the merge is exact given exact shard answers (the reference's
    any-number-of-executors reduce property, RapidsRowMatrix.scala:139).

    ``dists``/``ids``: sequences of (q, k_i) arrays (k_i may differ — a
    shard smaller than k contributes all its rows). ``descending`` for
    similarity metrics (inner_product). Invalid entries (id −1, distance
    +inf ascending / −inf descending) sort last; ties break toward the
    smaller row id.

    Merged distances come back in the shards' common dtype (f32 shards →
    f32 out, the single-daemon dtype — ADVICE r5(c)): the merge itself
    runs in f64 only so that comparisons are exact, and the selected
    values are bit-identical to the shard's own answer after the cast."""
    out_dtype = np.result_type(*[np.asarray(d).dtype for d in dists])
    D = np.concatenate([np.asarray(d, np.float64) for d in dists], axis=1)
    I = np.concatenate([np.asarray(i, np.int64) for i in ids], axis=1)
    if D.shape[1] < k:
        raise ValueError(
            f"merged candidate pool {D.shape[1]} < k = {k}; every shard "
            "must return min(k, its rows) candidates"
        )
    key = -D if descending else D
    # Row-wise lexsort: last key is primary (distance), id breaks ties;
    # a shard's not-found tail (±inf) keys sort past every real candidate.
    order = np.lexsort((I, key), axis=-1)[:, :k]
    return (
        np.take_along_axis(D, order, axis=1).astype(out_dtype, copy=False),
        np.take_along_axis(I, order, axis=1),
    )


def _normalized_rows(
    x: np.ndarray, zero_slot: int = 0, eps: float = 1e-12
) -> np.ndarray:
    """Cosine-metric preprocessing: unit rows + TWO augmentation columns.

    A zero row becomes a unit vector in augmentation column ``zero_slot``
    (0 for database/index rows, 1 for queries): orthogonal to every real
    vector AND to the other side's zero vectors, so its cosine distance is
    exactly 1 — matching sklearn's normalize()-then-dot semantics. A plain
    zero-stays-zero embedding would report 0.5 (= ‖q−0‖²/2), silently
    ranking zero rows ABOVE genuinely dissimilar neighbors."""
    x = np.asarray(x, np.float32 if x.dtype != np.float64 else np.float64)
    nrm = np.linalg.norm(x, axis=1, keepdims=True)
    out = np.concatenate(
        [x / np.maximum(nrm, eps), np.zeros((x.shape[0], 2), x.dtype)], axis=1
    )
    out[nrm[:, 0] <= eps, x.shape[1] + zero_slot] = 1.0
    return out


class _NNParams(HasFeaturesCol, HasSeed):
    k = ParamDecl(
        "k",
        "number of neighbors to return (> 0)",
        TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )
    metric = ParamDecl(
        "metric",
        "distance metric: euclidean (default), sqeuclidean, cosine, or "
        "inner_product (exact KNN only; returns similarities descending)",
        TypeConverters.toString,
        validator=ParamValidators.inList(KNN_METRICS),
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(k=5, featuresCol="features", seed=0, metric="euclidean")

    def getK(self) -> int:
        return self.getOrDefault(self.k)

    def getMetric(self) -> str:
        return self.getOrDefault(self.metric)


class NearestNeighbors(Estimator, _NNParams, MLWritable, MLReadable):
    """Exact brute-force KNN; ``fit`` indexes the database."""

    _uid_prefix = "NearestNeighbors"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setK(self, value: int) -> "NearestNeighbors":
        return self._set(k=value)

    def setMetric(self, value: str) -> "NearestNeighbors":
        return self._set(metric=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "NearestNeighborsModel":
        x = as_matrix(dataset, self.getFeaturesCol())
        model = NearestNeighborsModel(database=np.asarray(x), mesh=self._mesh)
        model.uid = self.uid
        self._copy_params_to(model)
        return model


class NearestNeighborsModel(Model, _NNParams, MLWritable, MLReadable):
    _uid_prefix = "NearestNeighborsModel"
    # device-resident index state rebuilds via _ensure_index after unpickle
    _transient_attrs = (
        "_mesh", "_db_sharded", "_db_mask", "_db_ids", "_n_global",
        "_index_rep",
    )

    def __init__(self, database: Optional[np.ndarray] = None, mesh=None, uid=None):
        super().__init__(uid=uid)
        self.database = None if database is None else np.asarray(database)
        self._mesh = mesh
        self._db_sharded = None
        self._db_mask = None
        self._db_ids = None
        self._n_global = None
        self._index_rep = None

    def _model_data(self):
        return {"database": self.database}

    @classmethod
    def _from_model_data(cls, uid, data):
        return cls(database=data["database"], uid=uid)

    def _copy_extra_state(self, source):
        self.database = source.database
        self._mesh = getattr(source, "_mesh", None)

    def _ensure_index(self, mesh):
        metric = self.getMetric()
        # Only the cosine boundary changes the SHARDED DATA (the
        # augmented-normalized copy); euclidean/sqeuclidean/inner_product
        # all shard the raw rows — switching among them must not repeat a
        # multi-GB reshard.
        rep = "cosine" if metric == "cosine" else "raw"
        if getattr(self, "_index_rep", None) != rep:
            self._db_sharded = None
            self._index_rep = rep
        if self._db_sharded is None:
            from spark_rapids_ml_tpu.parallel.sharding import shard_rows

            n_local = self.database.shape[0]
            if jax.process_count() > 1:
                # Multi-process: `database` is this process's local slice;
                # its original-row-id range starts after lower ranks' rows.
                from jax.experimental import multihost_utils as mhu

                counts = np.asarray(
                    mhu.process_allgather(np.asarray([n_local]))
                ).reshape(-1)
                lo = int(counts[: jax.process_index()].sum())
            else:
                lo = 0
            db = (
                _normalized_rows(self.database, zero_slot=0)
                if metric == "cosine"
                else self.database
            )
            self._db_sharded, self._db_mask, self._n_global = shard_rows(
                db, mesh
            )
            # Explicit id map; +1 shift so shard_rows's zero-padding decodes
            # to -1 (a real row 0 must stay distinguishable from padding).
            ids, _, _ = shard_rows(
                np.arange(lo + 1, lo + n_local + 1, dtype=np.int32),
                mesh,
                with_mask=False,
            )
            self._db_ids = ids - 1

    def kneighbors(
        self, queries: np.ndarray, k: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (distances (q, k), indices (q, k)) under ``metric``:
        euclidean (default) / sqeuclidean / cosine ascending, or
        inner_product DESCENDING (the "distances" are the similarities —
        the MIPS convention).

        Multi-process: every process passes the SAME query batch and its
        own local database slice was used at fit; returned indices are
        global row positions (concatenation order of the process slices).
        """
        if self.database is None:
            raise RuntimeError("model has no database (unfitted?)")
        k = self.getK() if k is None else k
        mesh = self._mesh or default_mesh()
        self._ensure_index(mesh)
        n = self._n_global
        if not 0 < k <= n:
            raise ValueError(f"k = {k} out of range (0, numRows = {n}]")
        metric = self.getMetric()
        queries = np.asarray(queries)
        if metric == "cosine":
            queries = _normalized_rows(queries, zero_slot=1)
        q = queries.shape[0]
        bucket = bucket_rows(q, 64)
        qp, _ = pad_rows(queries, bucket)
        with trace_span("knn query"):
            from spark_rapids_ml_tpu.parallel.sharding import replicated_array

            fn = _exact_knn_fn(
                mesh, k, config.get("compute_dtype"), config.get("accum_dtype"),
                metric="ip" if metric == "inner_product" else "l2",
                use_pallas=_exact_fused_enabled(),
            )
            d2, idx = jax.device_get(
                fn(self._db_sharded, self._db_mask, self._db_ids,
                   replicated_array(qp, mesh))
            )
        idx = idx[:q].astype(np.int64)
        if metric == "inner_product":
            # d2 holds NEGATED products (the shared ascending merge); the
            # +inf of never-found slots decodes to -inf similarity.
            return -d2[:q], idx
        if metric == "sqeuclidean":
            return np.maximum(d2[:q], 0), idx
        if metric == "cosine":
            # rows and queries are unit vectors: ||q-x||^2 = 2 - 2cos,
            # so the cosine distance (1 - cos) is half the squared L2.
            return np.clip(d2[:q] / 2.0, 0, None), idx
        return np.sqrt(np.maximum(d2[:q], 0)), idx

    def _serve_aot_plan(self, n_rows, n_cols, dtype="float32", k=None):
        """AOT-at-registration plan (serve/daemon.py; see PCAModel's):
        the sharded exact-kneighbors program, lowered against the
        device-RESIDENT index arrays plus an abstract replicated query
        spec. Serve buckets are powers of two ≥ 64, exactly the row
        counts ``kneighbors`` pads to, so the primed shape IS the served
        shape. ``k`` defaults to the fitted k (what the scheduler keys
        un-k'd traffic to). ``_ensure_index`` here DELIBERATELY
        front-loads the index's device upload into the registration warm
        — the ack's "servable at full speed" contract covers residency,
        not just compiles; the first query would pay it otherwise."""
        if self.database is None:
            return None
        if int(n_cols) != int(self.database.shape[1]):
            raise ValueError(
                f"warmup n_cols={int(n_cols)} does not match the "
                f"index's fitted width {int(self.database.shape[1])}"
            )
        from jax.sharding import NamedSharding

        mesh = self._mesh or default_mesh()
        self._ensure_index(mesh)
        metric = self.getMetric()
        k = self.getK() if k is None else int(k)
        fn = _exact_knn_fn(
            mesh, k, config.get("compute_dtype"), config.get("accum_dtype"),
            metric="ip" if metric == "inner_product" else "l2",
            use_pallas=_exact_fused_enabled(),
        )
        # MIRROR kneighbors' query padding (max(64, next-pow2)), not the
        # raw scheduler bucket: a sub-64 or non-pow2 ladder entry would
        # otherwise prime a shape the query path never dispatches.
        qspec = jax.ShapeDtypeStruct(
            (bucket_rows(int(n_rows), 64), int(self._db_sharded.shape[1])),
            jnp.dtype(dtype),
            sharding=NamedSharding(mesh, P()),
        )
        return [(fn, (self._db_sharded, self._db_mask, self._db_ids, qspec))]

    def _transform(self, dataset):
        x = as_matrix(dataset, self.getFeaturesCol())
        dists, idx = self.kneighbors(x)
        from spark_rapids_ml_tpu.core.dataset import with_column

        out = with_column(dataset, "knn_distances", dists)
        return with_column(out, "knn_indices", idx)


# ---------------------------------------------------------------------------
# IVF-Flat approximate
# ---------------------------------------------------------------------------


class IVFFlatIndex(NamedTuple):
    centroids: np.ndarray  # (nlist, d)
    lists: np.ndarray  # (nlist, maxlen, d) padded points
    list_ids: np.ndarray  # (nlist, maxlen) original row ids, -1 = pad
    list_mask: np.ndarray  # (nlist, maxlen) 1.0 valid


# Padded-list capacity bound, as a multiple of the mean list size n/nlist.
# The rectangular (nlist, maxlen, d) device layout pays nlist×maxlen×d for
# the HOTTEST list: on clustered data (the data IVF exists for) the coarse
# quantizer routinely drops several natural clusters into one list and a
# maxlen of 20-30× the mean follows — a 24 GB index for 3 GB of rows.
# Lists are therefore capacity-bounded: rows past a list's cap spill to
# their next-nearest centroid (FAISS keeps ragged lists instead; a fixed
# cap is the TPU-native answer, same trade as the query side's bucket
# capacity C). A query probing nprobe lists generally probes the spill
# target too, so the recall cost is small — and the scan cost drops with
# maxlen, so balance is also a throughput win.
IVF_MAX_LOAD_FACTOR = 2.0
_IVF_SPILL_CANDIDATES = 4


def _ivf_assign_chunk_fns(nlist: int):
    """The two chunked quantizer-assignment jits shared by the host and
    device IVF builders, with the fused Pallas routes behind the standard
    ``use_pallas`` gate: the primary assignment rides
    ``assign_min_dist_pallas`` (distance tile + argmin fused — the (m,
    nlist) matrix never reaches HBM) and the spill-candidate pass rides the
    EXACT ``dist_topk_pallas`` (replacing the XLA ``approx_min_k``'s 0.95
    recall, whose only consumer is capacity balancing — exact preference
    order is strictly better there). Infeasible shapes (a remainder chunk,
    a non-lane-aligned nlist) fall back to the XLA ops in-trace."""
    from spark_rapids_ml_tpu.ops.gram import _pallas_backend_ok

    T = min(_IVF_SPILL_CANDIDATES, nlist)

    @ledgered_jit("knn.ivf_assign")
    def _argmin_chunk(chunk, centroids):
        # The kmeans gate owns this kernel's full applicability story
        # (f32, d ≤ 512 VMEM bound, tile divisibility); the extra m % 8
        # keeps a sub-1024 REMAINDER chunk (where m % min(1024, m) is
        # vacuously 0) off the non-sublane-aligned block shapes the
        # kernel's other callers never exercise.
        from spark_rapids_ml_tpu.models.kmeans import _pallas_assign_applicable

        m = chunk.shape[0]
        if m % 8 == 0 and _pallas_assign_applicable(
            m, nlist, chunk.shape[1], jnp.float32
        ):
            from spark_rapids_ml_tpu.ops.pallas_kernels import (
                assign_min_dist_pallas,
            )

            idx, _ = assign_min_dist_pallas(
                chunk, centroids, interpret=not config.backend_is_tpu()
            )
            return idx
        d2 = sq_euclidean(chunk, centroids, accum_dtype=jnp.float32)
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    @ledgered_jit("knn.ivf_candidates")
    def _cand_chunk(chunk, centroids):
        m = chunk.shape[0]
        if _pallas_backend_ok() and fused_topk_fits(
            m, nlist, chunk.shape[1], T
        ):
            from spark_rapids_ml_tpu.ops.pallas_kernels import dist_topk_pallas

            _, idx = dist_topk_pallas(
                chunk, centroids,
                jnp.arange(nlist, dtype=jnp.int32),
                jnp.ones((nlist,), jnp.float32), T,
                interpret=not config.backend_is_tpu(),
            )
            return idx
        d2 = sq_euclidean(chunk, centroids, accum_dtype=jnp.float32)
        # approx_min_k, not top_k: exact top-k lowers to a full per-row
        # sort of the nlist-wide row — minutes at 1M×1024 — and the
        # preference order only feeds capacity balancing (the primary
        # assignment stays an EXACT argmin).
        _, idx = jax.lax.approx_min_k(d2, T, recall_target=0.95)
        return idx.astype(jnp.int32)

    return _argmin_chunk, _cand_chunk


def _balance_assignments(cand: np.ndarray, nlist: int, cap: int) -> np.ndarray:
    """Greedy capacity-bounded assignment from preference-ordered
    candidates ``cand`` (n, T): round t gives every still-unassigned row
    its t-th nearest list while capacity remains; leftovers after T rounds
    fill the least-loaded lists (guaranteed to fit: cap·nlist ≥ n)."""
    n, T = cand.shape
    assign = np.full(n, -1, np.int64)
    load = np.zeros(nlist, np.int64)
    pending = np.arange(n)
    for t in range(T):
        want = cand[pending, t].astype(np.int64)
        order = np.argsort(want, kind="stable")
        sw = want[order]
        run_start = np.searchsorted(sw, np.arange(nlist))
        pos_in_run = np.arange(len(sw)) - run_start[sw]
        ok = pos_in_run < np.maximum(cap - load[sw], 0)
        assign[pending[order[ok]]] = sw[ok]
        load += np.bincount(sw[ok], minlength=nlist)
        pending = pending[order[~ok]]
        if pending.size == 0:
            break
    if pending.size:
        spare = np.maximum(cap - load, 0)
        order = np.argsort(-spare, kind="stable")  # least-loaded lists first
        slots = np.repeat(order, spare[order])
        assign[pending] = slots[: pending.size]
    return assign


def _ivf_cap(n: int, nlist: int) -> int:
    """Per-list row capacity: load-factor × mean, floored so cap·nlist ≥ n."""
    return max(int(np.ceil(IVF_MAX_LOAD_FACTOR * n / nlist)), -(-n // nlist))


def _balanced_refine(get_cand, recenter, nlist: int, cap: int, rounds: int = 3):
    """Balanced-Lloyd refinement shared by the host and device builders:
    alternate capacity-greedy assignment with centroid recomputation FROM
    the balanced assignment. The recentering is what keeps recall: plain
    spill leaves a hot centroid mid-mega-cluster and scatters its overflow
    to far lists, while a recentred quantizer moves centroids toward their
    bounded share of the data, so spill targets become genuinely near rows
    (balanced k-means). ``get_cand()`` → (n, T) preference-ordered
    candidates for the CURRENT centroids; ``recenter(assign)`` updates the
    builder's centroids. Returns the final balanced (n,) assignment."""
    for _ in range(rounds):
        assign = _balance_assignments(np.asarray(get_cand()), nlist, cap)
        recenter(assign)
    return _balance_assignments(np.asarray(get_cand()), nlist, cap)


def build_ivf_flat(
    x: np.ndarray,
    nlist: int,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    train_rows: int = 2_000_000,
    centroids: Optional[np.ndarray] = None,
    train_data: Optional[np.ndarray] = None,
) -> IVFFlatIndex:
    """Train the coarse quantizer and bucket the database into padded lists.

    The quantizer uses random init (the IVF convention — a k-means++ pass
    with nlist in the hundreds is nlist sequential host passes over the
    sample for no recall benefit at this k) and trains on at most
    ``train_rows`` sampled rows — FAISS's convention: quantizer quality
    saturates at a few hundred points per list, and training on the full
    database would force it through HBM 10+ times for nothing (the
    assignment pass below still covers every row, in chunks).

    ``centroids``: a pretrained (nlist, d) quantizer — the shard-consistent
    build for an index spanning daemons (every daemon buckets ITS rows
    against the SAME centroids, so a query's probe set selects the same
    lists everywhere and the cross-daemon top-k union is the single-index
    candidate set). The provided quantizer is FROZEN: capacity balancing
    may still spill rows to their next-nearest list, but never recenters —
    recentering would diverge the shards' quantizers.

    ``train_data``: an explicit quantizer training set that REPLACES the
    local sample — the cross-shard fix for sharded builds (ADVICE
    r5(b)): training on this shard's rows alone skews the shared
    centroids toward whatever locality-sticky routing parked here, so
    the driver samples every daemon (``sample_rows`` op) and hands the
    union to the quantizer-owning build. Ignored when ``centroids`` is
    given (a pretrained quantizer never retrains).
    """
    from spark_rapids_ml_tpu.models.kmeans import fit_kmeans

    x = np.asarray(x)
    frozen = centroids is not None
    if frozen:
        centroids = np.asarray(centroids, np.float32)
        if centroids.shape != (nlist, x.shape[1]):
            raise ValueError(
                f"pretrained centroids shape {centroids.shape} != "
                f"({nlist}, {x.shape[1]})"
            )
    else:
        if train_rows < nlist:
            raise ValueError(
                f"train_rows = {train_rows} must be >= nlist = {nlist} "
                f"(the quantizer needs at least one training row per list)"
            )
        pool = x if train_data is None else np.asarray(train_data, x.dtype)
        if train_data is not None:
            if pool.ndim != 2 or pool.shape[1] != x.shape[1]:
                raise ValueError(
                    f"train_data shape {pool.shape} does not match the "
                    f"database width {x.shape[1]}"
                )
            if pool.shape[0] < nlist:
                raise ValueError(
                    f"train_data has {pool.shape[0]} rows < nlist = "
                    f"{nlist} (one training row per list minimum)"
                )
        if pool.shape[0] > train_rows:
            # shuffle=False: Floyd's O(train_rows) sampling — the default
            # shuffles a full O(n) permutation, ~800 MB at 100M rows, for an
            # ordering k-means training doesn't care about.
            pick = np.random.default_rng(seed).choice(
                pool.shape[0], train_rows, replace=False, shuffle=False
            )
            sample = pool[pick]
        else:
            sample = pool
        sol = fit_kmeans(
            sample, k=nlist, max_iter=10, seed=seed, init="random", mesh=mesh
        )
        centroids = sol.centers
    # Device-side assignment (the n·nlist·d FLOPs belong on the MXU — at
    # 1M×768×1024 the host-numpy version is minutes of CPU); only the
    # (n,) argmin comes back. The scatter into padded lists stays on host.
    n = x.shape[0]
    T = min(_IVF_SPILL_CANDIDATES, nlist)
    cdev = jnp.asarray(centroids, jnp.float32)
    _argmin_chunk, _cand_chunk = _ivf_assign_chunk_fns(nlist)

    step = 1 << 18

    def _chunked(fn, width):
        out = np.empty((n, width) if width > 1 else (n,), dtype=np.int32)
        for i in range(0, n, step):
            chunk = jnp.asarray(x[i : i + step], jnp.float32)
            out[i : i + step] = np.asarray(fn(chunk, cdev))
        return out

    @ledgered_jit("knn.ivf_recenter")
    def _recenter_chunk(xc, ac, sums, cnt):
        onehot = jax.nn.one_hot(ac, nlist, dtype=jnp.bfloat16)
        sums = sums + jax.lax.dot_general(
            onehot, xc.astype(jnp.bfloat16), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        cnt = cnt + jnp.sum(onehot.astype(jnp.float32), axis=0)
        return sums, cnt

    def _recenter(assign_np, cdev):
        sums = jnp.zeros((nlist, x.shape[1]), jnp.float32)
        cnt = jnp.zeros((nlist,), jnp.float32)
        for i in range(0, n, step):
            sums, cnt = _recenter_chunk(
                jnp.asarray(x[i : i + step], jnp.float32),
                jnp.asarray(assign_np[i : i + step], jnp.int32),
                sums, cnt,
            )
        return jnp.where((cnt > 0)[:, None],
                         sums / jnp.maximum(cnt, 1.0)[:, None], cdev)

    assign = _chunked(_argmin_chunk, 1).astype(np.int64)
    counts = np.bincount(assign, minlength=nlist)
    cap = _ivf_cap(n, nlist)
    if int(counts.max()) > cap:
        def _recenter_cb(assign_np):
            nonlocal cdev
            cdev = _recenter(assign_np, cdev)

        if frozen:
            # No recentering (the quantizer is shared across shards):
            # capacity-spill against the fixed preference order only.
            assign = _balance_assignments(
                np.asarray(_chunked(_cand_chunk, T)), nlist, cap
            )
        else:
            assign = _balanced_refine(
                lambda: _chunked(_cand_chunk, T), _recenter_cb, nlist, cap
            )
            centroids = np.asarray(jax.device_get(cdev), dtype=centroids.dtype)
        counts = np.bincount(assign, minlength=nlist)
    maxlen = max(int(counts.max()), 1)
    d = x.shape[1]
    lists = np.zeros((nlist, maxlen, d), dtype=x.dtype)
    list_ids = np.full((nlist, maxlen), -1, dtype=np.int64)
    # Vectorized bucketing: sort rows by list, then each row's slot within
    # its list is its rank minus the list's start offset. The random
    # tiebreak SHUFFLES each list's internal order: the query path's
    # positional partial top-k (approx_min_k) assumes near-neighbors are
    # spread across row positions, and insertion-ordered databases (e.g.
    # generated or ingested cluster-by-cluster) violate that adversarially.
    shuffle = np.random.default_rng(seed ^ 0x5EED).permutation(n)
    order = shuffle[np.argsort(assign[shuffle], kind="stable")]
    sorted_assign = assign[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slots = np.arange(n) - starts[sorted_assign]
    lists[sorted_assign, slots] = x[order]
    list_ids[sorted_assign, slots] = order
    list_mask = (list_ids >= 0).astype(np.float32)
    return IVFFlatIndex(centroids, lists, list_ids, list_mask)


def build_ivf_flat_device(
    x,
    nlist: int,
    seed: int = 0,
    train_rows: int = 2_000_000,
    centroids=None,
    train_data=None,
) -> IVFFlatIndex:
    """Device-side IVF-Flat build for data already resident on device.

    ``build_ivf_flat`` buckets on the host — right when the database
    arrives as host numpy, but a pure round-trip when rows are already on
    device (generated there, or fed by the data-plane daemon): 2×3 GB
    over PCIe plus host-speed fancy indexing. Here everything —
    quantizer Lloyd iterations, assignment, the sort-based bucketing
    scatter — runs on device; only the (nlist,) counts come back to fix
    the static ``maxlen``. Returns an IVFFlatIndex whose fields are
    device arrays (same container; the model's device-index cache accepts
    either).
    """
    from spark_rapids_ml_tpu.models.kmeans import _lloyd_fn
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    key = jax.random.key(seed)
    k_samp, k_init, k_shuf = jax.random.split(key, 3)
    frozen = centroids is not None
    if frozen:
        # Pretrained shard-consistent quantizer (see build_ivf_flat):
        # bucket against it, never retrain/recenter.
        centroids = jnp.asarray(centroids, jnp.float32)
        if centroids.shape != (nlist, d):
            raise ValueError(
                f"pretrained centroids shape {centroids.shape} != ({nlist}, {d})"
            )
    else:
        # train_data: explicit cross-shard training set (see
        # build_ivf_flat — ADVICE r5(b)); replaces the local sample.
        pool = x if train_data is None else jnp.asarray(train_data, jnp.float32)
        if train_data is not None and (pool.ndim != 2 or pool.shape[1] != d):
            raise ValueError(
                f"train_data shape {pool.shape} does not match the "
                f"database width {d}"
            )
        n_pool = pool.shape[0]
        n_train = min(n_pool, train_rows)
        if n_train < nlist:
            raise ValueError(
                f"effective train rows = {n_train} must be >= nlist = {nlist} "
                f"(the quantizer needs at least one training row per list)"
            )
        sample = (
            pool[jax.random.choice(k_samp, n_pool, (n_train,), replace=False)]
            if n_pool > train_rows
            else pool
        )
        centers0 = sample[
            jax.random.choice(k_init, n_train, (nlist,), replace=False)
        ]
        mesh = make_mesh(data=1, model=1, devices=list(x.devices())[:1])
        fn = _lloyd_fn(
            mesh, nlist, 10, 1e-4, config.get("compute_dtype"),
            config.get("accum_dtype"),
            use_pallas=bool(config.get("use_pallas")),
        )
        centroids, _, _ = fn(sample, jnp.ones((n_train,), jnp.float32), centers0)
        centroids = centroids.astype(jnp.float32)

    _argmin_chunk, _cand_chunk = _ivf_assign_chunk_fns(nlist)

    # Chunked assignment for ANY n (a whole-x call would materialize the
    # (n, nlist) distance matrix); at most two compiled shapes (full chunk
    # + remainder).
    step = 1 << 18

    def _chunked(fn, centroids):
        return (
            jnp.concatenate(
                [
                    fn(jax.lax.slice_in_dim(x, i, min(i + step, n)), centroids)
                    for i in range(0, n, step)
                ]
            )
            if n > step
            else fn(x, centroids)
        )

    @ledgered_jit("knn.ivf_recenter")
    def _recenter_chunk(xc, ac, sums, cnt):
        # One-hot MXU matmul, not scatter-add: the (chunk, nlist) one-hot
        # GEMM is milliseconds where a 1M-row scatter is minutes.
        onehot = jax.nn.one_hot(ac, nlist, dtype=jnp.bfloat16)
        sums = sums + jax.lax.dot_general(
            onehot, xc.astype(jnp.bfloat16), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        cnt = cnt + jnp.sum(onehot.astype(jnp.float32), axis=0)
        return sums, cnt

    def _recenter(assign, centroids):
        sums = jnp.zeros((nlist, d), jnp.float32)
        cnt = jnp.zeros((nlist,), jnp.float32)
        for i in range(0, n, step):
            sums, cnt = _recenter_chunk(
                jax.lax.slice_in_dim(x, i, min(i + step, n)),
                jax.lax.slice_in_dim(assign, i, min(i + step, n)),
                sums, cnt,
            )
        return jnp.where(
            (cnt > 0)[:, None], sums / jnp.maximum(cnt, 1.0)[:, None], centroids
        )

    assign = _chunked(_argmin_chunk, centroids)
    counts = jnp.zeros((nlist,), jnp.int32).at[assign].add(1)
    natural_max = int(jax.device_get(counts.max()))
    cap = _ivf_cap(n, nlist)
    if natural_max > cap:
        # Balanced-Lloyd refinement (_balanced_refine); the (n, T) int32
        # candidate round-trip to the host balancer is tiny next to the
        # index.
        def _recenter_cb(assign_np):
            nonlocal centroids
            centroids = _recenter(jnp.asarray(assign_np, jnp.int32), centroids)

        if frozen:  # shared quantizer: capacity-spill only, no recenter
            assign_np = _balance_assignments(
                np.asarray(_chunked(_cand_chunk, centroids)), nlist, cap
            )
        else:
            assign_np = _balanced_refine(
                lambda: _chunked(_cand_chunk, centroids), _recenter_cb,
                nlist, cap,
            )
        assign = jnp.asarray(assign_np, jnp.int32)
        counts = jnp.zeros((nlist,), jnp.int32).at[assign].add(1)
        maxlen = max(int(jax.device_get(counts.max())), 1)
    else:
        maxlen = max(natural_max, 1)  # static for the jit below

    @functools.partial(
        ledgered_jit, "knn.ivf_bucketize", static_argnames=("maxlen",)
    )
    def _bucketize(x, assign, counts, key, maxlen):
        # Same sort-based scatter as the host build, including the random
        # tiebreak shuffle that spreads near-neighbors across row slots.
        shuffle = jax.random.permutation(key, n)
        order = shuffle[jnp.argsort(assign[shuffle], stable=True)]
        sorted_assign = assign[order]
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts[:-1]).astype(jnp.int32)]
        )
        slots = jnp.arange(n, dtype=jnp.int32) - starts[sorted_assign]
        lists = (
            jnp.zeros((nlist, maxlen, d), x.dtype)
            .at[sorted_assign, slots].set(x[order])
        )
        list_ids = (
            jnp.full((nlist, maxlen), -1, jnp.int32)
            .at[sorted_assign, slots].set(order.astype(jnp.int32))
        )
        return lists, list_ids, (list_ids >= 0).astype(jnp.float32)

    lists, list_ids, list_mask = _bucketize(x, assign, counts, k_shuf, maxlen)
    return IVFFlatIndex(centroids, lists, list_ids, list_mask)


def _bucketed_capacity(q: int, nprobe: int, nlist: int, slack: float) -> int:
    """Per-list query capacity C, lane-rounded.

    Base: ceil(q*nprobe/nlist * slack) — expected per-list load times a
    slack for load fluctuations (relative headroom shrinks with the mean
    load λ = q*nprobe/nlist: (slack−1)·√λ sigmas for a Poisson load).

    A ceil(q/nprobe) floor additionally guarantees nprobe*C >= q — under
    the rank-rotated eviction order even a batch of IDENTICAL queries
    keeps at least one probed list per query — but only while that floor
    costs ≤ 4× the base capacity (i.e. nlist ≤ 4·slack·nprobe²). Beyond
    that the worst-case insurance would multiply every average-case
    query's FLOPs by nlist/(slack·nprobe²), so it is skipped: extremely
    correlated batches with tiny nprobe relative to nlist can then drop
    whole queries — raise nprobe, slack, or split the batch.
    At C == q nothing can ever be dropped.
    """
    base = int(np.ceil(q * nprobe / nlist * slack))
    floor = int(np.ceil(q / nprobe))
    cap = max(base, floor) if floor <= 4 * base else base
    return min(q, max(8, ((cap + 7) // 8) * 8))


def _probe_select_fits(nlist: int, d: int, qb: int) -> bool:
    """Feasibility gate for probe_select_pallas: the packed position bits
    must fit (nlist ≤ 65536 after 8-padding) and the resident centroid
    panel + (nlist, qb) f32 distance tile must fit VMEM."""
    nl8 = -(-nlist // 8) * 8
    if max(1, (nl8 - 1).bit_length()) > 16:
        return False
    return (nl8 * (d + qb + 1) + d * qb) * 4 <= 48 * 2**20


def _fused_scan_fits(C: int, maxlen: int, d: int, compute_dtype) -> bool:
    """VMEM feasibility gate for ivf_scan_select_pallas's ``auto`` mode:
    per grid step the kernel holds the (C_pad, d) query block, the
    (maxlen_pad, d) row block (each double-buffered by the pipeline) and
    the f32 (maxlen_pad, C_pad) score tile."""
    c_pad = -(-C // 128) * 128
    ml = -(-maxlen // 8) * 8
    e = jnp.dtype(compute_dtype).itemsize
    return 2 * (c_pad * d + ml * d) * e + ml * c_pad * 4 <= 10 * 2**20


def _bucketed_core(
    queries, probe, probe_d2, lists, list_ids, list_mask, resid_norms,
    n_valid, k: int, nprobe: int, C: int, compute_dtype, accum_dtype,
    list_block: int = 16, shortlist_mult: int = 2, rerank: bool = True,
    *, lists_lo, centroids, fused: str = "auto", rerank_width: int = 0,
    extract: str = "wide", _debug_stage=None,
):
    """The capacity-bucketed scorer over ONE device's lists.

    ``probe``: (q, nprobe) list indices INTO ``lists``; -1 marks pairs this
    device does not own (the sharded executor localizes global probe ids
    and marks the rest -1 — they are dropped here and satisfied by the
    owning device). ``probe_d2``: (q, nprobe) f32 ‖q − c_probe‖² from the
    probe stage. Returns (dists (q, k) exact f32 ascending, ids (q, k);
    +inf/-1 where fewer than k candidates exist locally).

    **Residual scoring** (FAISS's IVF convention, doubly needed at
    bfloat16): clustered data has ‖row‖ ≫ ‖row − c_list‖, so scoring raw
    rows at bf16 buries the within-list margins under rounding noise
    proportional to the LARGE absolute magnitudes — measured recall@10
    collapse 0.99 → 0.64 on clustered 128-d data. Instead
    ‖q − row‖² = ‖δ‖² − 2(q − c)·δ + ‖q − c‖² with δ = row − c_list: the
    GEMM runs on the SMALL residual operands (bf16 noise scales with
    them), the last term is the probe stage's per-(q, list) constant
    (added at candidate gather-back — it cannot change a within-list
    argmin), and the exact f32 rerank still reads the raw rows.

    ``lists_lo``: compute-dtype RESIDUAL copy of ``lists``
    (lists − centroids[:, None, :]) for the scan GEMMs — index data,
    cached on device by the model next to ``resid_norms`` (the f32
    per-row ‖δ‖²). At bfloat16 it halves the scan's HBM traffic AND drops
    the per-block cast. The public query() wrappers build both when a
    caller has no cache. ``centroids``: this device's (nlist, d) f32
    centroid rows, for the per-block query-residual subtraction.
    ``list_block=16`` keeps each block's (block, C, maxlen) distance tile
    small enough to stay on-chip between the GEMM and the shortlist
    selection — measured 4× faster than 32 at the bench shape (block=8
    over-fragments the pipeline and loses it back).

    See _ivf_query_fn's docstring for the full algorithm: eviction-ordered
    capacity bucketing, batched per-list-block GEMMs, position-only scan,
    and the exact f32 rerank.
    """
    q = queries.shape[0]
    nlist, maxlen, d = lists.shape
    n_pairs = q * nprobe

    # --- bucket (query, list) pairs by list with capacity C ---
    # Eviction order when a hot list overflows its capacity, least
    # valuable dropped first: (1) padding queries (rows >= n_valid) never
    # hold capacity at all; (2) higher probe rank — a query's least
    # promising list costs the least recall; (3) within a rank, a
    # RANK-KEYED rotated query order so correlated query batches spread
    # across their probed lists instead of the same C winners taking
    # every list.
    #
    # SORT-FREE slot assignment (replaced a 131k-element argsort that was
    # the single most expensive bucketing op): the (rank-major,
    # rot-within-rank) priority order is a FIXED, data-independent
    # permutation of the pairs, so a pair's slot is simply the number of
    # EARLIER same-list pairs along that static sequence — a chunked
    # prefix-count: per-chunk list histograms (scatter-add) + exclusive
    # cumsum across chunks + an in-chunk (S, S) equality/triangle count
    # the VPU eats whole. Pure elementwise/reduce work instead of a sort.
    # Non-owned pairs (probe < 0) and padding queries take the sentinel
    # list id ``nlist``: they count only against the sentinel row and
    # never hold capacity.
    S = 512
    n_seq = -(-n_pairs // S) * S
    seq_i = jnp.arange(n_seq, dtype=jnp.int32)
    r_seq = seq_i // q  # probe rank of sequence position (pad ranks >= nprobe)
    q_seq = (seq_i % q - r_seq * C) % q  # rank-keyed rotation, inverted
    valid_seq = r_seq < nprobe
    l_seq = jnp.where(
        valid_seq,
        probe.reshape(-1)[
            jnp.where(valid_seq, q_seq * nprobe + r_seq, 0)
        ],
        -1,
    )
    l_seq = jnp.where((l_seq >= 0) & (q_seq < n_valid), l_seq, nlist)
    ch = n_seq // S
    lc = l_seq.reshape(ch, S)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        < jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    )  # strict lower triangle: earlier-in-chunk mask
    within = jnp.sum(
        (lc[:, :, None] == lc[:, None, :]) & tri[None],
        axis=2,
        dtype=jnp.int32,
    ).reshape(-1)
    hist = jnp.zeros((ch, nlist + 1), jnp.int32).at[seq_i // S, l_seq].add(1)
    base = jnp.cumsum(hist, axis=0) - hist  # exclusive over earlier chunks
    slot_seq = base[seq_i // S, l_seq] + within
    keep = (slot_seq < C) & (l_seq < nlist)
    bucket_q = (
        jnp.full((nlist, C), -1, jnp.int32)
        .at[jnp.where(keep, l_seq, nlist), jnp.where(keep, slot_seq, 0)]
        .set(q_seq, mode="drop")
    )
    # Per original (query, probe) pair: its slot in its list (-1 =
    # dropped). Pair (qq, r) sits at the STATIC sequence position
    # r·q + rot(qq, r) — a constant-index gather, no inverse scatter.
    qq = jnp.arange(q, dtype=jnp.int32)[:, None]
    rr = jnp.arange(nprobe, dtype=jnp.int32)[None, :]
    i_pair = rr * q + (qq + rr * C) % q
    pair_slot = jnp.where(keep, slot_seq, -1)[i_pair]
    pair_list = jnp.where(probe >= 0, probe, 0)  # dropped pairs masked via pair_slot
    if _debug_stage == "bucket":
        # Profiling cut (benchmarks/profile_ivf_stages.py): everything up
        # to and including the bucketing counts/scatters stays live; the
        # scan and selection are dropped.
        live = (
            bucket_q.sum() + pair_slot.sum() + hist.sum()
        ).astype(accum_dtype)
        return (
            probe_d2[:, :k].astype(accum_dtype) + live,
            jnp.broadcast_to(pair_list[:, :1], (q, k)).astype(jnp.int64),
        )

    nblk = -(-nlist // list_block)
    pad = nblk * list_block - nlist
    lists_p = jnp.pad(lists, ((0, pad), (0, 0), (0, 0)))
    lists_lo_p = jnp.pad(lists_lo, ((0, pad), (0, 0), (0, 0)))
    cent_p = jnp.pad(centroids.astype(jnp.float32), ((0, pad), (0, 0)))
    ids_p = jnp.pad(list_ids, ((0, pad), (0, 0)), constant_values=-1)
    msk_p = jnp.pad(list_mask, ((0, pad), (0, 0)))
    bq_p = jnp.pad(bucket_q, ((0, pad), (0, 0)), constant_values=-1)
    # Masked residual norms (precomputed index data): padded rows carry a
    # huge norm so they never win a top-k.
    norms_p = jnp.pad(resid_norms.astype(accum_dtype), ((0, pad), (0, 0)))
    r2_all = jnp.where(msk_p > 0, norms_p, jnp.asarray(1e30, accum_dtype))
    # mult·k-wide per-(list, slot) shortlist: selection runs on the
    # compute dtype's noisy scores; the exact rerank recovers boundary
    # swaps. Width is the bf16 recall/speed dial (config
    # ann_shortlist_mult): noisy scores push true neighbors below the
    # within-list cut, and widening the cut is what recovers them —
    # measured on clustered 128-d data, mult 2 → recall@10 0.92 at ~115k
    # q/s/chip, mult 4 → 0.98 at ~65k (f32 scans sit at the 0.99 probing
    # ceiling already at mult 2).
    fused = str(fused).lower()
    if fused not in ("auto", "on", "off"):
        raise ValueError(
            f"ann_fused_scan={fused!r}: expected 'auto', 'on' or 'off'"
        )
    # The kernel computes and emits f32 scores: float64 accum configs
    # (supported by the XLA path) must not silently lose precision.
    f32_ok = jnp.dtype(accum_dtype) != jnp.float64
    use_fused = _debug_stage in (None, "rerank_norescore") and (
        (fused == "on" and f32_ok)
        or (
            fused == "auto"
            and f32_ok
            and config.backend_is_tpu()
            and _fused_scan_fits(C, maxlen, d, compute_dtype)
        )
    )
    # Exact selection needs no shortlist slack when its scores answer
    # directly (the global top-k is contained in exact per-(list, slot)
    # top-k): blk_k = k halves the fused kernel's extraction passes AND
    # the gather-back pool. The rerank path keeps the mult·k width — its
    # slack absorbs bf16 score-vs-f32-rank mismatch, which exactness of
    # the *selection* cannot remove.
    # Extraction width is the rerank-on speed/recall dial (round-4 stage
    # profile: the fused kernel's per-slot extraction cost scales with
    # blk_k). Round-5 same-run sweep at the bench point (k=10, exact-GT
    # recall@10 beside each): extract 10 ("narrow") 183k @ 0.9577; 12 →
    # 177k @ 0.9700; 14 → 169k @ 0.9706; 20 ("wide" = mult·k) → 153k @
    # 0.9706 — the rerank's R = 2k selection caps what extra extraction
    # can feed it, so ~1.2k captures the full rescue at +16% q/s.
    # "auto" (default) = ceil(1.2·k) under fused rerank; an integer sets
    # the width in rows; "narrow"/"wide" = k / mult·k — config
    # ann_extract. The XLA (non-fused) scan always extracts mult·k: its
    # APPROXIMATE per-slot selection needs the slack exactness removes.
    ext = str(extract).lower()
    ext_rows = int(ext) if ext.isascii() and ext.isdigit() else None
    if ext_rows is None and ext not in ("auto", "wide", "narrow"):
        raise ValueError(
            f"ann_extract={extract!r}: expected 'auto', 'wide', 'narrow' "
            "or an integer row width"
        )
    if use_fused:
        if not rerank:
            blk_k = min(k, maxlen)  # exact selection answers directly
        elif ext_rows is not None:
            blk_k = min(max(ext_rows, k), maxlen)
        elif ext == "narrow":
            blk_k = min(k, maxlen)
        elif ext == "wide":
            blk_k = min(shortlist_mult * k, maxlen)
        else:  # auto: ceil(1.2·k), the measured rerank frontier point
            blk_k = min(-(-12 * k // 10), maxlen)
    else:
        blk_k = min(shortlist_mult * k, maxlen)
    if nprobe * blk_k < k:
        raise ValueError(
            f"k={k} exceeds the bucketed candidate pool nprobe*maxlen="
            f"{nprobe * maxlen}; raise nprobe or use mode='dense'"
        )

    if use_fused:
        # Fused Pallas scan+selection (ops/pallas_kernels.py): per-list
        # residual GEMM + EXACT per-slot top-blk_k in one kernel, the
        # (maxlen, C) score tile VMEM-resident. The per-(list, slot) query
        # residuals are pre-gathered OUTSIDE the kernel — dynamic row
        # gathers don't belong inside; XLA fuses gather + f32 subtract +
        # compute-dtype cast into one loop writing the bf16 buffer the
        # kernel then streams sequentially. (The same hoist measured
        # no-effect for the XLA scan — benchmarks/README.md — because
        # there the gather cost merely moves; the kernel REQUIRES it.)
        # C stays at its 8-multiple: Mosaic masks the non-128 lane tail of
        # the (maxlen, C) score tile, and NOT padding C to 128 saves 25%
        # of the pre-gather + qv streaming HBM traffic at the bench shape.
        qv_all = (
            queries.astype(jnp.float32)[jnp.maximum(bq_p, 0)]
            - cent_p[:, None, :]
        ).astype(compute_dtype)  # (nlist_p, C, d)
        fd, fp = ivf_scan_select_pallas(
            qv_all, lists_lo_p, r2_all.astype(jnp.float32), blk_k,
            keep_pad=True, interpret=not config.backend_is_tpu(),
        )
        # (nlist_p, C, blk_k_pad) for the gather-back epilogue, KEEPING
        # the kernel's 8-multiple selection-lane pad: gathering aligned
        # rows and slicing to blk_k after measured ~1.7x faster than
        # slicing first (the slice materializes an unaligned-row copy).
        res_d = jnp.swapaxes(fd, 1, 2).astype(accum_dtype)
        res_p = jnp.swapaxes(fp, 1, 2)
    else:
        def _block_d2(b):
            """One list-block's (L, C, maxlen) within-list scores — shared
            by the real scan body and the scan_nosel profiling cut so the
            two measure the identical scoring pipeline."""
            qidx = jax.lax.dynamic_slice(bq_p, (b * list_block, 0), (list_block, C))
            # Query residuals q − c_list, formed in f32 BEFORE the compute-
            # dtype cast: bf16-rounding q and c separately leaves absolute-
            # magnitude noise that does not cancel in the subtraction.
            cent = jax.lax.dynamic_slice(cent_p, (b * list_block, 0), (list_block, d))
            qv = (
                queries.astype(jnp.float32)[jnp.maximum(qidx, 0)]  # (L, C, d)
                - cent[:, None, :]
            ).astype(compute_dtype)
            rows = jax.lax.dynamic_slice(
                lists_lo_p, (b * list_block, 0, 0), (list_block, maxlen, d)
            )
            r2 = jax.lax.dynamic_slice(r2_all, (b * list_block, 0), (list_block, maxlen))
            # Batched MXU GEMM: each list scores only its assigned queries.
            # Full precision for f32 compute (TPU's DEFAULT is bf16-mantissa).
            from spark_rapids_ml_tpu.ops.gram import mm_precision

            with mm_precision(compute_dtype):
                qr = jnp.einsum(
                    "lcd,lmd->lcm", qv, rows, preferred_element_type=accum_dtype
                )
            # Within-list ranking score ‖δ‖² − 2(q−c)·δ: the per-(query, list)
            # ‖q−c‖² constant joins at gather-back (it cannot change a
            # within-list argmin) and the rerank restores true distances.
            return r2[:, None, :] - 2.0 * qr  # (L, C, maxlen)

        def body(_, b):
            d2 = _block_d2(b)
            # 0.95 within-list recall: recall_target=1.0 degenerates to a
            # full per-row sort (4x the einsum+selection cost); misses
            # concentrate at the k-th boundary and the 2k shortlist +
            # rerank absorbs them.
            # (Round-3 negative result: an exact min+argmin pre-reduction
            # over size-8 groups measured 3x SLOWER — the 8-wide group
            # axis lands on the 128-lane dimension and wastes 15/16 of
            # every vreg — and cost ~2% recall from within-list winner
            # collisions. See benchmarks/README.md.)
            bd, bpos = jax.lax.approx_min_k(
                d2.reshape(list_block * C, maxlen), blk_k, recall_target=0.95
            )
            # Positions, not ids: the in-scan per-row id gather measured
            # ~2x the GEMM+selection cost; ids resolve once for winners.
            return _, (
                bd.reshape(list_block, C, blk_k),
                bpos.reshape(list_block, C, blk_k).astype(jnp.int32),
            )

        def body_nosel(_, b):
            # Profiling cut (_debug_stage="scan_nosel"): the einsum + d2
            # stay live (same _block_d2 as the real body), the
            # approx_min_k selection is replaced by a slice.
            d2 = _block_d2(b)
            return _, (
                d2[:, :, :blk_k],
                jnp.broadcast_to(
                    jax.lax.broadcasted_iota(jnp.int32, (1, 1, blk_k), 2),
                    (list_block, C, blk_k),
                ),
            )

        _, (res_d, res_p) = jax.lax.scan(
            body_nosel if _debug_stage == "scan_nosel" else body,
            None, jnp.arange(nblk),
        )
        res_d = res_d.reshape(nblk * list_block, C, blk_k)
        res_p = res_p.reshape(nblk * list_block, C, blk_k)
        if _debug_stage in ("scan", "scan_nosel"):
            # Profiling cut: bucketing + the blocked residual-GEMM scan
            # stay live; candidate gather-back and final selection dropped.
            live = (res_d.sum() + res_p.sum().astype(accum_dtype)).astype(accum_dtype)
            return (
                probe_d2[:, :k].astype(accum_dtype)
                + live
                + (bucket_q.sum() + pair_slot.sum()).astype(accum_dtype),
                jnp.broadcast_to(pair_list[:, :1], (q, k)).astype(jnp.int64),
            )

    # Gather each query's candidates back from its (list, slot) buckets,
    # completing the residual identity with the probe stage's ‖q−c‖² term
    # so scores are comparable ACROSS lists at the shortlist top-k.
    ps = jnp.maximum(pair_slot, 0)
    # [..., :blk_k]: no-op for the XLA path; drops the fused kernel's
    # selection-lane pad AFTER the aligned gather (see above).
    cand_d = (
        res_d[pair_list, ps][..., :blk_k]
        + probe_d2.astype(accum_dtype)[:, :, None]
    )
    cand_pos = res_p[pair_list, ps][..., :blk_k]
    dropped = (pair_slot < 0)[:, :, None]
    cand_d = jnp.where(dropped, jnp.inf, cand_d).reshape(q, nprobe * blk_k)
    cand_pos = jnp.where(dropped, 0, cand_pos).reshape(q, nprobe * blk_k)
    cand_list = jnp.broadcast_to(
        pair_list[:, :, None], (q, nprobe, blk_k)
    ).reshape(q, nprobe * blk_k)
    if not rerank:
        # Residual-identity scores ARE comparable across lists (the probe
        # term was added above); answering from them skips the (q, R, d)
        # raw-row gather — the most expensive post-scan op (1.3-1.8x q/s
        # for 0.005-0.017 recall@10; 1.8x / -0.017 measured at the
        # clustered 768-d bench shape — config ann_rerank).
        # approx_min_k, not top_k: top_k over the (q, nprobe·blk_k) pool
        # is a full per-row sort (see gt path); the 0.99-target partial
        # reduce answers the same queries measurably faster.
        bd, pos = jax.lax.approx_min_k(cand_d, k, recall_target=0.99)
        neg = -bd
        wl = jnp.take_along_axis(cand_list, pos, axis=1)
        wp = jnp.take_along_axis(cand_pos, pos, axis=1)
        ids_k = ids_p[wl, wp]
        # Padded-row candidates carry the finite r2 sentinel (~1e30), not
        # inf — map them to the documented (+inf, -1) missing contract.
        missing = jnp.isinf(neg) | (ids_k < 0)
        win_ids = jnp.where(missing, -1, ids_k)
        return jnp.where(missing, jnp.inf, jnp.maximum(-neg, 0.0)), win_ids
    # Exact rerank (the ScaNN two-stage): select an R = width·k shortlist
    # by approximate score, rescore exactly in f32 from the stored rows.
    # The (q, R, d) raw-row gather is the dominant rerank cost and scales
    # linearly with R. Auto width: 2·mult for the approx XLA scan (sized
    # for its PartialReduce selection noise), mult for the fused kernel —
    # with EXACT per-slot selection the extra pool bought nothing
    # (measured same-run at the bench shape: rw 4 → 132.9k q/s, rw 2 →
    # 148.7k, recall@10 0.9706 identical to 4 decimals).
    auto_w = shortlist_mult if use_fused else 2 * shortlist_mult
    R = min((rerank_width or auto_w) * k, nprobe * blk_k)
    negd_R, posR = jax.lax.approx_min_k(cand_d, R, recall_target=0.99)
    negR = -negd_R
    wl = jnp.take_along_axis(cand_list, posR, axis=1)  # (q, R)
    wp = jnp.take_along_axis(cand_pos, posR, axis=1)
    # Flat single-level id gather (same lesson as the row gather below:
    # the 2-level [wl, wp] form lowers poorly in-graph).
    ids_R = ids_p.reshape(-1)[wl * maxlen + wp]  # (q, R); -1 = padded row
    # (Round-4 negative result: rescoring from the bf16 residual
    # reconstruction c + r̃ — dropping the raw f32 lists from the graph —
    # measured BOTH slower (141 vs 151k q/s: two gathers + extra
    # elementwise beat one f32 row gather, which is cheap) and lower
    # recall (0.9653 vs 0.9706). The f32 row gather stays.)
    if _debug_stage == "rerank_norescore":
        # Profiling cut: R-selection + id resolution live, the (q, R, d)
        # row gather + exact rescore dropped — isolates the rescore's
        # IN-GRAPH cost (standalone it measures ~0.02 ms).
        exact_d = jnp.where(ids_R < 0, jnp.inf, -negR)
    else:
        # Flat single-level row gather: the 2-level [wl, wp] batched
        # gather lowers poorly inside the full query graph (measured
        # ~2.9 ms in-graph vs 0.02 ms standalone); flattening to one
        # row-index into the (nlist·maxlen, d) view gives XLA the simple
        # leading-axis row-gather emitter.
        rows_R = lists_p.reshape(-1, d)[wl * maxlen + wp].astype(accum_dtype)
        diff = rows_R - queries.astype(accum_dtype)[:, None, :]
        exact_d = jnp.sum(diff * diff, axis=2)  # (q, R) — direct, exact f32
    exact_d = jnp.where((ids_R < 0) | jnp.isinf(-negR), jnp.inf, exact_d)
    neg, pos = jax.lax.top_k(-exact_d, k)
    win_ids = jnp.where(jnp.isinf(neg), -1, jnp.take_along_axis(ids_R, pos, axis=1))
    return jnp.maximum(-neg, 0.0), win_ids


def _residual_index_data(lists, centroids, compute_dtype, chunk: int = 64):
    """(resid_norms f32, lists_lo compute-dtype) for the bucketed scan —
    the residual-encoded index-side device data (see _bucketed_core).
    ``lists`` may have more rows than ``centroids`` (sharding pad): pad
    centroids with zeros — pad lists are never probed.

    Large single-device indexes stream through a ``lax.map`` over list
    chunks: the f32 residual intermediate of a multi-GB index would
    otherwise transiently double the index's HBM footprint."""
    nlist, maxlen, d = lists.shape
    cpad = jnp.pad(
        jnp.asarray(centroids, jnp.float32),
        ((0, nlist - centroids.shape[0]), (0, 0)),
    )
    single = getattr(lists.sharding, "num_devices", 1) == 1 if hasattr(
        lists, "sharding"
    ) else True
    while chunk > 1 and nlist % chunk:
        chunk //= 2  # largest power-of-two divisor; 1 always divides
    if single and nlist % chunk == 0 and lists.size * 4 > 2**30:
        def f(args):
            lb, cb = args
            r = lb.astype(jnp.float32) - cb[:, None, :]
            return jnp.sum(jnp.square(r), axis=2), r.astype(compute_dtype)

        norms, lo = jax.lax.map(
            f,
            (
                lists.reshape(nlist // chunk, chunk, maxlen, d),
                cpad.reshape(nlist // chunk, chunk, d),
            ),
        )
        return norms.reshape(nlist, maxlen), lo.reshape(nlist, maxlen, d)
    resid = lists.astype(jnp.float32) - cpad[:, None, :]
    return jnp.sum(jnp.square(resid), axis=2), resid.astype(compute_dtype)


@functools.lru_cache(maxsize=32)
def _ivf_query_fn(k: int, nprobe: int, cd: str, ad: str, mode: str = "auto",
                  slack: float = 1.5, shortlist_mult: int = 2,
                  rerank: bool = True, fused: str = "auto",
                  rerank_width: int = 0, extract: str = "wide",
                  _debug_stage=None):
    """Build the jitted IVF query executor.

    Two TPU execution strategies, both avoiding the GPU-idiomatic per-query
    list gather (a (q, nprobe, maxlen, d) intermediate, gather-bound on TPU):

    * ``dense`` — every block of lists is scored against EVERY query with one
      (q, d) × (d, block·maxlen) MXU GEMM; non-probed (query, list) pairs are
      masked to +inf. Bandwidth-optimal (the database streams through HBM
      exactly once per query batch) and exact within probed lists, but pays
      nlist/nprobe× the probed FLOPs — the right trade when a large fraction
      of lists is probed.
    * ``bucketed`` — ScaNN-style query grouping: queries are bucketed by
      probed list with a fixed per-list capacity C, each list block scores
      only its assigned queries with a batched (block, C, d) × (block, d,
      maxlen) GEMM, and per-(list, slot) top-k candidates are gathered back
      per query for the final merge. FLOPs ≈ slack × the probed work — at
      nprobe/nlist = 1/32 that is ~16× fewer than dense. Capacity overflow
      (C per _bucketed_capacity: slack-scaled expected load, with a
      bounded identical-query coverage floor) drops a query's coverage of
      an over-subscribed list — the standard fixed-capacity ANN trade; C
      clamps at q, where no drops are possible.

    ``mode="auto"`` picks dense when nprobe·4 ≥ nlist (probing ≥ a quarter of
    the lists: FLOP waste ≤ 4× and exactness is kept — this covers the
    nprobe = nlist "exact" configuration), else bucketed.
    """
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)
    LIST_BLOCK = 32

    @ledgered_jit("knn.ivf_query_dense")
    def query_dense(centroids, lists, list_ids, list_mask, queries):
        q = queries.shape[0]
        nlist, maxlen, d = lists.shape
        qc = queries.astype(compute_dtype)
        cd2 = sq_euclidean(qc, centroids.astype(compute_dtype), accum_dtype=accum_dtype)
        _, probe = jax.lax.top_k(-cd2, nprobe)  # (q, nprobe)
        # (q, nlist) probe-membership mask.
        probe_mask = (
            jnp.zeros((q, nlist), jnp.bool_)
            .at[jnp.arange(q)[:, None], probe]
            .set(True)
        )

        nblk = -(-nlist // LIST_BLOCK)
        pad = nblk * LIST_BLOCK - nlist
        lists_p = jnp.pad(lists, ((0, pad), (0, 0), (0, 0)))
        ids_p = jnp.pad(list_ids, ((0, pad), (0, 0)), constant_values=-1)
        msk_p = jnp.pad(list_mask, ((0, pad), (0, 0)))
        pm_p = jnp.pad(probe_mask, ((0, 0), (0, pad)))

        def body(carry, b):
            best_d, best_i = carry  # (q, k) running top-k
            rows = jax.lax.dynamic_slice(
                lists_p, (b * LIST_BLOCK, 0, 0), (LIST_BLOCK, maxlen, d)
            ).reshape(LIST_BLOCK * maxlen, d)
            ids = jax.lax.dynamic_slice(
                ids_p, (b * LIST_BLOCK, 0), (LIST_BLOCK, maxlen)
            ).reshape(LIST_BLOCK * maxlen)
            msk = jax.lax.dynamic_slice(
                msk_p, (b * LIST_BLOCK, 0), (LIST_BLOCK, maxlen)
            ).reshape(LIST_BLOCK * maxlen)
            pm = jax.lax.dynamic_slice(
                pm_p, (0, b * LIST_BLOCK), (q, LIST_BLOCK)
            )  # (q, LIST_BLOCK)
            d2 = sq_euclidean(
                qc, rows.astype(compute_dtype), accum_dtype=accum_dtype
            )  # (q, LIST_BLOCK·maxlen) — the MXU GEMM
            keep = pm[:, :, None] & (msk.reshape(LIST_BLOCK, maxlen) > 0)[None]
            d2 = jnp.where(keep.reshape(q, -1), d2, jnp.inf)
            # TPU-native partial top-k per block (exact top_k sorts the whole
            # 12k-wide row and dominates the query time). recall_target=1.0
            # keeps the PartialReduce lowering but guarantees exact recall,
            # preserving the exact-within-probed-lists IVF contract; the only
            # approximation in this method stays the probing itself. A block
            # contributes at most LIST_BLOCK*maxlen candidates, so clamp the
            # per-block k to that (the cross-block merge restores full k).
            blk_k = min(k, LIST_BLOCK * maxlen)
            blk_d, blk_pos = jax.lax.approx_min_k(d2, blk_k, recall_target=1.0)
            blk_i = ids[blk_pos]  # (q, blk_k) gather from the block's ids
            cat_d = jnp.concatenate([best_d, blk_d], axis=1)
            cat_i = jnp.concatenate([best_i, blk_i], axis=1)
            neg, pos = jax.lax.top_k(-cat_d, k)
            return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

        init = (
            jnp.full((q, k), jnp.inf, accum_dtype),
            jnp.full((q, k), -1, ids_p.dtype),
        )
        (dists, ids), _ = jax.lax.scan(body, init, jnp.arange(nblk))
        return dists, ids

    @ledgered_jit("knn.ivf_probe")
    def probe_bucketed(centroids, queries):
        # Fused probe kernel (same gate family as the scan kernel): f32
        # centroid GEMM + EXACT packed-key top-nprobe per query in one
        # Pallas call — removes both the XLA approx_min_k's cost (the
        # probe stage's dominant op) and its recall_target=0.95
        # approximation, making probe coverage exact. f64 accum configs
        # and non-dividing query blocks fall through to the XLA path.
        fu = str(fused).lower()
        q = queries.shape[0]
        nlist_, d_ = centroids.shape
        qb = min(512, q)
        # "on" means "use wherever representable" (same semantics as the
        # scan gate's f64 carve-out): infeasible shapes — f64 accum,
        # non-dividing query batches, nlist past the packed-key bits or
        # the VMEM tile — fall through to the XLA probe either way.
        use_kernel = (
            fu == "on"
            or (fu == "auto" and config.backend_is_tpu())
        ) and (
            jnp.dtype(accum_dtype) != jnp.float64
            and q % qb == 0
            and _probe_select_fits(nlist_, d_, qb)
        )
        if use_kernel:
            probe, probe_d2 = probe_select_pallas(
                centroids, queries, nprobe, block_q=qb,
                interpret=not config.backend_is_tpu(),
            )
            return probe, probe_d2
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        # Full-f32 centroid distances: the values feed the residual
        # identity's cross-list ‖q−c‖² term, where bf16-magnitude noise
        # would corrupt the candidate shortlist ordering. The GEMM is
        # (q, nlist, d) — trivial FLOPs next to the selection.
        with mm_precision(jnp.float32):
            cd2 = sq_euclidean(
                queries.astype(jnp.float32), centroids.astype(jnp.float32),
                accum_dtype=jnp.float32,
            )
        # Probing is this executor's approximation already; an exact top_k
        # here costs more than the whole list scan (it sorts every
        # (q, nlist) row), so select probes approximately too — misses are
        # distant lists that contribute the least recall.
        probe_d2, probe = jax.lax.approx_min_k(cd2, nprobe, recall_target=0.95)
        return probe.astype(jnp.int32), probe_d2

    @ledgered_jit("knn.ivf_query_bucketed")
    def core_bucketed(queries, probe, probe_d2, centroids, lists, list_ids,
                      list_mask, n_valid, resid_norms, lists_lo):
        q = queries.shape[0]
        nlist = lists.shape[0]
        C = _bucketed_capacity(q, nprobe, nlist, slack)
        if _debug_stage == "dispatch":
            # Near-noop cut: measures the per-call dispatch floor of the
            # two-jit probe+core pipeline.
            return (
                queries[:, :k].astype(jnp.dtype(ad)),
                probe[:, :k].astype(jnp.int64),
            )
        if _debug_stage == "probe":
            return (
                probe_d2[:, :k].astype(jnp.dtype(ad)),
                probe[:, :k].astype(jnp.int64),
            )
        return _bucketed_core(
            queries, probe, probe_d2, lists, list_ids, list_mask,
            resid_norms, n_valid, k, nprobe, C, compute_dtype, accum_dtype,
            list_block=16, shortlist_mult=shortlist_mult, rerank=rerank,
            lists_lo=lists_lo, centroids=centroids, fused=fused,
            rerank_width=rerank_width, extract=extract,
            _debug_stage=_debug_stage,
        )

    @ledgered_jit("knn.ivf_probe_trivial")
    def _probe_trivial(centroids, queries):
        # Profiling stand-in for probe_bucketed (_debug_stage="dispatch"):
        # data-dependent but ~zero compute, so the two-jit pipeline's
        # dispatch overhead is measured WITHOUT the probe GEMM/selection
        # (the earlier cut returned real probe output and folded the
        # probe's device time into the "floor").
        probe = jnp.broadcast_to(
            jax.lax.broadcasted_iota(jnp.int32, (1, nprobe), 1),
            (queries.shape[0], nprobe),
        ) + (queries[:, :1] * 0).astype(jnp.int32)
        return probe, queries[:, :nprobe].astype(jnp.float32) * 0.0

    def query_bucketed(centroids, lists, list_ids, list_mask, queries, n_valid,
                       resid_norms, lists_lo):
        # Two dispatches, not one fused jit: XLA schedules the monolithic
        # probe+scan+rerank graph measurably worse (+20% wall) than the
        # same stages compiled separately and pipelined by async dispatch.
        probe_fn = (
            _probe_trivial if _debug_stage == "dispatch" else probe_bucketed
        )
        probe, probe_d2 = probe_fn(centroids, queries)
        return core_bucketed(
            queries, probe, probe_d2, centroids, lists, list_ids, list_mask,
            n_valid, resid_norms, lists_lo,
        )

    def query(centroids, lists, list_ids, list_mask, queries,
              n_valid=None, resid_norms=None, lists_lo=None):
        # Host-side dispatch on the index shape (static under each jit).
        # n_valid: true query count when the batch is padded (default: all
        # rows are real). resid_norms / lists_lo: precomputed index-side
        # device data (f32 Σ(row−c)² and the compute-dtype RESIDUAL scan
        # copy) — computed here per call if absent; serving callers cache
        # them (the model does, via _ensure_dev_index).
        dense_auto = (
            nprobe * 4 >= lists.shape[0]
            and jnp.dtype(compute_dtype) == jnp.float32
        )
        # At bfloat16 compute the dense executor's raw-magnitude scores
        # suffer the recall collapse residual encoding exists to fix (its
        # "exact within probed lists" contract only holds at f32), so auto
        # routes everything to the bucketed executor there — with nprobe
        # near nlist its capacity clamps at q and it degenerates to a
        # dense-FLOPs scan WITH residual scoring + exact rerank.
        if mode == "dense" or (mode == "auto" and dense_auto):
            return query_dense(centroids, lists, list_ids, list_mask, queries)
        if n_valid is None:
            n_valid = queries.shape[0]
        if resid_norms is None or lists_lo is None:
            resid_norms, lists_lo = _residual_index_data(
                lists, centroids, compute_dtype
            )
        return query_bucketed(
            centroids, lists, list_ids, list_mask, queries,
            jnp.asarray(n_valid, jnp.int32), resid_norms, lists_lo,
        )

    return query


@functools.lru_cache(maxsize=32)
def _ivf_query_fn_sharded(
    k: int, nprobe: int, cd: str, ad: str, mesh: Mesh, slack: float = 1.5,
    shortlist_mult: int = 2,
    rerank: bool = True, fused: str = "auto", rerank_width: int = 0,
    extract: str = "wide",
):
    """Sharded IVF query: inverted lists sharded over the ``data`` mesh
    axis (BASELINE.json config #5's multi-host shape — a 10M×768 database
    does not fit one chip).

    Under ``shard_map``, every device probes the replicated centroids
    (identical (q, nprobe) global probe set), localizes the probe ids to
    its own list range (non-owned pairs marked -1 and satisfied by their
    owning device), runs the capacity-bucketed scorer over its local
    lists, and the per-device (q, k) exact-reranked candidates merge with
    one ``all_gather`` over ICI + a final top-k — communication is
    O(q·k·devices), independent of database size, the same merge shape as
    the exact KNN. Always the bucketed (approximate) executor; list ids
    stay global so returned ids need no translation.
    """
    compute_dtype = jnp.dtype(cd)
    accum_dtype = jnp.dtype(ad)
    n_data = mesh.shape[DATA_AXIS]

    def shard(cent_pad, lists, list_ids, list_mask, resid_norms, lists_lo,
              queries, n_valid, n_real):
        # cent_pad: (nlist_padded, d) f32 centroids, zero-padded to the
        # sharded list count and replicated; pad lists (columns >= n_real)
        # are masked to +inf so they are never probed.
        q = queries.shape[0]
        nlist_local = lists.shape[0]
        from spark_rapids_ml_tpu.ops.gram import mm_precision

        with mm_precision(jnp.float32):  # exact ‖q−c‖² (see probe_bucketed)
            cd2 = sq_euclidean(
                queries.astype(jnp.float32), cent_pad, accum_dtype=jnp.float32
            )
        pad_col = jax.lax.broadcasted_iota(jnp.int32, cd2.shape, 1) >= n_real
        cd2 = jnp.where(pad_col, jnp.inf, cd2)
        # Approximate probe selection, same trade as the single-device
        # bucketed executor (every device computes the identical set).
        probe_d2, probe = jax.lax.approx_min_k(cd2, nprobe, recall_target=0.95)
        probe = probe.astype(jnp.int32)  # global list ids, replicated
        lo = jax.lax.axis_index(DATA_AXIS).astype(jnp.int32) * nlist_local
        local = (probe >= lo) & (probe < lo + nlist_local)
        probe_local = jnp.where(local, probe - lo, -1)
        cent_local = jax.lax.dynamic_slice(
            cent_pad, (lo, jnp.zeros((), lo.dtype)), (nlist_local, cent_pad.shape[1])
        )
        C = _bucketed_capacity(q, nprobe, nlist_local * n_data, slack)
        dists, ids = _bucketed_core(
            queries, probe_local, probe_d2, lists, list_ids, list_mask,
            resid_norms, n_valid, k, nprobe, C, compute_dtype, accum_dtype,
            shortlist_mult=shortlist_mult, rerank=rerank,
            lists_lo=lists_lo, centroids=cent_local, fused=fused,
            rerank_width=rerank_width, extract=extract,
        )
        # Merge the per-device top-k: O(q·k·devices) over ICI.
        return mr.reduce_topk(dists, ids, k, DATA_AXIS)

    f = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(
            P(),
            P(DATA_AXIS, None, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None, None),
            P(),
            P(),
            P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,  # gathered candidates are value-replicated
    )
    jitted = ledgered_jit("knn.ivf_query_sharded", f)

    def query(centroids, lists, list_ids, list_mask, queries,
              n_valid=None, resid_norms=None, lists_lo=None):
        if n_valid is None:
            n_valid = queries.shape[0]
        if resid_norms is None or lists_lo is None:
            resid_norms, lists_lo = _residual_index_data(
                lists, centroids, compute_dtype
            )
        nlist_pad = lists.shape[0]
        cent_pad = jnp.pad(
            jnp.asarray(centroids, jnp.float32),
            ((0, nlist_pad - centroids.shape[0]), (0, 0)),
        )
        return jitted(
            cent_pad, lists, list_ids, list_mask, resid_norms, lists_lo,
            queries, jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(centroids.shape[0], jnp.int32),
        )

    return query


class _ANNParams(_NNParams):
    nlist = ParamDecl(
        "nlist",
        "number of IVF inverted lists (> 0)",
        TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )
    nprobe = ParamDecl(
        "nprobe",
        "number of lists probed per query (> 0)",
        TypeConverters.toInt,
        validator=ParamValidators.gt(0),
    )

    def __init__(self, uid=None):
        super().__init__(uid=uid)
        self.setDefault(nlist=32, nprobe=4)

    def getNlist(self) -> int:
        return self.getOrDefault(self.nlist)

    def getNprobe(self) -> int:
        return self.getOrDefault(self.nprobe)


class ApproximateNearestNeighbors(Estimator, _ANNParams, MLWritable, MLReadable):
    """IVF-Flat approximate KNN (spark-rapids-ml ApproximateNearestNeighbors
    shape, algorithm="ivfflat")."""

    _uid_prefix = "ApproximateNearestNeighbors"

    def __init__(self, uid=None, mesh: Optional[Mesh] = None):
        super().__init__(uid=uid)
        self._mesh = mesh

    def setK(self, value: int) -> "ApproximateNearestNeighbors":
        return self._set(k=value)

    def setNlist(self, value: int) -> "ApproximateNearestNeighbors":
        return self._set(nlist=value)

    def setNprobe(self, value: int) -> "ApproximateNearestNeighbors":
        return self._set(nprobe=value)

    def setMetric(self, value: str) -> "ApproximateNearestNeighbors":
        return self._set(metric=value)

    def _copy_extra_state(self, source):
        self._mesh = getattr(source, "_mesh", None)

    def _fit(self, dataset) -> "ApproximateNearestNeighborsModel":
        metric = self.getMetric()
        if metric == "inner_product":
            raise ValueError(
                "metric='inner_product' is supported by the exact "
                "NearestNeighbors only (IVF-Flat partitions by L2 "
                "proximity; MIPS needs a different quantizer)"
            )
        x = np.asarray(as_matrix(dataset, self.getFeaturesCol()))
        if metric == "cosine":
            # The index stores the UNIT-normalized (augmented) rows: L2 on
            # them is a monotone transform of cosine distance, so the
            # whole IVF machinery (quantizer, residual scan, rerank)
            # applies as-is.
            x = _normalized_rows(x, zero_slot=0)
        with trace_span("ivf build"):
            index = build_ivf_flat(
                x, nlist=self.getNlist(), seed=self.getSeed(), mesh=self._mesh
            )
        model = ApproximateNearestNeighborsModel(index=index)
        model.uid = self.uid
        self._copy_params_to(model)
        model._index_metric = metric
        return model


class ApproximateNearestNeighborsModel(Model, _ANNParams, MLWritable, MLReadable):
    _uid_prefix = "ApproximateNearestNeighborsModel"
    # device index + residual cache rebuild via _ensure_dev_index on use.
    # _index_metric is NOT transient: the metric's normalization is baked
    # into the stored lists, so it travels with the index (pickle AND
    # save/load) rather than re-deriving from the mutable metric param —
    # a _set(metric=...) after load must hit the built-under guard, not
    # silently mis-score (round-3 advisor finding).
    _transient_attrs = ("_mesh", "_dev_index", "_resid_cache", "_shard_mesh")

    def __init__(self, index: Optional[IVFFlatIndex] = None, uid=None):
        super().__init__(uid=uid)
        self.index = index
        self._dev_index = None  # device-resident index cache
        self._resid_cache = None  # bucketed executor's residual data (lazy)
        self._shard_mesh = None  # set by shard_index()

    def _model_data(self):
        data = {
            "centroids": self.index.centroids,
            "lists": self.index.lists,
            "list_ids": self.index.list_ids.astype(np.float64),
            "list_mask": self.index.list_mask,
        }
        fit_metric = getattr(self, "_index_metric", None)
        if fit_metric is not None:
            # Persisted as a KNN_METRICS ordinal (the payload store is
            # numeric); legacy saves without it fall back to the param.
            data["fit_metric"] = np.array(
                [KNN_METRICS.index(fit_metric)], dtype=np.float64
            )
        return data

    @classmethod
    def _from_model_data(cls, uid, data):
        index = IVFFlatIndex(
            centroids=data["centroids"],
            lists=data["lists"],
            list_ids=data["list_ids"].astype(np.int64),
            list_mask=data["list_mask"],
        )
        model = cls(index=index, uid=uid)
        code = data.get("fit_metric")
        if code is not None:
            model._index_metric = KNN_METRICS[int(np.asarray(code).reshape(-1)[0])]
        return model

    def _copy_extra_state(self, source):
        self.index = source.index
        self._dev_index = None
        self._resid_cache = None
        self._index_metric = getattr(source, "_index_metric", None)
        # Re-run the sharded placement (it pads nlist to a device multiple
        # — an invariant _ensure_dev_index alone would not restore).
        src_mesh = getattr(source, "_shard_mesh", None)
        self._shard_mesh = None
        if src_mesh is not None and self.index is not None:
            self.shard_index(src_mesh)

    def shard_index(self, mesh: Optional[Mesh] = None) -> "ApproximateNearestNeighborsModel":
        """Shard the inverted lists over the mesh's ``data`` axis — the
        capacity path for databases ≫ one chip's HBM (BASELINE.json config
        #5: 10M×768 on multi-host). nlist pads to a device multiple (pad
        lists are never probed: the centroid set stays unpadded). Queries
        then execute with the sharded bucketed executor (approximate:
        probing + capacity + 0.95-recall shortlists + exact rerank) and an
        O(q·k·devices) all_gather merge. Returns self (fluent)."""
        mesh = mesh or default_mesh()
        n_data = mesh.shape[DATA_AXIS]
        idx = self.index
        nlist = idx.lists.shape[0]
        pad = (-nlist) % n_data
        from jax.sharding import NamedSharding

        def put(arr, spec, pad_width, fill=0):
            if pad:
                arr = np.pad(arr, pad_width, constant_values=fill)
            return jax.device_put(arr, NamedSharding(mesh, spec))

        lists = put(idx.lists, P(DATA_AXIS, None, None), ((0, pad), (0, 0), (0, 0)))
        ids = put(idx.list_ids, P(DATA_AXIS, None), ((0, pad), (0, 0)), fill=-1)
        mask = put(idx.list_mask, P(DATA_AXIS, None), ((0, pad), (0, 0)))
        cent = jax.device_put(np.asarray(idx.centroids), NamedSharding(mesh, P()))
        self._dev_index = (cent, lists, ids, mask)
        self._resid_cache = None  # built lazily, keyed by compute_dtype
        self._shard_mesh = mesh
        return self

    def _ensure_dev_index(self):
        """Upload the index to device ONCE per model — the reference
        re-uploads its model matrix every batch (SURVEY.md §3.2,
        rapidsml_jni.cu:85); repeated query batches here reuse residents."""
        if self._dev_index is None:
            self._dev_index = (
                jnp.asarray(self.index.centroids),
                jnp.asarray(self.index.lists),
                jnp.asarray(self.index.list_ids),
                jnp.asarray(self.index.list_mask),
            )
        return self._dev_index

    def _ensure_resid_data(self, cd):
        """The bucketed executor's residual scan copy + norms, built lazily
        (dense-dispatch queries never pay its +50% index HBM) and KEYED BY
        compute dtype — a config change between queries rebuilds it rather
        than silently scanning at the stale precision."""
        cd = jnp.dtype(cd)
        cache = getattr(self, "_resid_cache", None)
        if cache is None or cache[0] != cd:
            cent, lists = self._dev_index[0], self._dev_index[1]
            self._resid_cache = (cd, *_residual_index_data(lists, cent, cd))
        return self._resid_cache[1], self._resid_cache[2]

    def kneighbors(
        self, queries: np.ndarray, k: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Approximate (distances, indices) under ``metric`` — euclidean
        (default) / sqeuclidean / cosine — ascending.

        IVF semantics: only the ``nprobe`` nearest lists are searched. If the
        probed lists hold fewer than k valid points for some query, the tail
        entries of that query's result carry index -1 and distance +inf
        ("fewer than k found" — same convention as IVF in cuML/FAISS).

        Precision note: with ``ann_rerank`` off, the fused TPU scan
        (``ann_fused_scan`` auto/on) returns distances quantized to ~24−⌈log₂
        maxlen⌉ mantissa bits — its exact selection packs candidate ids into
        the low bits of the f32 score key. Neighbor IDs are unaffected and
        the default rerank recomputes full-precision distances; set
        ``ann_fused_scan="off"`` if rerank-off configs need full-f32 values.
        """
        if self.index is None:
            raise RuntimeError("model has no index (unfitted?)")
        k = self.getK() if k is None else k
        n_db = int(self.index.list_mask.sum())
        if not 0 < k <= n_db:
            raise ValueError(f"k = {k} out of range (0, numRows = {n_db}]")
        nprobe = min(self.getNprobe(), self.index.centroids.shape[0])
        pool = nprobe * self.index.lists.shape[1]
        if pool < k:
            raise ValueError(
                f"candidate pool nprobe*maxlen = {pool} < k = {k}; "
                f"increase nprobe (or nlist granularity)"
            )
        metric = self.getMetric()
        fit_metric = getattr(self, "_index_metric", None)
        if fit_metric is None:
            # Loaded/legacy model: the persisted metric param IS the fit
            # metric (it was copied from the estimator at fit).
            fit_metric = metric
            self._index_metric = fit_metric
        if metric != fit_metric:
            raise ValueError(
                f"index was built under metric={fit_metric!r}; the "
                f"normalization is baked into the stored lists, so refit "
                f"to query with metric={metric!r}"
            )
        queries = np.asarray(queries)
        if metric == "cosine":
            queries = _normalized_rows(queries, zero_slot=1)  # index at fit
        q = queries.shape[0]
        bucket = bucket_rows(q, 64)
        qp, _ = pad_rows(queries, bucket)
        with trace_span("ivf query"):
            if self._shard_mesh is not None:
                fn = _ivf_query_fn_sharded(
                    k, nprobe, config.get("compute_dtype"),
                    config.get("accum_dtype"), self._shard_mesh,
                    shortlist_mult=int(config.get("ann_shortlist_mult")),
                    rerank=bool(config.get("ann_rerank")),
                    fused=str(config.get("ann_fused_scan")),
                    rerank_width=int(config.get("ann_rerank_width")),
                    extract=str(config.get("ann_extract")),
                )
            else:
                fn = _ivf_query_fn(
                    k, nprobe, config.get("compute_dtype"),
                    config.get("accum_dtype"),
                    shortlist_mult=int(config.get("ann_shortlist_mult")),
                    rerank=bool(config.get("ann_rerank")),
                    fused=str(config.get("ann_fused_scan")),
                    rerank_width=int(config.get("ann_rerank_width")),
                    extract=str(config.get("ann_extract")),
                )
            cent, lists, ids_dev, mask = self._ensure_dev_index()
            cd = jnp.dtype(config.get("compute_dtype"))
            # Mirror the executor's dispatch: dense (f32, wide probing)
            # never reads the residual cache — don't build it.
            dense = (
                self._shard_mesh is None
                and nprobe * 4 >= lists.shape[0]
                and cd == jnp.float32
            )
            rnorms, lists_lo = (None, None) if dense else self._ensure_resid_data(cd)
            d2, ids = jax.device_get(
                fn(cent, lists, ids_dev, mask, jnp.asarray(qp),
                   n_valid=q, resid_norms=rnorms, lists_lo=lists_lo)
            )
        ids = ids[:q].astype(np.int64)
        if metric == "sqeuclidean":
            return np.maximum(d2[:q], 0), ids
        if metric == "cosine":
            # unit rows: cosine distance = ||q - x||^2 / 2 (see exact path)
            return np.clip(d2[:q] / 2.0, 0, None), ids
        return np.sqrt(np.maximum(d2[:q], 0)), ids

    def _transform(self, dataset):
        x = as_matrix(dataset, self.getFeaturesCol())
        dists, idx = self.kneighbors(x)
        from spark_rapids_ml_tpu.core.dataset import with_column

        out = with_column(dataset, "knn_distances", dists)
        return with_column(out, "knn_indices", idx)


class KnnRowsJob(JobAlgorithm):
    """KNN's "sufficient statistic" IS the dataset (the model is the
    database, SURVEY §2.3): the job's state is the fed row blocks, on the
    host, in arrival order — not a device accumulator. Nothing folds,
    merges or steps; the daemon's row-store job stages the blocks under
    the same exactly-once rules, and its finalize builds the index from
    them and REGISTERS it for serving."""

    name = "knn"
    mergeable = False

    def zero_state(self):
        return []
