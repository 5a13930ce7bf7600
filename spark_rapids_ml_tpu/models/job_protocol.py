"""What a data-plane daemon job asks of the algorithm it accumulates for.

A job (serve/daemon.py ``_Job``) owns transport and bookkeeping: its lock
and the device lock, padding and placing a batch, stages and exactly-once,
replay memories, ``pass_id`` fencing, the pass cache, cross-daemon merges
of the state's leaves, snapshots. What depends on the algorithm is one
:class:`JobAlgorithm` object, defined beside that algorithm's streaming
functions and listed by wire name in ``models/jobs.py``. Nothing here
imports from ``serve/``, takes a lock, or sees a stage or a wire field:
the job holds its lock around every call and the device lock around every
member marked *dispatches* (docs/protocol.md "Adding a job algorithm").
The iterate of an ``iterative`` algorithm lives on this object; a pass's
statistics live in the job, which hands them to fold / step / finalize.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.ops import gram as gram_ops
from spark_rapids_ml_tpu.parallel.sharding import row_sharding


class JobAlgorithm:
    """Construction validates the creation params, runs the capacity gate
    and builds the update programs; a ``ValueError`` there is the first
    feed's clean refusal (no job is registered)."""

    #: Wire name (the request's ``algo``), for error texts.
    name = ""
    #: Feeds carry a label column / ``y`` array.
    needs_labels = False
    #: Multi-pass: ``step`` at each pass boundary, the iterate snapshotted.
    iterative = False
    #: The state is a tree of additive device statistics: stages merge by
    #: elementwise add and daemons exchange the leaves. False: the state
    #: is the rows themselves, on the host (the daemon's ``_RowsJob``).
    mergeable = True
    #: May keep its pass on the device for ``rescan``: gives ``fold_group``.
    #: Ask ``cacheable_for(params)``: a class may say less for some params.
    cacheable = False
    #: Name of the span the job opens around a pass boundary, or None.
    boundary_span: Optional[str] = None
    #: op → refusal text while no iterate is installed.
    no_iterate: Dict[str, str] = {}

    def __init__(self, n_cols: int, mesh, params: Dict[str, Any]):
        self.n_cols = n_cols
        self.mesh = mesh
        self.accum = jnp.dtype(config.get("accum_dtype"))
        self.v_sharding = row_sharding(mesh, ndim=1)

    def _require_gram_capacity(self) -> None:
        """Daemon job state is REPLICATED on every device, so a (d, d)
        block over the per-device budget must refuse at job creation —
        never an opaque device OOM mid-pass (docs/mesh.md)."""
        if gram_ops.require_gram_capacity(self.n_cols, self.mesh):
            raise gram_ops.GramCapacityError(
                f"the ({self.n_cols}, {self.n_cols}) job accumulator is over the "
                "per-device budget and daemon job state is replicated; "
                "use the in-memory fit with mesh_model_axis > 1 "
                "(docs/mesh.md) or raise SRML_GRAM_DEVICE_BUDGET_MB"
            )

    def _place_column(self, values, target: int, dtype):
        """A per-row column (labels, bag keys) padded to the batch's
        bucket and placed under the row sharding."""
        padded = np.zeros((target,), dtype=dtype)
        flat = np.asarray(values).reshape(-1)
        padded[: flat.shape[0]] = flat
        return jax.device_put(padded, self.v_sharding)

    # -- a feed, from the request alone (no job, no lock, no device) -------

    @classmethod
    def check_labels(cls, params: Dict[str, Any], y) -> None:
        """Refuse a feed's labels before any job exists."""

    @classmethod
    def check_first_batch(cls, params: Dict[str, Any], x) -> None:
        """Refuse the batch that would create the job."""

    @classmethod
    def cacheable_for(cls, params: Dict[str, Any]) -> bool:
        """Whether a job of these params may keep its pass on the device:
        the one answer, for the daemon's job and for the driver that
        decides whether to ask the caches at all."""
        return cls.cacheable

    def feed_mismatch(self, params: Dict[str, Any]) -> Optional[str]:
        """Why a feed's params are not this job's ("has …; feed carried …")."""
        return None

    # -- the iterate -------------------------------------------------------

    @property
    def installed(self) -> bool:
        return True

    def require_iterate(self, op: str) -> None:
        if not self.installed and op in self.no_iterate:
            raise ValueError(self.no_iterate[op])

    def check_seed(self, x) -> None:
        """Refuse a ``seed`` op's batch (before the job's lock)."""
        raise ValueError(f"seed only applies to kmeans jobs, not {self.name!r}")

    def seed(self, x) -> None:
        """Install the iterate from rows the caller has checked: the ``seed``
        op's, or the first unpartitioned batch fed (*dispatches*)."""
        self.check_seed(x)

    def iterate_arrays(self) -> Dict[str, np.ndarray]:
        """The iterate on the host, for ``get_iterate`` AND the snapshot (*dispatches*)."""
        raise NotImplementedError

    def install_iterate(self, arrays: Dict[str, np.ndarray]) -> None:
        """Validate + install, for ``set_iterate`` AND the restore (*dispatches*)."""
        raise NotImplementedError

    # -- a pass ------------------------------------------------------------

    def zero_state(self):
        """A pass's empty statistics at the installed iterate (*dispatches*)."""
        raise NotImplementedError

    def next_pass_state(self):
        """The job's state for the pass a ``step`` has just opened, asked
        right after it in the same hold of the device lock. A stage's state
        is always ``zero_state()``; an algorithm whose finished pass says
        something of the next one's statistics starts the JOB's from that
        (*dispatches*)."""
        return self.zero_state()

    def state_merged(self) -> None:
        """The job's state has just taken another daemon's (``merge_state``
        / ``reduce_mesh``): it is no longer this job's rows alone."""

    def place_columns(self, target: int, y=None, n: int = 0,
                      partition: Optional[int] = None, offset: int = 0) -> tuple:
        """The per-row device columns this batch's fold reads beside
        ``(xs, ms)`` — the ``n`` host labels ``y``, keys minted from
        ``(partition, offset)`` (``offset``: the rows the batch's stage —
        the pass, for a direct feed — held before) — each padded to
        ``target`` rows and placed under the row sharding; ``()`` where the
        fold reads none. With ``xs`` and ``ms`` they are the batch as the
        pass cache keeps it (*dispatches*)."""
        return ()

    def fold(self, state, xs, ms, columns: tuple = (), n: int = 0):
        """Fold one placed batch (``xs`` padded rows, ``ms`` its mask,
        ``columns`` what ``place_columns`` gave for it, ``n`` its true
        rows) against the iterate (*dispatches*)."""
        raise NotImplementedError

    def fold_group(self, state, xs: tuple, ms: tuple, columns: tuple = ()):
        """``fold`` over a run of placed batches of one shape in one program
        — ``columns`` holds, for each column ``place_columns`` gives, the
        run's tuple of it; ``rescan`` calls it back to back under ONE hold
        of the device lock: nothing else belongs here (*dispatches*)."""
        raise NotImplementedError

    def step(self, state, params: Dict[str, Any]) -> Dict[str, Any]:
        """A pass boundary: advance the iterate, return the algorithm's
        ``step`` ack fields in ack order (device scalars are read by the job
        once it has let go of the device lock) (*dispatches*)."""
        raise NotImplementedError

    def finalize(self, state, params: Dict[str, Any], rows: int,
                 iteration: int) -> Dict[str, np.ndarray]:
        """The model arrays of the ``finalize`` ack (*dispatches*)."""
        raise NotImplementedError
