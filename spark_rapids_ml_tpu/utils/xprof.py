"""Jit ledger: per-(function, shape-signature) device-cost attribution.

Five bench rounds of a flat headline produced zero insight into WHY,
because ``trace_span`` measures host wall-clock only: a phase that is 90% XLA compile looks identical to one
that is 90% HBM-bound GEMM. The reference could at least point Nsight at
its NVTX ranges (RapidsRowMatrix.scala:62,70); the TPU-native equivalent
of that attribution is XLA's own cost model — and it is queryable, not
GUI-bound. This module is the process-wide registry every jit entry
point in the package registers with (lint-enforced for ops/ and models/,
tests/test_lint.py), recording per (function name, shape signature):

* **compile count + compile seconds** — attributed exactly, via a
  ``jax.monitoring`` duration listener (``backend_compile_duration``
  events fire inside the wrapped call; a thread-local names the ledger
  entry on the stack). Cache *misses* (first call with a new signature:
  one trace + lowering, possibly a persistent-cache disk hit instead of
  a real compile) are counted separately.
* **flops / bytes accessed** — ``Lowered.cost_analysis()`` on the
  once-per-signature lowering (graceful ``None`` where the backend
  doesn't report them). The roofline numerators of "Distributed Linear
  Algebra with TPUs" (PAPERS.md 2112.09017): achieved flops/s against
  the MXU bound says compute-bound; achieved bytes/s against HBM says
  memory-bound; neither says compile- or feed-bound.
* **peak / argument / output bytes** — ``Compiled.memory_analysis()``,
  harvested only in the timing mode below (it needs an AOT compile).
* **execution wall-clock** — only with ``SRML_DEVICE_TIMING=1`` (config
  ``device_timing``): the wrapper brackets the call with
  ``block_until_ready``, so async dispatch is serialized per call. OFF
  by default: the production hot path keeps its pipelining, and the
  wrapper is signature lookup + counter bumps.
* **dispatch seconds** — always: the host seconds each top-level call
  spent on the wrapper's clock, signature found → dispatch returned
  (``srml_xla_dispatch_seconds_total{fn}``); over
  ``srml_xla_calls_total{fn}`` it is what one dispatch costs the host,
  or, once the runtime's queue is full, how long it held the caller at
  the device's pace.

With config ``metrics`` off the wrapper is a passthrough (one lock-free
``config.peek`` then straight into the jitted callable) — the acceptance
state for goldens and overhead checks.

Exposed as ``srml_xla_*`` metrics (docs/observability.md), a
``snapshot()`` for bench records (bench.py embeds the compile-vs-execute
breakdown each BENCH round; tools/perfcheck.py gates on it), and a
``format_table()`` achieved-vs-bound text roofline.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

from spark_rapids_ml_tpu.utils import metrics as metrics_mod

__all__ = [
    "ledgered_jit",
    "annotate",
    "snapshot",
    "reset",
    "format_table",
    "LEDGER",
]

#: Ledger telemetry (docs/observability.md "Jit ledger"). ``fn`` is the
#: registration name passed to :func:`ledgered_jit`.
_M_CALLS = metrics_mod.counter(
    "srml_xla_calls_total", "Calls through ledgered jit entry points, by fn"
)
_M_COMPILES = metrics_mod.counter(
    "srml_xla_compiles_total",
    "XLA backend compiles observed inside ledgered calls, by fn",
)
_M_COMPILE_SECONDS = metrics_mod.counter(
    "srml_xla_compile_seconds_total",
    "Seconds spent in XLA backend compilation inside ledgered calls, by fn",
)
_M_CACHE_MISSES = metrics_mod.counter(
    "srml_xla_cache_misses_total",
    "First calls with a new shape signature (trace + lowering), by fn",
)
_M_EXEC_SECONDS = metrics_mod.histogram(
    "srml_xla_execute_seconds",
    "Blocked (block_until_ready) execution wall-clock per call, by fn — "
    "recorded only in the SRML_DEVICE_TIMING mode",
)
_M_DISPATCH_SECONDS = metrics_mod.counter(
    "srml_xla_dispatch_seconds_total",
    "Host seconds top-level calls spent in the ledger wrapper's clock "
    "(signature found -> dispatch returned: first-call analysis and "
    "compile, jit dispatch, any wait on a full runtime queue), by fn",
)
_M_DISPATCH_DURATION = metrics_mod.histogram(
    "srml_xla_dispatch_duration_seconds",
    "The same clock as srml_xla_dispatch_seconds_total, one observation a "
    "top-level call (its sum and count are that counter's and "
    "srml_xla_calls_total's): the distribution a dispatch's p99 is read "
    "from, by fn",
)
_M_PCACHE_HITS = metrics_mod.counter(
    "srml_xla_persistent_cache_hits_total",
    "XLA programs served from the persistent compilation cache "
    "(utils/compile_cache.py) instead of recompiling",
)

_tls = threading.local()  # .current: (entry, sig) of the innermost call

_listener_lock = threading.Lock()
_listener_installed = False


def _enabled() -> bool:
    from spark_rapids_ml_tpu import config

    return bool(config.peek("metrics"))


def _device_timing() -> bool:
    from spark_rapids_ml_tpu import config

    return bool(config.peek("device_timing"))


def _ensure_listener() -> None:
    """Install the process-wide compile-duration listener (idempotent).

    ``/jax/core/compile/backend_compile_duration`` fires synchronously
    inside the jit call that compiles, so the thread-local set by the
    wrapper names exactly the entry whose program is being built —
    compile seconds are attributed, not guessed from first-call wall
    clock. Unattributed compiles (outside any ledgered call) are
    ignored here; they still show in jax's own logs."""
    global _listener_installed
    if _listener_installed:
        return
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        # Plain (no-duration) events: the persistent compilation cache
        # announces each disk hit here — the cheap half of ROADMAP 2b's
        # "compile once, serve forever" measured by the same ledger.
        jax.monitoring.register_event_listener(_on_plain_event)
        _listener_installed = True


def _on_plain_event(event: str, **kw: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _M_PCACHE_HITS.inc()


def _on_event(event: str, duration: float, **kw: Any) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    cur = getattr(_tls, "current", None)
    if cur is None:
        return
    entry, sig = cur
    with entry.lock:
        rec = entry.records.get(sig)
        if rec is None:
            return
        rec["compiles"] += 1
        rec["compile_s"] += float(duration)
    _M_COMPILES.inc(fn=entry.name)
    _M_COMPILE_SECONDS.inc(float(duration), fn=entry.name)


def _sig_of(x: Any, static: bool = False) -> Any:
    """Hashable shape signature of one argument, mirroring the jit-cache
    key axes: arrays by (shape, dtype); TRACED Python scalars by type
    only — jit compiles one executable per weak type, so keying them by
    value would fabricate a cache miss (and pay a ``lower()``) per
    distinct scalar streamed through the hot path; declared-static args
    (``static=True``) by value, because each value genuinely is its own
    compiled program."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(shape), str(dtype))
    if isinstance(x, (tuple, list)):
        return ("t", tuple(_sig_of(v, static) for v in x))
    if isinstance(x, dict):
        return (
            "d",
            tuple((str(k), _sig_of(v, static)) for k, v in sorted(x.items())),
        )
    if not static and isinstance(x, (bool, int, float, complex)):
        return ("w", type(x).__name__)
    try:
        return ("s", repr(x))
    except Exception:  # pragma: no cover - exotic unreprable arg
        return ("s", type(x).__name__)


def _fresh_record() -> Dict[str, Any]:
    return {
        "calls": 0,
        "compiles": 0,
        "compile_s": 0.0,
        "first_call_s": None,
        "flops": None,
        "bytes_accessed": None,
        "peak_bytes": None,
        "argument_bytes": None,
        "output_bytes": None,
        "execute_calls": 0,
        "execute_s": 0.0,
    }


class _Entry:
    """One registered jit entry point: records keyed by shape signature.

    ``analysis`` caches the once-per-signature cost/memory analysis
    SEPARATELY from the mutable records: :meth:`JitLedger.reset` clears
    counters at a bench epoch boundary, and the first post-reset call
    must not pay a retrace+lowering (or, in the timing mode, a backend
    compile) INSIDE the timed window it is supposed to measure."""

    def __init__(self, name: str):
        self.name = name
        self.lock = threading.Lock()
        self.records: Dict[Any, Dict[str, Any]] = {}
        self.analysis: Dict[Any, Dict[str, Any]] = {}

    def record(self, sig: Any) -> Tuple[Dict[str, Any], bool]:
        with self.lock:
            rec = self.records.get(sig)
            if rec is not None:
                return rec, False
            rec = self.records[sig] = _fresh_record()
            return rec, True


class JitLedger:
    """Process-wide name → entry registry (module singleton ``LEDGER``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}

    def entry(self, name: str) -> _Entry:
        with self._lock:
            e = self._entries.get(name)
            if e is None:
                e = self._entries[name] = _Entry(name)
            return e

    def names(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._entries))

    def reset(self) -> None:
        """Drop every recorded signature (tests / bench epoch boundaries).
        Entries AND their analysis caches survive — wrappers hold entry
        references, and re-analyzing inside a post-reset timed window
        would charge the window a retrace (plus a compile in the timing
        mode) that belongs to warmup."""
        with self._lock:
            entries = list(self._entries.values())
        for e in entries:
            with e.lock:
                e.records.clear()

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able per-fn view: per-signature records plus aggregates.
        ``flops_per_s`` / ``bytes_per_s`` are derived from the blocked
        execution clock, so they are present only after calls in the
        SRML_DEVICE_TIMING mode."""
        with self._lock:
            entries = sorted(self._entries.items())
        out: Dict[str, Any] = {}
        for name, e in entries:
            with e.lock:
                recs = {sig: dict(r) for sig, r in e.records.items()}
            if not recs:
                continue
            agg = {
                "calls": sum(r["calls"] for r in recs.values()),
                "compiles": sum(r["compiles"] for r in recs.values()),
                "compile_s": sum(r["compile_s"] for r in recs.values()),
                "cache_misses": len(recs),
                "execute_calls": sum(r["execute_calls"] for r in recs.values()),
                "execute_s": sum(r["execute_s"] for r in recs.values()),
            }
            flops = sum(
                r["flops"] * r["execute_calls"]
                for r in recs.values()
                if r["flops"] is not None
            )
            nbytes = sum(
                r["bytes_accessed"] * r["execute_calls"]
                for r in recs.values()
                if r["bytes_accessed"] is not None
            )
            if agg["execute_s"] > 0:
                agg["flops_per_s"] = flops / agg["execute_s"]
                agg["bytes_per_s"] = nbytes / agg["execute_s"]
            else:
                agg["flops_per_s"] = None
                agg["bytes_per_s"] = None
            agg["signatures"] = [
                {"sig": _render_sig(sig), **r} for sig, r in sorted(
                    recs.items(), key=lambda kv: -kv[1]["calls"]
                )
            ]
            out[name] = agg
        return out


def _render_sig(sig: Any) -> str:
    """Compact human form of a signature tuple: ``f32[512,2048]``-style."""

    def one(s: Any) -> str:
        if isinstance(s, tuple) and s and s[0] == "a":
            return f"{s[2]}[{','.join(str(d) for d in s[1])}]"
        if isinstance(s, tuple) and s and s[0] == "t":
            return "(" + ",".join(one(v) for v in s[1]) + ")"
        if isinstance(s, tuple) and s and s[0] == "d":
            return "{" + ",".join(f"{k}={one(v)}" for k, v in s[1]) + "}"
        if isinstance(s, tuple) and s and s[0] == "w":
            return str(s[1])
        if isinstance(s, tuple) and s and s[0] == "s":
            return str(s[1])
        return str(s)

    return one(sig)


LEDGER = JitLedger()


class LedgeredJit:
    """``jax.jit`` plus ledger accounting — drop-in callable.

    The wrapped computation is byte-identical to a bare ``jax.jit``:
    the ledger never touches values, only observes shapes, the compile
    events the call fires anyway, and (in the timing mode) the clock
    around a ``block_until_ready``."""

    def __init__(self, name: str, fun: Callable, jit_kwargs: Dict[str, Any]):
        import jax

        self.name = name
        self._fun = fun
        self._jit = jax.jit(fun, **jit_kwargs)
        self._entry = LEDGER.entry(name)
        #: AOT executables by signature (aot_prime): a hit dispatches the
        #: held ``Compiled`` directly — no jit-cache lookup, and by
        #: construction no compile. hits/misses are the serve plane's
        #: per-instance compile ledger (a miss = a call at a shape nothing
        #: primed, i.e. a potential lazy compile on the latency path).
        self._aot: Dict[Any, Any] = {}
        self.aot_hits = 0
        self.aot_misses = 0
        #: Optional ``(*args, **kwargs) -> None`` run once per ledgered
        #: dispatch, after the ledger's own counts and outside the timed
        #: dispatch — for a counter of the caller's beside them (the
        #: KMeans fold counts its dispatches by path).
        self.on_dispatch: Optional[Callable[..., None]] = None
        # Static args are value-keyed in the signature (each value is its
        # own compiled program); everything else is keyed like the jit
        # cache (shape/dtype for arrays, type for scalars).
        nums = jit_kwargs.get("static_argnums") or ()
        names = jit_kwargs.get("static_argnames") or ()
        self._static_nums = frozenset(
            (nums,) if isinstance(nums, int) else tuple(nums)
        )
        self._static_names = frozenset(
            (names,) if isinstance(names, str) else tuple(names)
        )
        self.__wrapped__ = fun
        self.__name__ = getattr(fun, "__name__", name)
        self.__doc__ = getattr(fun, "__doc__", None)

    # AOT escape hatch: callers that lower/compile explicitly keep
    # working through the wrapper.
    def lower(self, *args: Any, **kwargs: Any):
        return self._jit.lower(*args, **kwargs)

    def _sig(self, args, kwargs) -> Any:
        sig_args = (
            "t",
            tuple(
                _sig_of(a, static=i in self._static_nums)
                for i, a in enumerate(args)
            ),
        )
        if not kwargs:
            return sig_args
        return (
            sig_args,
            (
                "d",
                tuple(
                    (str(k), _sig_of(v, static=k in self._static_names))
                    for k, v in sorted(kwargs.items())
                ),
            ),
        )

    def aot_prime(self, *args: Any, **kwargs: Any) -> bool:
        """``lower().compile()`` this signature NOW and hold the executable
        on the wrapper — the "compile the whole program once, then serve"
        move (Flare / Julia-to-TPU, PAPERS.md 1703.08219, 1810.09868): a
        later call at the same signature dispatches the held ``Compiled``
        directly, so no first-request compile (and no jit dispatch-cache
        trace) ever sits on the latency path. ``args`` may be
        ``jax.ShapeDtypeStruct``s — nothing executes here. The compile is
        attributed to this entry in the ledger (it happens at registration
        time, where it belongs). Returns True when this signature was
        freshly compiled, False when already primed."""
        sig = self._sig(args, kwargs)
        if sig in self._aot:
            return False
        entry = self._entry
        # Record the signature so a later real call is not booked as a
        # fresh cache miss (the program it would have traced exists) —
        # and populate the cost analysis HERE, since that later call's
        # new=False branch will skip it (AOT-served shapes must not read
        # as flops/bytes-less in the roofline).
        rec, new = entry.record(sig)
        if new:
            with entry.lock:
                ana = entry.analysis.get(sig)
            if ana is None:
                ana = self._analyze(sig, args, kwargs, _device_timing())
                with entry.lock:
                    entry.analysis[sig] = ana
            with entry.lock:
                rec.update(
                    {k: v for k, v in ana.items() if not k.startswith("_")}
                )
        prev = getattr(_tls, "current", None)
        _tls.current = (entry, sig)
        try:
            exe = self._jit.lower(*args, **kwargs).compile()
        finally:
            _tls.current = prev
        self._aot[sig] = exe
        return True

    def _dispatch(self, sig: Any, args, kwargs):
        """Run one call: the primed AOT executable when this signature has
        one, the jit otherwise. An executable that rejects the concrete
        args (sharding/layout drift) degrades to the jit — never fails a
        request the lazy path would have served — but COUNTS as a miss
        (the dispatch was not AOT-served; a clean ledger must not read
        "fully warm" while every request quietly takes the lazy path)
        and logs once per wrapper."""
        exe = self._aot.get(sig)
        if exe is None:
            if self._aot:
                self.aot_misses += 1
            return self._jit(*args, **kwargs)
        try:
            out = exe(*args, **kwargs)
        except Exception as e:
            self.aot_misses += 1
            if not getattr(self, "_aot_fallback_logged", False):
                self._aot_fallback_logged = True
                from spark_rapids_ml_tpu.utils.logging import get_logger

                get_logger("xprof").warning(
                    "AOT executable for %r rejected its arguments "
                    "(%s); degrading to the lazy jit — subsequent "
                    "rejections count as AOT misses silently", self.name, e,
                )
            return self._jit(*args, **kwargs)
        self.aot_hits += 1
        return out

    def _analyze(self, sig: Any, args, kwargs, timed: bool) -> Dict[str, Any]:
        """Once per signature (cached on the entry across resets):
        lowering-level cost analysis (cheap — trace + StableHLO, no
        backend compile), plus, only in the timing mode, an AOT compile
        for ``memory_analysis``. That compile is not a throwaway: the jit
        and a later ``lower().compile()`` of the same signature reuse its
        executable and fire no compile event of their own, so it is THE
        compile of this signature and is booked to this entry — never to
        whatever entry/annotation encloses the call. ``_timed`` records
        which mode produced the cache so a later timing-mode call can
        upgrade it."""
        out: Dict[str, Any] = {"_timed": timed}
        _ensure_listener()
        prev = getattr(_tls, "current", None)
        _tls.current = (self._entry, sig)
        try:
            lowered = self._jit.lower(*args, **kwargs)
            self._harvest_cost(out, lowered.cost_analysis())
            if timed:
                compiled = lowered.compile()
                ma = compiled.memory_analysis()
                if ma is not None:
                    out["peak_bytes"] = int(ma.temp_size_in_bytes)
                    out["argument_bytes"] = int(ma.argument_size_in_bytes)
                    out["output_bytes"] = int(ma.output_size_in_bytes)
                # Post-optimization cost analysis outranks the
                # lowering-level estimate where the backend provides it.
                self._harvest_cost(out, compiled.cost_analysis())
        finally:
            _tls.current = prev
        return out

    @staticmethod
    def _harvest_cost(out: Dict[str, Any], ca: Optional[Dict[str, Any]]) -> None:
        """flops / bytes from a cost-analysis dict (None where the
        backend reports nothing for this program)."""
        if not ca:
            return
        if "flops" in ca:
            out["flops"] = float(ca["flops"])
        if "bytes accessed" in ca:
            out["bytes_accessed"] = float(ca["bytes accessed"])

    def __call__(self, *args: Any, **kwargs: Any):
        import jax

        # Inside another trace (a ledgered jit calling a ledgered jit —
        # every pallas.* kernel under a streaming update), this call is
        # INLINED into the outer program: it runs once at trace time and
        # never again, while the outer entry's cost analysis already
        # includes this kernel's flops. Recording here would book a
        # phantom call (and phantom flops) per compile, so the ledger
        # counts device dispatches from Python only — direct calls. (An
        # AOT executable is likewise uncallable under a trace.)
        if not jax.core.trace_ctx.is_top_level():
            return self._jit(*args, **kwargs)

        if not _enabled():
            if self._aot:
                return self._dispatch(self._sig(args, kwargs), args, kwargs)
            return self._jit(*args, **kwargs)

        entry = self._entry
        sig = self._sig(args, kwargs)
        timing = _device_timing()
        rec, new = entry.record(sig)
        _ensure_listener()
        # Taken BEFORE the analysis: in the timing mode its compile is
        # this signature's compile (see _analyze), and the call that paid
        # it is the compile-bearing one.
        compiles_before = rec["compiles"]
        t0 = time.perf_counter()
        if new:
            _M_CACHE_MISSES.inc(fn=entry.name)
            # Analyze BEFORE executing, while donated buffers are still
            # alive. Cached on the entry: a post-reset re-record reuses it
            # instead of paying the retrace inside the window reset()
            # opened.
            with entry.lock:
                ana = entry.analysis.get(sig)
            if ana is None or (timing and not ana.get("_timed")):
                ana = self._analyze(sig, args, kwargs, timing)
                with entry.lock:
                    entry.analysis[sig] = ana
            with entry.lock:
                rec.update(
                    {k: v for k, v in ana.items() if not k.startswith("_")}
                )
        prev = getattr(_tls, "current", None)
        _tls.current = (entry, sig)
        try:
            out = self._dispatch(sig, args, kwargs)
            if timing:
                out = jax.block_until_ready(out)
        finally:
            _tls.current = prev
        dt = time.perf_counter() - t0
        compiled_now = rec["compiles"] > compiles_before
        with entry.lock:
            rec["calls"] += 1
            if compiled_now and rec["first_call_s"] is None:
                rec["first_call_s"] = dt
            if timing and not compiled_now:
                # A compile-bearing call's clock is compile, not
                # execution — keep the execution series clean.
                rec["execute_calls"] += 1
                rec["execute_s"] += dt
        _M_CALLS.inc(fn=entry.name)
        # In the timing mode dt holds the blocked execution too.
        _M_DISPATCH_SECONDS.inc(dt, fn=entry.name)
        _M_DISPATCH_DURATION.observe(dt, fn=entry.name)
        if timing and not compiled_now:
            _M_EXEC_SECONDS.observe(dt, fn=entry.name)
        if self.on_dispatch is not None:
            self.on_dispatch(*args, **kwargs)
        return out


def ledgered_jit(name: str, fun: Optional[Callable] = None, **jit_kwargs: Any):
    """``jax.jit`` registered with the jit ledger under ``name``.

    The ONLY sanctioned way to jit in ops/ and models/ (lint-enforced,
    tests/test_lint.py — the mirror of the "every hot path spanned"
    gate): an unledgered entry point is invisible to the device-cost
    attribution every perf PR is judged with. Usable three ways::

        fitted = ledgered_jit("pca.fit", fit)                 # wrap
        @ledgered_jit("kmeans.predict")                       # decorate
        @functools.partial(ledgered_jit, "pallas.gram",
                           static_argnames=("block_n",))      # with opts
    """
    if fun is None:
        return lambda f: LedgeredJit(name, f, jit_kwargs)
    return LedgeredJit(name, fun, jit_kwargs)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Attribute compiles fired inside the block to ledger entry
    ``name`` — for dispatch sites that reach jitted code indirectly
    (the serve scheduler's bucket dispatch calls model methods whose
    inner jits are ledgered; anything NOT individually ledgered lands
    here instead of nowhere)."""
    if not _enabled():
        yield
        return
    entry = LEDGER.entry(name)
    sig = ("ambient",)
    rec, _ = entry.record(sig)
    _ensure_listener()
    prev = getattr(_tls, "current", None)
    _tls.current = (entry, sig)
    try:
        yield
    finally:
        _tls.current = prev
        with entry.lock:
            rec["calls"] += 1
        _M_CALLS.inc(fn=entry.name)


def snapshot() -> Dict[str, Any]:
    return LEDGER.snapshot()


def reset() -> None:
    LEDGER.reset()


def format_table(
    snap: Optional[Dict[str, Any]] = None,
    peak_flops_per_s: Optional[float] = None,
    peak_bytes_per_s: Optional[float] = None,
) -> str:
    """Achieved-vs-bound text table (the roofline framing of 2112.09017).

    One row per fn: calls, compiles, compile seconds, execute seconds,
    achieved GFLOP/s and GB/s — plus utilization columns when the
    hardware bounds are supplied (e.g. v5e: 197e12 bf16 flops/s,
    819e9 HBM bytes/s). Rates need SRML_DEVICE_TIMING runs; without
    them the rate columns read ``-`` (that absence IS the finding:
    nothing measured device time yet)."""
    snap = LEDGER.snapshot() if snap is None else snap
    cols = ["fn", "calls", "compiles", "compile_s", "execute_s",
            "GFLOP/s", "GB/s"]
    if peak_flops_per_s:
        cols.append("flops%")
    if peak_bytes_per_s:
        cols.append("hbm%")
    rows = [cols]
    for name in sorted(snap):
        a = snap[name]
        row = [
            name,
            str(a["calls"]),
            str(a["compiles"]),
            f"{a['compile_s']:.3f}",
            f"{a['execute_s']:.3f}" if a["execute_calls"] else "-",
            f"{a['flops_per_s'] / 1e9:.1f}" if a["flops_per_s"] else "-",
            f"{a['bytes_per_s'] / 1e9:.1f}" if a["bytes_per_s"] else "-",
        ]
        if peak_flops_per_s:
            row.append(
                f"{100 * a['flops_per_s'] / peak_flops_per_s:.1f}"
                if a["flops_per_s"] else "-"
            )
        if peak_bytes_per_s:
            row.append(
                f"{100 * a['bytes_per_s'] / peak_bytes_per_s:.1f}"
                if a["bytes_per_s"] else "-"
            )
        rows.append(row)
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    return "\n".join(
        "  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
        for r in rows
    )
