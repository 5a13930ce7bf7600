"""Device-cost attribution: the jit ledger, trace stitching, perfcheck.

The jit ledger (utils/xprof.py) is the instrument every subsequent perf
PR is judged with, so these tests pin its accounting exactly: calls and
shape signatures are counted, compiles are attributed to the entry that
fired them (not guessed from wall clock), cost analysis lands once per
signature, the SRML_DEVICE_TIMING mode records blocked execution time,
and with metrics off the wrapper is a passthrough that records nothing.

tools/trace.py and tools/perfcheck.py are tested on synthetic journals
and records (the multi-daemon END-TO-END stitch lives in
test_trace_distributed.py, next to the protocol tests it extends).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.utils import journal, metrics, xprof
from spark_rapids_ml_tpu.tools import perfcheck, trace


@pytest.fixture(autouse=True)
def _fresh_ledger():
    xprof.reset()
    yield
    xprof.reset()


def _entry(snap, name):
    assert name in snap, f"{name} not in ledger snapshot: {sorted(snap)}"
    return snap[name]


# ---------------------------------------------------------------------------
# jit ledger accounting
# ---------------------------------------------------------------------------


def test_ledger_counts_calls_and_signatures():
    f = xprof.ledgered_jit("test.add_one", lambda x: x + 1)
    a = jnp.ones((4, 3), jnp.float32)
    b = jnp.ones((8, 3), jnp.float32)
    f(a)
    f(a)
    f(b)  # new shape -> new signature
    agg = _entry(xprof.snapshot(), "test.add_one")
    assert agg["calls"] == 3
    assert agg["cache_misses"] == 2
    sigs = {s["sig"]: s for s in agg["signatures"]}
    assert "(float32[4,3])" in sigs and "(float32[8,3])" in sigs
    assert sigs["(float32[4,3])"]["calls"] == 2
    assert sigs["(float32[8,3])"]["calls"] == 1


def test_ledger_attributes_compiles_to_the_entry():
    """Compile events fire inside the wrapped call; the ledger must book
    them to THIS entry, with nonzero compile seconds, and never again on
    the warm path."""
    f = xprof.ledgered_jit("test.compiled", lambda x: (x * 2).sum())
    x = jnp.ones((16,), jnp.float32)
    f(x)
    agg = _entry(xprof.snapshot(), "test.compiled")
    assert agg["compiles"] >= 1
    assert agg["compile_s"] > 0
    before = agg["compiles"]
    f(x)  # warm: no new compile
    assert _entry(xprof.snapshot(), "test.compiled")["compiles"] == before


def test_ledger_cost_analysis_populates_flops_and_bytes():
    f = xprof.ledgered_jit(
        "test.matmul", lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ()))
        )
    )
    a = jnp.ones((32, 16), jnp.float32)
    f(a, a.T)
    (sig,) = _entry(xprof.snapshot(), "test.matmul")["signatures"]
    # CPU XLA reports flops for a GEMM; bytes may be backend-dependent,
    # flops must not be (2·32·32·16 model flops).
    assert sig["flops"] is not None and sig["flops"] > 0


def test_ledger_passthrough_when_metrics_off():
    f = xprof.ledgered_jit("test.off", lambda x: x - 1)
    with config.option("metrics", False):
        out = f(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out), [-1.0, 0.0, 1.0, 2.0])
    assert "test.off" not in xprof.snapshot()


def _xla_counter(name, fn):
    return metrics.REGISTRY.counter(name).value(fn=fn)


def test_dispatch_seconds_are_kept_for_every_top_level_call():
    """The host's cost of a dispatch: Δseconds ÷ Δcalls, by fn — the `dt`
    the wrapper always computed and used to throw away."""
    f = xprof.ledgered_jit("test.dispatch", lambda x: x * 2 + 1)
    x = jnp.ones((8,), jnp.float32)
    f(x)  # the compile-bearing call counts too (a measured window has none)
    first = _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch")
    calls = _xla_counter("srml_xla_calls_total", "test.dispatch")
    assert first > 0
    n = 5
    for _ in range(n):
        f(x)
    warm = _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch") - first
    assert _xla_counter("srml_xla_calls_total", "test.dispatch") - calls == n
    assert warm > 0


def test_dispatch_seconds_do_not_move_with_metrics_off():
    f = xprof.ledgered_jit("test.dispatch_off", lambda x: x + 2)
    with config.option("metrics", False):
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))
    assert _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch_off") == 0.0
    assert _xla_counter("srml_xla_calls_total", "test.dispatch_off") == 0.0


def test_a_call_inlined_into_another_trace_books_no_dispatch():
    inner = xprof.ledgered_jit("test.dispatch_inner", lambda x: x * 3)
    outer = xprof.ledgered_jit("test.dispatch_outer", lambda x: inner(x) + 1)
    outer(jnp.ones((4,)))
    assert _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch_outer") > 0
    assert _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch_inner") == 0.0


def _dispatch_hist(fn):
    """(bucket counts, sum, count) of the dispatch histogram's `fn` series,
    zeros while it has none."""
    for s in metrics.snapshot().get(
            "srml_xla_dispatch_duration_seconds", {}).get("samples", []):
        if s["labels"] == {"fn": fn}:
            return s["buckets"], s["sum"], s["count"]
    return {}, 0.0, 0


def test_every_top_level_call_is_one_observation_of_the_dispatch_histogram():
    """`srml_xla_dispatch_duration_seconds{fn}` is the counter pair as a
    distribution: the same `dt` of the same calls, so its sum and count ARE
    `srml_xla_dispatch_seconds_total` and `srml_xla_calls_total`."""
    f = xprof.ledgered_jit("test.dispatch_hist", lambda x: x * 2 + 1)
    x = jnp.ones((8,), jnp.float32)
    f(x)
    _, sum0, count0 = _dispatch_hist("test.dispatch_hist")
    seconds0 = _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch_hist")
    assert count0 == 1 and sum0 == pytest.approx(seconds0, rel=1e-12)
    for _ in range(5):
        f(x)
    buckets, total, count = _dispatch_hist("test.dispatch_hist")
    assert count - count0 == 5 == (
        _xla_counter("srml_xla_calls_total", "test.dispatch_hist") - 1)
    assert total == pytest.approx(
        _xla_counter("srml_xla_dispatch_seconds_total", "test.dispatch_hist"),
        rel=1e-12)
    # the registry's default buckets: 0.5 ms … 60 s, cumulative
    assert list(buckets)[0] == "0.0005" and list(buckets)[-2:] == ["60", "+Inf"]
    assert "0.05" in buckets and buckets["+Inf"] == count


@pytest.mark.parametrize("how", ["inlined", "ledger_off"])
def test_the_dispatch_histogram_takes_nothing_from_a_call_the_ledger_leaves_out(how):
    inner = xprof.ledgered_jit(f"test.dispatch_hist_{how}", lambda x: x * 3)
    if how == "inlined":
        outer = xprof.ledgered_jit("test.dispatch_hist_outer", lambda x: inner(x) + 1)
        outer(jnp.ones((4,)))
        assert _dispatch_hist("test.dispatch_hist_outer")[2] == 1
    else:
        with config.option("metrics", False):
            inner(jnp.ones((4,)))
            inner(jnp.ones((4,)))
    assert _dispatch_hist(f"test.dispatch_hist_{how}") == ({}, 0.0, 0)


def test_the_registry_holds_no_cost_analysis_counters():
    """`srml_xla_executed_{flops,bytes}_total` (cost-analysis × calls, blind
    inside a custom call, read by nothing) left the fold's hot path."""
    f = xprof.ledgered_jit("test.no_executed", lambda a: a @ a.T)
    f(jnp.ones((8, 4), jnp.float32))
    assert not [name for name in metrics.snapshot() if "executed" in name]
    assert "srml_xla_executed" not in metrics.render_prometheus()


def test_device_timing_mode_records_execution_seconds():
    f = xprof.ledgered_jit("test.timed", lambda x: jnp.sin(x).sum())
    x = jnp.ones((64,), jnp.float32)
    with config.option("device_timing", True):
        f(x)  # compile call: clock is compile, excluded from execute_s
        f(x)
        f(x)
    agg = _entry(xprof.snapshot(), "test.timed")
    assert agg["execute_calls"] == 2
    assert agg["execute_s"] > 0
    assert agg["flops_per_s"] is None or agg["flops_per_s"] > 0


def test_device_timing_off_keeps_execution_series_empty():
    f = xprof.ledgered_jit("test.untimed", lambda x: x * 3)
    x = jnp.ones((8,), jnp.float32)
    f(x)
    f(x)
    agg = _entry(xprof.snapshot(), "test.untimed")
    assert agg["execute_calls"] == 0 and agg["execute_s"] == 0.0
    assert agg["flops_per_s"] is None


def test_ledgered_jit_supports_static_and_donated_args():
    """The two decorator forms the package hot paths actually use:
    functools.partial with static_argnames, and donate_argnums."""
    import functools

    @functools.partial(xprof.ledgered_jit, "test.static",
                       static_argnames=("n",))
    def tile(x, n):
        return jnp.tile(x, n)

    assert tile(jnp.ones((2,)), n=3).shape == (6,)

    @functools.partial(xprof.ledgered_jit, "test.donated",
                       donate_argnums=(0,))
    def bump(state, x):
        return state + x

    s = jnp.zeros((4,))
    s = bump(s, jnp.ones((4,)))
    s = bump(s, jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(s), 2.0)
    assert _entry(xprof.snapshot(), "test.donated")["calls"] == 2
    assert _entry(xprof.snapshot(), "test.donated")["cache_misses"] == 1


def test_annotate_attributes_ambient_compiles():
    """Dispatch sites that reach jits indirectly (serve scheduler) book
    their compiles under the annotation's name."""
    def fresh(x):
        return x @ x.T

    jitted = jax.jit(fresh)  # NOT ledgered on purpose
    with xprof.annotate("test.ambient"):
        jitted(jnp.ones((5, 4), jnp.float32))
    agg = _entry(xprof.snapshot(), "test.ambient")
    assert agg["calls"] == 1
    assert agg["compiles"] >= 1


def test_reset_clears_records_but_entries_survive():
    f = xprof.ledgered_jit("test.resettable", lambda x: x)
    f(jnp.ones((3,)))
    assert "test.resettable" in xprof.snapshot()
    xprof.reset()
    assert "test.resettable" not in xprof.snapshot()
    f(jnp.ones((3,)))  # wrapper still ledgered after reset
    assert _entry(xprof.snapshot(), "test.resettable")["calls"] == 1


def test_format_table_renders_rates_and_bounds():
    f = xprof.ledgered_jit("test.table", lambda a: a @ a)
    with config.option("device_timing", True):
        a = jnp.ones((64, 64), jnp.float32)
        f(a)
        f(a)
    text = xprof.format_table(
        peak_flops_per_s=197e12, peak_bytes_per_s=819e9
    )
    assert "test.table" in text
    assert "flops%" in text and "hbm%" in text
    # Two header-plus-rows lines minimum, aligned columns.
    assert len(text.splitlines()) >= 2


def test_ledger_result_is_bitwise_identical_to_bare_jit():
    def body(x):
        return jnp.cumsum(x * 1.7) / 3.0

    ledgered = xprof.ledgered_jit("test.parity", body)
    bare = jax.jit(body)
    x = jnp.linspace(0.0, 5.0, 257)
    np.testing.assert_array_equal(
        np.asarray(ledgered(x)), np.asarray(bare(x))
    )


# ---------------------------------------------------------------------------
# tools/trace.py on synthetic journals
# ---------------------------------------------------------------------------


def _write_journal(path, body):
    with config.option("run_journal", str(path)):
        body()
    journal.close()


def test_trace_chrome_events_have_microsecond_spans(tmp_path):
    p = tmp_path / "j.jsonl"

    def body():
        with journal.run("fit"):
            with journal.span("phase_a"):
                pass
        journal.mark("note")

    _write_journal(p, body)
    obj = trace.chrome_trace(trace.load([str(p)]))
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert "X" in phs and "M" in phs and "i" in phs
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"fit", "phase_a"}
    for e in xs:
        assert e["ts"] > 1e15  # unix seconds in µs
        assert e["dur"] >= 0
        assert "span_id" in e["args"]


def test_trace_stitches_adopted_spans_across_files(tmp_path):
    """The distributed case in miniature: 'driver' journals to one file,
    the 'daemon' to ANOTHER file under an adopted trace_ctx; the merge
    parents the daemon span into the driver tree."""
    drv, dmn = tmp_path / "driver.jsonl", tmp_path / "daemon.jsonl"
    ctx = {}

    def driver():
        with journal.run("fit"):
            with journal.span("feed pass"):
                ctx.update(journal.trace_ctx())

    _write_journal(drv, driver)

    def daemon():
        with journal.adopt(ctx["run"], ctx["span"]):
            with journal.span("daemon.feed", job="j"):
                pass

    _write_journal(dmn, daemon)

    events = trace.load([str(drv), str(dmn)])
    (root,) = trace.tree(events)
    assert root.name == "fit"
    (feed,) = root.children
    assert feed.name == "feed pass"
    (dspan,) = feed.children
    assert dspan.name == "daemon.feed"
    assert dspan.event["run_id"] == root.event["run_id"]
    text = trace.flame(events)
    assert "daemon.feed" in text and "fit" in text


def test_trace_orphan_parent_degrades_to_root(tmp_path):
    p = tmp_path / "j.jsonl"

    def body():
        with journal.adopt("feedfeed", "cafecafe"):  # parent file not given
            with journal.span("daemon.step"):
                pass

    _write_journal(p, body)
    (root,) = trace.tree(trace.load([str(p)]))
    assert root.name == "daemon.step"


def test_trace_run_filter_and_listing(tmp_path):
    p = tmp_path / "j.jsonl"
    ids = {}

    def body():
        with journal.run("fit_a") as ra:
            ids["a"] = ra
        with journal.run("fit_b") as rb:
            ids["b"] = rb

    _write_journal(p, body)
    events = trace.load([str(p)])
    assert set(trace.runs(events)) == {ids["a"], ids["b"]}
    only_a = trace.chrome_trace(events, run_id=ids["a"])
    names = {e["name"] for e in only_a["traceEvents"] if e["ph"] == "X"}
    assert names == {"fit_a"}


def test_trace_cli_writes_chrome_json(tmp_path, capsys):
    p = tmp_path / "j.jsonl"

    def body():
        with journal.run("fit"):
            with journal.span("phase"):
                pass

    _write_journal(p, body)
    out = tmp_path / "trace.json"
    rc = trace.main([str(p), "--out", str(out), "--flame"])
    assert rc == 0
    obj = json.loads(out.read_text())
    assert any(e["ph"] == "X" for e in obj["traceEvents"])
    printed = capsys.readouterr().out
    assert "phase" in printed  # flame summary requested too


# ---------------------------------------------------------------------------
# tools/perfcheck.py
# ---------------------------------------------------------------------------

_METRIC = "pca_fit_streaming_rows_per_sec_per_chip_d2048_k32"


def _record(value, steady_compiles=0):
    return {
        "metric": _METRIC,
        "value": value,
        "unit": "rows/s/chip",
        "xla": {
            "warmup": {"gram.streaming_update": {
                "calls": 2, "compiles": 2, "compile_s": 1.2,
                "cache_misses": 1, "execute_s": 0.0,
                "flops": 1e9, "bytes": 1e8,
                "flops_per_s": None, "bytes_per_s": None,
            }},
            "steady": {"gram.streaming_update": {
                "calls": 384, "compiles": steady_compiles,
                "compile_s": 0.4 if steady_compiles else 0.0,
                "cache_misses": 1, "execute_s": 0.0,
                "flops": 1e12, "bytes": 1e11,
                "flops_per_s": None, "bytes_per_s": None,
            }},
            "device_timing": False,
        },
    }


_HISTORY = [{"metric": _METRIC, "value": v}
            for v in (21.5e6, 21.8e6, 22.0e6, 21.6e6, 21.9e6)]


def test_perfcheck_passes_at_parity():
    ok, lines = perfcheck.check(_record(21.7e6), _HISTORY)
    assert ok, lines
    assert any("[OK]" in l for l in lines)


def test_perfcheck_fails_on_throughput_regression():
    ok, lines = perfcheck.check(_record(0.8 * 21.8e6), _HISTORY)
    assert not ok
    assert any("REGRESSION" in l for l in lines)


def test_perfcheck_tolerates_small_dips():
    ok, _ = perfcheck.check(_record(0.9 * 21.8e6), _HISTORY)
    assert ok  # −10% is within the 15% gate


def test_perfcheck_fails_on_steady_state_compile_storm():
    ok, lines = perfcheck.check(
        _record(21.9e6, steady_compiles=7), _HISTORY
    )
    assert not ok
    assert any("compile storm [FAIL]" in l for l in lines)
    # The exemption hatch names the fn explicitly.
    ok, _ = perfcheck.check(
        _record(21.9e6, steady_compiles=7), _HISTORY,
        allow_compiles=("gram.streaming_update",),
    )
    assert ok


def test_perfcheck_skips_throughput_without_matching_history():
    smoke = _record(4e5)
    smoke["metric"] = "pca_fit_streaming_rows_per_sec_per_chip_d64_k8"
    ok, lines = perfcheck.check(smoke, _HISTORY)
    assert ok
    assert any("[SKIP]" in l for l in lines)


def _write_wrapper_history(directory):
    """Five driver-side wrapper records ({n, cmd, rc, tail, parsed}) of one
    flat metric, written under ``directory`` — the BENCH_r*.json file
    format, built here so the test owns its history."""
    for n, value in enumerate(
        (22.05e6, 21.49e6, 21.62e6, 21.81e6, 21.70e6), start=1
    ):
        parsed = {"metric": _METRIC, "value": value, "unit": "rows/s/chip"}
        (directory / f"BENCH_w{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed) + "\n", "parsed": parsed,
        }))
    return str(directory / "BENCH_w*.json")


def test_perfcheck_reads_a_wrapper_trajectory(tmp_path):
    """The driver-side BENCH_r*.json wrapper format parses: five flat
    rounds of one shared metric agree with each other within the gate,
    and a later round under its own metric (the repo's r06: sandbox
    shapes, first embedded-ledger round) parses alongside without
    perturbing that trajectory."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    history = perfcheck.load_history(
        [_write_wrapper_history(tmp_path), str(root / "BENCH_r06.json")]
    )
    assert len(history) == 6
    values = [h["value"] for h in history if h.get("metric") == _METRIC]
    assert len(values) == 5
    ok, lines = perfcheck.check(
        _record(min(values)), history
    )
    assert ok, lines


@pytest.mark.perf
def test_perfcheck_gates_a_real_smoke_bench(tmp_path):
    """End-to-end perfcheck smoke: run bench.py at toy shapes in-process
    conditions (subprocess, CPU), pipe its record through the gate. Toy
    shapes have no matching history, so this exercises record parsing +
    the compile-storm gate on a REAL ledger breakdown."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        SRML_BENCH_D="32", SRML_BENCH_K="4",
        SRML_BENCH_BATCH_ROWS="1024", SRML_BENCH_BATCHES="3",
    )
    out = subprocess.run(
        [sys.executable, str(root / "bench.py")],
        env=env, cwd=str(root), capture_output=True, text=True,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = perfcheck.parse_record(json.loads(out.stdout.strip().splitlines()[-1]))
    assert "steady" in rec["xla"]
    ok, lines = perfcheck.check(
        rec, perfcheck.load_history([_write_wrapper_history(tmp_path)])
    )
    assert ok, lines


def test_ledger_ignores_trace_time_inlining():
    """A ledgered jit called INSIDE another trace (every pallas kernel
    under a streaming update) is inlined into the outer program: it runs
    once at trace time and never again, while the outer entry's cost
    analysis already covers its flops. Booking that trace-time call
    would fabricate one phantom call per compile — the ledger must count
    only device dispatches from Python."""
    inner = xprof.ledgered_jit("test.inner", lambda x: x * 2)

    @xprof.ledgered_jit("test.outer")
    def outer(x):
        return inner(x) + 1

    x = jnp.ones((8,), jnp.float32)
    outer(x)
    outer(x)
    snap = xprof.snapshot()
    assert _entry(snap, "test.outer")["calls"] == 2
    assert "test.inner" not in snap  # inlined, never dispatched directly
    inner(x)  # a DIRECT call still ledgers
    assert _entry(xprof.snapshot(), "test.inner")["calls"] == 1


def test_reset_does_not_reanalyze_inside_the_next_window(monkeypatch):
    """reset() opens a measurement window (bench epoch boundary): the
    first post-reset call must reuse the cached per-signature analysis —
    a retrace+lowering (plus a throwaway compile in the timing mode)
    inside the timed window would charge the window warmup work and, in
    the timing mode, hide a multi-second compile from the steady-state
    storm gate."""
    f = xprof.ledgered_jit("test.reanalyze", lambda a: a @ a)
    calls = []
    real = type(f)._analyze
    monkeypatch.setattr(
        type(f), "_analyze",
        lambda self, *a, **k: calls.append(1) or real(self, *a, **k),
    )
    x = jnp.ones((16, 16), jnp.float32)
    f(x)
    assert calls == [1]
    flops_before = _entry(xprof.snapshot(), "test.reanalyze")["signatures"][0]["flops"]
    xprof.reset()
    f(x)
    assert calls == [1], "post-reset call re-ran the analysis"
    sig = _entry(xprof.snapshot(), "test.reanalyze")["signatures"][0]
    assert sig["flops"] == flops_before  # attribution survives the reset
    # A NEW signature still analyzes.
    f(jnp.ones((8, 8), jnp.float32))
    assert calls == [1, 1]


def test_perfcheck_empty_steady_is_a_skip_not_a_pass():
    """A metrics-off bench run produces an EMPTY xla.steady (the ledger
    wrapper was a passthrough): the storm gate must say it checked
    nothing, never print a clean '[OK] across 0 fns'."""
    rec = _record(21.7e6)
    rec["xla"]["steady"] = {}
    ok, lines = perfcheck.check(rec, _HISTORY)
    assert ok
    storm_lines = [l for l in lines if l.startswith("compile storm")]
    assert storm_lines and "[SKIP]" in storm_lines[0]
    assert not any("[OK]" in l for l in storm_lines)


def test_analyze_throwaway_compile_not_booked_to_enclosing_entry():
    """In the timing mode, _analyze's throwaway AOT compile fires the
    same monitoring event as a real compile — it must not be attributed
    to whatever entry/annotation encloses the call (the scheduler's
    annotate shell, or an outer ledgered fn)."""
    inner = xprof.ledgered_jit("test.throwaway_inner", lambda x: x + 2.0)
    # Built OUTSIDE the annotation: jnp.ones itself compiles a fill
    # program, and ambient compiles inside the block belong to the
    # annotation by contract.
    x = jnp.ones((4,), jnp.float32)
    with config.option("device_timing", True):
        with xprof.annotate("test.throwaway_outer"):
            inner(x)
    snap = xprof.snapshot()
    outer = _entry(snap, "test.throwaway_outer")
    assert outer["compiles"] == 0, (
        "the analysis compile leaked into the enclosing annotation"
    )
    assert _entry(snap, "test.throwaway_inner")["compiles"] >= 1


def test_traced_scalars_share_one_signature_static_values_do_not():
    """jit compiles ONE executable per traced-scalar type — the ledger
    must mirror that key (a fold that takes a Python n_valid per
    ragged batch; value-keying fabricated a cache miss and paid a full
    lower() per batch). Declared-static args keep
    value keys: each value genuinely is its own compiled program."""
    import functools

    traced = xprof.ledgered_jit("test.traced_scalar", lambda x, n: x * n)
    x = jnp.ones((8,), jnp.float32)
    for n in range(1, 31):
        traced(x, n)
    agg = _entry(xprof.snapshot(), "test.traced_scalar")
    assert agg["calls"] == 30
    assert agg["cache_misses"] == 1, [s["sig"] for s in agg["signatures"]]
    assert agg["compiles"] <= 2  # XLA's own weak-type key, not per value

    @functools.partial(xprof.ledgered_jit, "test.static_scalar",
                       static_argnames=("n",))
    def tile(x, n):
        return jnp.tile(x, n)

    tile(x, n=2)
    tile(x, n=3)
    agg = _entry(xprof.snapshot(), "test.static_scalar")
    assert agg["cache_misses"] == 2  # one per static value: two programs
