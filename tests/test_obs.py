"""repro.obs — metrics registry, phase tracing, the residual ledger, the
min-of-N timing helper, and every surface that consumes them: the
batcher's serve telemetry, ``autotune(feedback=)`` grid rescoring, the
harness's protocol stamping, and ``smoke_check``'s residual gates.

The two load-bearing guarantees locked down here:

* quantiles are EXACT order statistics while a histogram's count stays
  within its reservoir capacity (serve percentiles at real flush counts
  must not be estimates), checked against ``np.quantile``;
* the disabled path is free: with no registry installed, ``span()``
  returns a process-wide singleton and allocates nothing — asserted with
  ``tracemalloc`` — so the flush hot path can stay instrumented.
"""
import json
import math
import threading
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import (Histogram, MetricRegistry, ResidualLedger,
                       choice_labels, span, time_min_of_n)


@pytest.fixture(autouse=True)
def _no_registry():
    """Every test starts and ends with instrumentation disabled."""
    obs.uninstall()
    yield
    obs.uninstall()


# ---------------------------------------------------------------- metrics

def _hist(capacity=1024):
    return Histogram("t", (), capacity=capacity)


@pytest.mark.parametrize("values", [
    [1.0],                              # n=1: every quantile is the value
    [2.0, 1.0],                         # n=2: interpolation between both
    [3.0, 1.0, 2.0],                    # n=3
    [5.0] * 7,                          # constant stream
    list(range(100)),
    list(np.random.default_rng(0).standard_normal(257)),
])
def test_quantiles_exact_match_numpy(values):
    h = _hist()
    for v in values:
        h.observe(v)
    assert h.exact
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            float(np.quantile(np.asarray(values, float), q)), rel=1e-12)
    assert h.count == len(values)
    assert h.total == pytest.approx(sum(values))
    assert h.min == min(values) and h.max == max(values)
    assert h.mean == pytest.approx(sum(values) / len(values))


def test_empty_histogram_quantiles_are_none():
    h = _hist()
    assert h.quantile(0.5) is None
    assert h.mean is None
    assert h.percentiles() == {"p50": None, "p95": None, "p99": None}


def test_reservoir_bounds_memory_and_keeps_minmax_exact():
    h = _hist(capacity=64)
    rng = np.random.default_rng(1)
    values = rng.standard_normal(10_000)
    for v in values:
        h.observe(float(v))
    assert not h.exact
    assert len(h._reservoir) == 64          # bounded, past capacity
    assert h.count == 10_000
    # min/max/sum track the FULL stream even after downsampling
    assert h.min == float(values.min()) and h.max == float(values.max())
    assert h.total == pytest.approx(float(values.sum()))
    # the estimate stays an estimate of the right distribution
    assert abs(h.quantile(0.5) - float(np.quantile(values, 0.5))) < 0.5


def test_reservoir_is_deterministic_across_instances():
    def fill():
        h = _hist(capacity=16)
        for v in range(1000):
            h.observe(float(v))
        return list(h._reservoir)
    assert fill() == fill()                  # crc32-seeded, not hash()


def test_quantile_rejects_out_of_range():
    h = _hist()
    h.observe(1.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_registry_series_identity_and_labels():
    reg = MetricRegistry(backend="cpu")
    assert reg.counter("c") is reg.counter("c")
    assert reg.counter("c", {"k": 1}) is not reg.counter("c", {"k": 2})
    # label order must not mint a new series
    assert reg.histogram("h", {"a": 1, "b": 2}) is \
        reg.histogram("h", {"b": 2, "a": 1})


def test_registry_dump_schema(tmp_path):
    reg = MetricRegistry(backend="cpu", mesh="4x2")
    reg.counter("flushes").inc()
    reg.counter("flushes").inc(2)
    reg.gauge("pending").set(3)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("flush_s", {"k": 8}).observe(v)
    reg.ledger.record("serve/flush", 2e-3, 1e-3, schedule="merge")
    path = tmp_path / "m.json"
    doc = reg.dump(str(path))
    assert json.loads(path.read_text()) == doc
    assert doc["schema"] == MetricRegistry.SCHEMA == "repro.obs/v1"
    assert doc["labels"] == {"backend": "cpu", "mesh": "4x2"}
    (c,) = doc["counters"]
    assert c["value"] == 3.0 and c["labels"]["backend"] == "cpu"
    (h,) = doc["histograms"]
    assert h["count"] == 4 and h["exact"] is True
    assert h["labels"] == {"backend": "cpu", "mesh": "4x2", "k": "8"}
    assert h["p50"] == pytest.approx(2.5)
    assert h["min"] == 1.0 and h["max"] == 4.0
    (r,) = doc["residuals"]
    assert r["residual"] == pytest.approx(2.0)
    assert r["labels"] == {"schedule": "merge"}


def test_install_uninstall_toggle_enabled():
    assert not obs.enabled() and obs.current_registry() is None
    reg = obs.install(MetricRegistry())
    assert obs.enabled() and obs.current_registry() is reg
    obs.uninstall()
    assert not obs.enabled()


# ------------------------------------------------------------------ spans

def test_span_records_wall_time():
    reg = obs.install(MetricRegistry())
    with span("phase"):
        pass
    h = reg.histogram("phase")
    assert h.count == 1 and 0 <= h.min < 1.0


def test_span_nesting_builds_slash_paths():
    reg = obs.install(MetricRegistry())
    with span("flush"):
        with span("pad"):
            pass
        with span("multiply"):
            pass
    names = {h.name for h in reg.histograms() if h.count}
    assert names == {"flush", "flush/pad", "flush/multiply"}


def test_absolute_span_names_ignore_the_stack():
    """Library instrumentation (spmm/kernel) keeps a stable series name no
    matter which caller spans are open — and does not extend the stack."""
    reg = obs.install(MetricRegistry())
    with span("flush"):
        with span("spmm/kernel"):
            with span("inner"):
                pass
    names = {h.name for h in reg.histograms() if h.count}
    assert "spmm/kernel" in names
    assert "flush/inner" in names           # kernel never joined the stack


def test_span_reentrancy_same_name():
    reg = obs.install(MetricRegistry())
    with span("a"):
        with span("a"):
            pass
    assert reg.histogram("a").count == 1
    assert reg.histogram("a/a").count == 1


def test_span_records_and_unwinds_on_exception():
    reg = obs.install(MetricRegistry())
    with pytest.raises(RuntimeError):
        with span("outer"):
            with span("dies"):
                raise RuntimeError("boom")
    assert reg.histogram("outer/dies").count == 1
    assert reg.histogram("outer").count == 1
    with span("outer"):                     # the stack fully unwound
        with span("next"):
            pass
    assert reg.histogram("outer/next").count == 1


def test_span_stack_is_per_thread():
    reg = obs.install(MetricRegistry())
    seen = []

    def worker():
        with span("w"):
            seen.append(True)

    with span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen and reg.histogram("w").count == 1   # not "main/w"


def test_disabled_span_is_singleton_and_allocation_free():
    """The zero-overhead guarantee: with no registry installed, span()
    returns one shared object and the enter/exit cycle allocates zero
    bytes — the batcher can keep its instrumentation on the flush hot
    path unconditionally."""
    assert span("x") is span("y")           # shared null singleton

    def hot_loop(n):
        for _ in range(n):
            with span("hot"):
                pass

    hot_loop(10)                            # warm up lazy interning
    tracemalloc.start()
    hot_loop(1000)
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    # a per-call allocation would show up ~1000 times; one-shot constants
    # (the range object, the snapshot machinery itself) are fine
    per_iter = [s for s in snap.statistics("lineno")
                if s.traceback[0].filename == __file__ and s.count > 2]
    assert not per_iter, f"disabled span allocates per call: {per_iter}"


def test_maybe_block_passthrough_when_disabled():
    x = object()
    assert obs.maybe_block(x) is x


class _FailedExecution:
    """A device result whose execution failed: blocking on it raises, as
    ``block_until_ready`` does for a real failed device computation."""

    def block_until_ready(self):
        raise RuntimeError("device execution failed")


def test_maybe_block_propagates_device_errors():
    """Regression: maybe_block used to swallow every exception from
    block_until_ready, so a failed device execution still closed its span
    as if it had run."""
    obs.install(MetricRegistry())
    try:
        with pytest.raises(RuntimeError, match="device execution failed"):
            obs.maybe_block(_FailedExecution())
    finally:
        obs.uninstall()


# ---------------------------------------------------------------- ledger

def test_ledger_residual_invariant():
    led = ResidualLedger()
    rec = led.record("r", 3e-3, 1.5e-3, schedule="row")
    assert rec.residual == pytest.approx(rec.observed_s / rec.modeled_s)
    assert rec.residual == pytest.approx(2.0)
    for r in led.records():
        assert math.isfinite(r.residual) and r.residual > 0


@pytest.mark.parametrize("obs_s,mod_s", [
    (0.0, 1.0), (-1.0, 1.0), (float("nan"), 1.0), (float("inf"), 1.0),
    (1.0, 0.0), (1.0, -2.0), (1.0, float("nan")),
])
def test_ledger_rejects_degenerate_pairs(obs_s, mod_s):
    with pytest.raises(ValueError):
        ResidualLedger().record("r", obs_s, mod_s)


def test_ledger_correction_geomean_and_default():
    led = ResidualLedger()
    assert led.correction(schedule="row") == 1.0        # no evidence
    assert led.correction(default=7.0, schedule="row") == 7.0
    led.record("a", 2.0, 1.0, schedule="row")           # residual 2
    led.record("b", 1.0, 2.0, schedule="row")           # residual 0.5
    assert led.correction(schedule="row") == pytest.approx(1.0)
    led.record("c", 8.0, 1.0, schedule="merge")
    assert led.correction(schedule="merge") == pytest.approx(8.0)


def test_ledger_absent_record_keys_are_wildcards():
    """A coarse record (schedule only) corrects every query that agrees on
    schedule, whatever its finer labels; a fully-labelled record only
    matches queries that agree on every label it carries."""
    led = ResidualLedger()
    led.record("coarse", 4.0, 1.0, schedule="merge")
    q = choice_labels(schedule="merge", num_chunks=4, mesh_shape=(4, 2),
                      compact_x=True)
    assert led.correction(**q) == pytest.approx(4.0)
    led2 = ResidualLedger()
    led2.record("fine", 4.0, 1.0, **q)
    assert led2.correction(**q) == pytest.approx(4.0)
    q_other = dict(q, num_chunks="8")
    assert led2.correction(**q_other) == 1.0            # label disagrees


def test_choice_labels_canonical_forms():
    lab = choice_labels(schedule="merge", num_chunks=4, mesh_shape=(4, 2),
                        compact_x=True, k=64)
    assert lab == {"schedule": "merge", "num_chunks": "4", "mesh": "4x2",
                   "compact_x": "on", "k": "64"}
    assert choice_labels(compact_x=False)["compact_x"] == "off"
    assert choice_labels() == {}


# ---------------------------------------------------------------- timing

def test_time_min_of_n_protocol_and_result():
    calls = []
    r = time_min_of_n(lambda: calls.append(1) or len(calls),
                      reps=4, warmup=2, block=False)
    assert len(calls) == 6                  # warmup + reps, all executed
    assert r.reps == 4 and r.warmup == 2
    assert r.best_s >= 0 and r.last_result == 6


def test_time_min_of_n_propagates_device_errors():
    """Regression: the timer's block swallowed device errors and returned
    a time for work that never ran."""
    with pytest.raises(RuntimeError, match="device execution failed"):
        time_min_of_n(_FailedExecution, reps=1, warmup=0)


def test_time_min_of_n_rejects_bad_protocol():
    with pytest.raises(ValueError):
        time_min_of_n(lambda: None, reps=0)
    with pytest.raises(ValueError):
        time_min_of_n(lambda: None, warmup=-1)


# ------------------------------------------------- batcher serve metrics

def _tiny_coo():
    from repro.core.formats import COO
    rng = np.random.default_rng(0)
    m = n = 64
    nnz = 300
    return COO(rng.integers(0, m, nnz).astype(np.int32),
               rng.integers(0, n, nnz).astype(np.int32),
               rng.standard_normal(nnz).astype(np.float32), (m, n))


def test_batcher_records_serve_metrics():
    from repro.core import convert
    from repro.spmm import RequestBatcher
    import jax.numpy as jnp
    mat = convert(_tiny_coo(), "sellcs")
    reg = obs.install(MetricRegistry())
    b = RequestBatcher(mat, max_batch=4, impl="ref")
    rng = np.random.default_rng(2)
    for _ in range(6):
        b.submit(jnp.asarray(rng.standard_normal(64).astype(np.float32)))
    assert reg.gauge("batcher/pending").value == 6
    out = b.drain()
    assert len(out) == 6
    assert reg.counter("batcher/submitted").value == 6
    assert reg.counter("batcher/served").value == 6
    assert reg.counter("batcher/flushes").value == 2
    assert reg.gauge("batcher/pending").value == 0
    assert reg.histogram("batcher/queue_wait_s").count == 6
    assert reg.histogram("batcher/flush").count == 2
    for phase in ("batcher/pad", "batcher/multiply", "batcher/scatter"):
        assert reg.histogram(phase).count == 2, phase
    assert not b._submit_t                  # timestamps fully consumed


def test_batcher_uninstrumented_results_identical():
    """Metrics must observe, never perturb: the served vectors are
    bitwise the same with and without a registry installed."""
    from repro.core import convert
    from repro.spmm import RequestBatcher
    import jax.numpy as jnp
    mat = convert(_tiny_coo(), "sellcs")
    xs = [np.random.default_rng(i).standard_normal(64).astype(np.float32)
          for i in range(5)]

    def serve():
        b = RequestBatcher(mat, max_batch=4, impl="ref")
        rids = [b.submit(jnp.asarray(x)) for x in xs]
        out = b.drain()
        return [np.asarray(out[r]) for r in rids]

    plain = serve()
    obs.install(MetricRegistry())
    instrumented = serve()
    obs.uninstall()
    for a, b_ in zip(plain, instrumented):
        np.testing.assert_array_equal(a, b_)


# ------------------------------------------------- autotune feedback loop

def test_autotune_feedback_reorders_rigged_grid():
    """A ledger claiming the model flatters the winner by 100x must flip
    the distributed grid to another candidate, and the correction the
    winner's score actually absorbed lands in TuneResult.residual."""
    from repro.core import autotune
    led_best, _ = autotune(_tiny_coo(), num_spmvs=10,
                           algorithms=("sellcs",), reps=1, k=8,
                           num_devices=8)
    assert led_best.residual is None        # no feedback, no correction
    led = ResidualLedger()
    led.record("rig", 100.0, 1.0, schedule=led_best.schedule)
    fb_best, _ = autotune(_tiny_coo(), num_spmvs=10,
                          algorithms=("sellcs",), reps=1, k=8,
                          num_devices=8, feedback=led)
    assert fb_best.schedule != led_best.schedule
    # the un-penalized winner carried no matching record -> no correction
    assert fb_best.residual is None
    # now penalize EVERY schedule; whoever wins absorbed its correction
    led.record("rig2", 100.0, 1.0, schedule=fb_best.schedule)
    all_best, results = autotune(_tiny_coo(), num_spmvs=10,
                                 algorithms=("sellcs",), reps=1, k=8,
                                 num_devices=8, feedback=led)
    assert all_best.residual == pytest.approx(100.0)
    assert all(r.residual == pytest.approx(100.0) for r in results)


# ------------------------------------------------- harness metadata stamp

def test_harness_stamps_backend_and_protocol(capsys):
    import jax
    from benchmarks import harness
    harness.reset_records()
    csv = harness.Csv("t")
    sec = harness.time_fn(lambda: 1, reps=2, warmup=1)
    csv.row("timed", sec, "gflops=1")
    csv.row("break_even.analytic", 0.0, "spmvs_to_amortize=inf")
    capsys.readouterr()
    timed, analytic = harness.records()
    assert timed["backend"] == jax.default_backend()
    assert timed["reps"] == 2 and timed["warmup"] == 1
    assert analytic["backend"] == jax.default_backend()
    assert "reps" not in analytic           # nothing timed the row
    harness.reset_records()


# -------------------------------------------------- smoke_check residuals

def test_smoke_check_residual_derived_field():
    import benchmarks.smoke_check as sk

    def row(residual, backend):
        return {"section": "s", "name": "m/sellcs+row@4dev/k=8",
                "us_per_call": 10.0,
                "derived": f"gflops=1;residual={residual};"
                           f"backend={backend}"}
    # finite-and-positive everywhere
    assert sk.check_residuals([row(2.5, "cpu")], "f") == []
    assert any("finite" in p
               for p in sk.check_residuals([row("nan", "cpu")], "f"))
    assert any("finite" in p
               for p in sk.check_residuals([row(0.0, "tpu")], "f"))
    # the 10x model-off flag arms off-cpu only
    assert sk.check_residuals([row(500.0, "cpu")], "f") == []
    bad = sk.check_residuals([row(500.0, "tpu")], "f")
    assert len(bad) == 1 and "more than 10x" in bad[0]
    assert sk.check_residuals([row(0.005, "tpu")], "f") != []
    assert sk.check_residuals([row(9.9, "tpu")], "f") == []


def test_smoke_check_obs_document(tmp_path):
    import benchmarks.smoke_check as sk
    reg = MetricRegistry(backend="cpu", mode="spmv")
    for v in (1e-3, 2e-3, 3e-3):
        reg.histogram("serve/flush_s").observe(v)
    reg.counter("batcher/flushes").inc(3)
    reg.ledger.record("serve/flush", 1.0, 1e-5, backend="cpu")
    assert sk.check_obs_document(reg.as_dict(), "m.json") == []
    # same huge residual on a tpu-labelled record -> flagged
    reg2 = MetricRegistry(backend="tpu")
    reg2.ledger.record("serve/flush", 1.0, 1e-5, backend="tpu")
    bad = sk.check_obs_document(reg2.as_dict(), "m.json")
    assert len(bad) == 1 and "more than 10x" in bad[0]
    # and main() dispatches a dumped document by its schema key
    path = tmp_path / "BENCH_serve_metrics.json"
    reg.dump(str(path))
    assert sk.main([str(path)]) == 0


def test_smoke_check_obs_document_structural():
    import benchmarks.smoke_check as sk
    doc = {"schema": "repro.obs/v1", "labels": {},
           "counters": [{"name": "c", "labels": {}, "value": -1.0}],
           "gauges": [],
           "histograms": [{"name": "h", "labels": {}, "count": 2,
                           "sum": 3.0, "min": 1.0, "max": 2.0,
                           "mean": 1.5, "exact": True,
                           "p50": 2.0, "p95": 1.5, "p99": 2.0}],
           "residuals": []}
    problems = sk.check_obs_document(doc, "m.json")
    assert any("counter/c" in p for p in problems)
    assert any("quantiles out of order" in p for p in problems)


# ----------------------------------------------------- serve e2e (1 dev)

def test_serve_spmv_metrics_end_to_end(tmp_path):
    """serve --mode spmv --metrics on one device: the dump is a valid
    repro.obs/v1 document with flush percentiles, batcher phase spans,
    and one residual record per flush."""
    import benchmarks.smoke_check as sk
    from repro.launch import serve
    path = tmp_path / "serve_metrics.json"
    serve.main(["--mode", "spmv", "--matrix", "mawi_like",
                "--requests", "8", "--max-batch", "4", "--impl", "ref",
                "--reps", "1", "--metrics", str(path)])
    doc = json.loads(path.read_text())
    assert doc["schema"] == "repro.obs/v1"
    assert doc["labels"]["mode"] == "spmv"
    hists = {h["name"]: h for h in doc["histograms"]}
    assert hists["serve/flush_s"]["count"] == 2        # 8 reqs / batch 4
    assert hists["serve/flush_s"]["exact"] is True
    assert hists["serve/flush_s"]["p50"] > 0
    assert hists["batcher/multiply"]["count"] >= 2
    assert len(doc["residuals"]) == 2
    for r in doc["residuals"]:
        assert r["name"] == "serve/flush"
        assert math.isfinite(r["residual"]) and r["residual"] > 0
        assert r["labels"]["schedule"] == "single"
    assert sk.check_obs_document(doc, str(path)) == []
    assert not obs.enabled()                # serve uninstalled on exit
