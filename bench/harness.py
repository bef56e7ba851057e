"""One run of one cell: set-up, the measured window, the check, the
result line.

Set-up (all of it in ``setup_s``): the matrix made on the device from the
seed and pulled to the host as triplets; its registration (``convert_s``):
``to_coo`` then ``SparseOperator.from_coo`` with the single-chip SELL-C-σ
plan, blocked until the plan's arrays are on the device; one pass of the
traffic loop on its own request vectors, which compiles every flush shape
the window uses. The window drives ``RequestBatcher.submit`` and
``flush`` exactly as ``repro.launch.serve`` wires them. After it closes,
a sample of the answers drawn from the seed, at least one of each
client's, is compared with the plain reference in ``bench/reference.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import loop, reference, spec

SAMPLE = 32                 # answers compared with the reference per run,
                            # split evenly over the clients, at least one each
TRACE_DIR = ".bench_out/trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator, too few of them, or one without a peaks entry."""


def seed_key(jax, seed: int):
    """A JAX key from any whole number: 31 bits at a time, so seeds past
    what 32 signed bits hold keep all their bits."""
    s = int(seed) % (1 << 64)
    key = jax.random.key(s & 0x7FFFFFFF)
    for part in ((s >> 31) & 0x7FFFFFFF, s >> 62):
        key = jax.random.fold_in(key, part)
    return key


def check_device(jax, chips: int, root: Path):
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        peaks = spec.peaks(devs[0].device_kind, root)
    except LookupError as e:
        raise NoChip(str(e)) from e
    return devs, peaks


@dataclasses.dataclass
class Context:
    """What a metric's reader may read."""
    window: loop.Window
    setup_s: float
    convert_s: float
    m: int
    n: int
    nnz: int
    peaks: dict
    trace: Optional[object] = None       # bench.devtrace.Summary
    registry: Optional[object] = None    # repro.obs.MetricRegistry


def check_kernel(jax, op, n: int, k: int) -> None:
    """Raise unless the flush multiply runs the Mosaic SELL-C-σ kernel."""
    import jax.numpy as jnp
    from repro.spmm import spmm
    plan = op.plan
    x = jax.ShapeDtypeStruct((n, k), jnp.float32)
    if plan.impl == "pallas":
        lowered = jax.jit(lambda mat, X: spmm(mat, X, impl=plan.impl)).lower(
            plan.matrix, x)
        if "tpu_custom_call" in lowered.as_text():
            return
    raise RuntimeError(f"plan {plan.label} does not run the Mosaic "
                       "SELL-C-σ kernel")


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = spec.ROOT, t_start: Optional[float] = None) -> dict:
    """Run ``cell_name`` once and return the result line as a dict.
    Raises :class:`NoChip` before any work when the device will not do."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, cell_name)
    cfg = spec.config(bench, cell["config"], root)
    mix = spec.traffic(cell["traffic"], root)
    wanted = spec.metrics_for(bench, cell_name, trace)
    readers = {m["name"]: spec.reader(m["name"], root) for m in wanted}
    gen = spec.generator(cfg["generator"], root)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(root=root)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs, peaks = check_device(jax, int(cell["chips"]), root)
    from repro import obs
    from repro.core.convert import to_coo
    from repro.core.selector import PlanSpec
    from repro.spmm import RequestBatcher, SparseOperator

    compiles = [0]

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    key = seed_key(jax, seed)
    t = time.perf_counter()
    trip = gen.generate(cfg, jax.random.fold_in(key, 0))
    generate_s = time.perf_counter() - t
    m, n = trip.shape

    t = time.perf_counter()
    coo = to_coo(trip.rows, trip.cols, trip.vals, trip.shape)
    op = SparseOperator.from_coo(
        coo, PlanSpec(num_devices=1, algorithm="sellcs"))
    jax.block_until_ready(jax.tree_util.tree_leaves(op.plan.matrix))
    convert_s = time.perf_counter() - t
    clients = int(mix["clients"])
    check_kernel(jax, op, n, min(clients, int(mix["max_batch"])))

    batcher = RequestBatcher(op, max_batch=int(mix["max_batch"]),
                             impl=op.plan.impl,
                             spmm_fn=lambda _m, X: op.matmul(X))
    vectors = loop.Vectors(jax.random.fold_in(key, 1), n)
    if trace:
        obs.install(obs.MetricRegistry())
    t = time.perf_counter()
    loop.run(batcher, vectors, mix, 0.0, loop.WARM_UP)
    warm_s = time.perf_counter() - t
    registry = obs.install(obs.MetricRegistry()) if trace else None
    gc.collect()
    setup_s = time.perf_counter() - t_start

    per_client = -(-SAMPLE // clients)
    sample = [loop.Reservoir(per_client, (seed % (1 << 64), c))
              for c in range(clients)]
    trace_dir = root / TRACE_DIR
    compiles_before = compiles[0]
    if trace:
        from bench import devtrace
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=devtrace.options(jax))
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            window = loop.run(batcher, vectors, mix, seconds, loop.WINDOW,
                              on_answer=lambda c, i, y: sample[c].offer(
                                  (c, i, y)))
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs.uninstall()
    window_compiles = compiles[0] - compiles_before
    memory_peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")

    # the check: sampled answers and their requests, then the program's
    # state is freed and the reference runs on the host
    t = time.perf_counter()
    picked = [it for r in sample for it in sorted(r.items,
                                                  key=lambda it: it[1])]
    ys = np.stack([np.asarray(y) for _, _, y in picked], axis=1)
    xs = np.stack([np.asarray(vectors(loop.WINDOW, c, i))
                   for c, i, _ in picked], axis=1)
    del picked, sample, batcher, op, coo
    gc.collect()
    a = reference.csr(trip.rows, trip.cols, trip.vals, trip.shape)
    errs = reference.normwise_err(ys, reference.multiply(a, xs))
    limit = float(cfg["limit_normwise_err"])
    worst = float(np.max(errs))
    failed = int(np.sum(~(errs <= limit)))
    failed += window.submitted - window.answered
    reference_s = time.perf_counter() - t

    summary = None
    if trace:
        from bench import devtrace
        summary = devtrace.summarize(devtrace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = Context(window=window, setup_s=setup_s, convert_s=convert_s,
                  m=m, n=n, nnz=trip.nnz, peaks=peaks, trace=summary,
                  registry=registry)
    metrics: Dict[str, dict] = {}
    for entry in wanted:
        value = readers[entry["name"]].read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(failed == 0 and window.answered > 0
                           and worst <= limit),
           "attempted": window.submitted, "failed": failed,
           "metrics": metrics, "device": device,
           "window_compiles": window_compiles,
           "answered": window.answered, "flushes": window.flushes,
           "compared": int(errs.size),
           "setup_parts_s": {"generate": generate_s, "convert": convert_s,
                             "warm_up": warm_s},
           "reference_s": reference_s}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {"normwise_err": {"value": worst, "limit": limit},
                     "unanswered": {"value": window.submitted
                                    - window.answered, "limit": 0}}
    return out


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
