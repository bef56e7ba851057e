#!/usr/bin/env python3
"""Smoke test of the sparse serving path on TPU v5e chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the distributed schedules, 4 chips

One chip: ``repro.launch.serve --mode spmv`` serves 64 single-vector
requests, batched 32 per flush, against the Graph500-class ``kron_like``
matrix at ``--scale 128`` (rmat scale 21: 2,097,152 rows and columns,
48,097,363 nonzeros) through the Pallas SELL-C-σ kernel. Every served
column is checked against XLA's COO multiply (``spmm_ref`` on the COO,
no Pallas) on the same chip.

Four chips: the same matrix served over a 4-device mesh, once by the row
schedule and once by the merge schedule at two chunks (8 requests, one
flush each), each checked against the same one-device reference.

Everything runs in this one process (a child that touched JAX could not
reach the chip). The last line of standard output is one JSON object naming
the device, printed only when every phase passed; the script exits non-zero
without it when no TPU v5e is found, when a check fails, or when any phase
raises. Serve metrics go to ``chiprun_out/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
MAX_REL_ERR = 1e-5      # norm-wise relative error per served column
REF_COLS = 8            # reference columns per multiply: bounds the
                        #   [nnz, cols] gather of the COO reference
MAX_BATCH = 32          # one chip: 64 requests, two flushes of 32
MESH_BATCH = 8          # four chips: 8 requests, one flush per schedule
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check_device(jax, chips: int):
    """The first device must be a TPU v5e, and ``chips`` of them must be
    visible; anything else is a failure, never a fallback."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: jax reports platform {d.platform!r}")
    kind = d.device_kind
    if "v5 lite" not in kind.lower() and "v5e" not in kind.lower():
        raise RuntimeError(f"device kind {kind!r} is not a TPU v5e")
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} but jax sees {len(devs)}")
    return d


def worst_rel_err(run):
    """Max over served columns of ||y - y_ref|| / ||y_ref||, where y_ref is
    XLA's COO multiply of the same request on the first device; also the
    smallest ||y_ref||, which shows the comparison is not of zeros."""
    import jax
    import jax.numpy as jnp
    from repro.spmm import spmm_ref
    dev = jax.devices()[0]
    coo = jax.device_put(run.coo, dev)
    worst, ref_norm = 0.0, float("inf")
    for j in range(0, len(run.xs), REF_COLS):
        x = jax.device_put(jnp.stack(run.xs[j:j + REF_COLS], axis=1), dev)
        y = jax.device_put(jnp.stack(run.ys[j:j + REF_COLS], axis=1), dev)
        ref = spmm_ref(coo, x)
        norm = jnp.linalg.norm(ref, axis=0)
        err = jnp.linalg.norm(y - ref, axis=0) / jnp.maximum(norm, 1e-30)
        worst = max(worst, float(jnp.max(err)))
        ref_norm = min(ref_norm, float(jnp.min(norm)))
    return worst, ref_norm


def flush_has_kernel(run, k: int) -> bool:
    """Whether the compiled k-column flush multiply holds a Mosaic
    kernel."""
    import jax
    import jax.numpy as jnp
    from repro.spmm import spmm
    plan = run.plan
    x = jax.ShapeDtypeStruct((run.coo.shape[1], k), jnp.float32)
    if hasattr(plan.multiply, "lower"):          # jitted mesh multiply
        lowered = plan.multiply.lower(x)
    else:
        lowered = jax.jit(lambda m, X: spmm(m, X, impl=plan.impl)).lower(
            plan.matrix, x)
    return "tpu_custom_call" in lowered.compile().as_text()


def serve_phase(name: str, argv, k: int, compile_s) -> int:
    """One ``launch.serve`` run at ``k`` requests per flush, plus its
    checks; returns the matrix's column count. Nothing of the run is kept,
    so the next phase has the device memory to itself."""
    from repro.launch import serve
    log(f"{name}: serve {' '.join(argv)}")
    c0, t0 = compile_s[0], time.perf_counter()
    run = serve.main(argv)
    wall = time.perf_counter() - t0
    plan = run.plan
    sc = plan.local_matrix
    m, n = run.coo.shape
    log(f"{name}: m={m} n={n} nnz={run.coo.nnz} slots={sc.padded_nnz} "
        f"fill_ratio={sc.fill_ratio:.4f}")
    log(f"{name}: plan={plan.label} impl={plan.impl}")
    log(f"{name}: compile_s={compile_s[0] - c0:.3f} serve_wall_s={wall:.3f} "
        f"flush_p50_s={run.flush_p50_s} batched_s={run.t_batched:.6f} "
        f"sequential_s={run.t_seq:.6f}")
    if plan.impl != "pallas":
        raise RuntimeError(f"{name}: served by impl={plan.impl!r}, "
                           "not the Pallas kernel")
    if not flush_has_kernel(run, k):
        raise RuntimeError(f"{name}: compiled flush holds no "
                           "tpu_custom_call")
    log(f"{name}: compiled flush holds tpu_custom_call")
    worst, ref_norm = worst_rel_err(run)
    log(f"{name}: worst per-column relative error {worst:.3e} over "
        f"{len(run.ys)} columns (limit {MAX_REL_ERR:g}; smallest reference "
        f"column norm {ref_norm:.4g})")
    if not worst <= MAX_REL_ERR or not ref_norm > 0:
        raise RuntimeError(f"{name}: reference check failed: {worst:.3e}")
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-chip serve; 4: the row and merge "
                         "schedules on a 4-device mesh, and nothing else")
    ap.add_argument("--scale", type=float, default=128.0,
                    help="matrix suite scale (128 = rmat scale 21)")
    args = ap.parse_args(argv)
    try:
        sys.path.insert(0, os.path.join(HERE, "src"))
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache(root=HERE)}")
        import jax
        dev = check_device(jax, args.chips)
        log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
        compile_s = [0.0]

        def on_duration(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                compile_s[0] += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        os.makedirs(OUT_DIR, exist_ok=True)
        common = ["--mode", "spmv", "--matrix", "kron_like",
                  "--scale", f"{args.scale:g}", "--algorithm", "sellcs",
                  "--impl", "pallas", "--seed", "0"]
        if args.chips == 1:
            n = serve_phase("1chip", common + [
                "--max-batch", str(MAX_BATCH), "--requests", "64",
                "--reps", "2", "--metrics",
                os.path.join(OUT_DIR, "chip_smoke_1chip.json")],
                MAX_BATCH, compile_s)
        else:
            for sched, extra in (("row", []), ("merge", ["--chunks", "2"])):
                n = serve_phase(f"4chip-{sched}", common + [
                    "--devices", "4", "--schedule", sched,
                    "--compact-x", "off", "--max-batch", str(MESH_BATCH),
                    "--requests", str(MESH_BATCH), "--reps", "1",
                    "--metrics",
                    os.path.join(OUT_DIR, f"chip_smoke_4chip_{sched}.json"),
                ] + extra, MESH_BATCH, compile_s)
                gc.collect()            # free the phase's device buffers
        peaks = [d.memory_stats().get("peak_bytes_in_use")
                 for d in jax.devices()[:args.chips]]
        log(f"peak_bytes_in_use per device: {peaks}")
        # every mesh device must have held at least its replicated X
        x_bytes = n * MESH_BATCH * 4
        if args.chips > 1 and not all(p and p >= x_bytes for p in peaks):
            raise RuntimeError(f"a mesh device held less than one X slab "
                               f"({x_bytes} B): {peaks}")
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
