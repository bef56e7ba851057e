"""SpMM k-sweep: GFLOP/s and achieved arithmetic intensity vs the roofline
prediction, per format, for k in 1..256 (powers of two).

The point of the table: the matrix stream is paid once per multiply, so
intensity — and with it the attainable fraction of peak — must climb
monotonically with k until the ridge. ``ai`` uses each format's *actual*
``storage_bytes()`` (fill-in and padding included); ``ai_ideal`` is the
roofline model's ideal-CSR prediction from ``repro.roofline``.

  PYTHONPATH=src python -m benchmarks.spmm_sweep --scale 0.02 --json out.json

``--devices P`` additionally times the distributed SELL-C-σ schedules
(``repro.spmm.distributed``) on a P-device mesh per k; when jax has not
been imported yet the host-platform device count is forced automatically.
``--chunks 1,2,8`` sweeps the merge-psum pipelining depth too — one
``chunks=<c>`` row per count, so ``benchmarks.smoke_check`` can gate the
chunked rows against the monolithic (``chunks=1``) baseline.

``--mesh 8x1,4x2`` sweeps 2-D (data, model) mesh factorizations instead of
(or next to) the 1-D ``--devices`` mesh: one ``@PdxPmmesh`` row group per
shape, each with the 2-D traffic model's ``model_us`` prediction, so
``smoke_check`` can gate the model-sharded rows against the pure-data
(``Pm = 1``) baseline wherever the model says the model axis pays.

``--compact-x on,off`` adds a sparsity-aware X gather column to every
distributed row group: one ``cx=on`` row (per-shard column compaction,
gathered ``[n_touched, kc]`` slabs) next to each ``cx=off`` row
(replicated X), each priced by the compact traffic model with the
partitioner's *measured* mean ``n_touched``, so
``smoke_check.check_compact_regressions`` can gate the compacted rows
wherever the model says the gather pays (disarmed on ``backend=cpu``
like the mesh gate — a host-platform mesh shares one X buffer).

``--gather upfront,overlap`` sweeps the compact-X gather schedule next to
the up-front one: each compacted (``cx=on``) row grows a ``gx=<mode>``
sibling per non-default mode (``overlap`` double-buffers the per-span
gather against the merge chunk stream), each priced by the
exposed-gather roofline term (``spmm_distributed_gather_s``) and stamped
with ``exposed_gather_us=`` so ``smoke_check.check_gather_overlap`` can
gate the hidden-gather rows against their up-front baseline wherever the
model says hiding pays (disarmed on ``backend=cpu`` like the other mesh
gates).

``--op N,T`` adds the transpose multiply (``A^T X``, X read at [m, k])
next to each forward row of every distributed group: one ``op=T`` row per
``op=N`` row, each priced by the op-aware traffic model (dense slot-space
X read, full-column partial, scatter psum), so
``smoke_check.check_transpose_regressions`` can gate the transpose rows
against the model-predicted N-to-T slowdown (disarmed on ``backend=cpu``
like the other mesh gates).

Emits the same CSV columns and JSON schema as ``benchmarks.run``.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def sweep_matrix(name: str, coo, ks, impl: str, reps: int, csv) -> None:
    import jax.numpy as jnp
    from repro.core import coo_to_csr
    from repro.kernels.tiling import coo_to_tiled
    from repro.roofline import (spmm_arithmetic_intensity,
                                spmm_roofline_gflops)
    from repro.spmm import coo_to_sellcs, spmm
    from . import harness

    m, n = coo.shape
    nnz = coo.nnz
    formats = {"csr": coo_to_csr(coo), "sellcs": coo_to_sellcs(coo)}
    try:
        formats["tiled_csb"] = coo_to_tiled(coo, "csb")
    except MemoryError:
        pass                       # too sparse for dense mini-tiles
    rng = np.random.default_rng(0)
    for fmt, mat in formats.items():
        # the Pallas kernels are the SELL-C-σ family: the other formats'
        # rows stay on the XLA reference
        fmt_impl = ("ref" if impl.startswith("pallas") and fmt != "sellcs"
                    else impl)
        for k in ks:
            X = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
            sec = harness.time_fn(lambda: spmm(mat, X, impl=fmt_impl),
                                  reps=reps, warmup=1)
            flops = 2.0 * nnz * k
            gflops = flops / sec / 1e9
            ai = spmm_arithmetic_intensity(
                nnz, m, n, k, matrix_bytes=mat.storage_bytes())
            ai_ideal = spmm_arithmetic_intensity(nnz, m, n, k)
            roof = spmm_roofline_gflops(ai)
            csv.row(f"{name}/{fmt}/k={k}", sec,
                    f"gflops={gflops:.4g};ai={ai:.4f};"
                    f"ai_ideal={ai_ideal:.4f};roof_gflops={roof:.1f}")


def _sweep_shapes(name: str, coo, ks, mesh_shapes, reps: int, csv,
                  chunk_counts, tag_of, compact_flags=(False,),
                  ops=("N",), gathers=("upfront",)) -> None:
    """Shared measurement core of ``sweep_distributed`` / ``sweep_mesh2d``:
    both schedules per (P_data, P_model) shape (ref impl bodies — the
    host-platform mesh has no TPU cores to feed the Pallas path), the
    merge schedule once per ``chunk_counts`` entry, each row priced by the
    (2-D) traffic model. ``tag_of(pd, pm)`` renders the mesh part of the
    row name; sweeping ``compact_flags`` beyond the plain ``(False,)``
    appends a ``/cx=on|off`` segment and prices the compact rows with the
    partitioner's measured mean ``n_touched``; sweeping ``ops`` beyond
    ``("N",)`` appends an ``/op=N|T`` segment — the transpose rows read X
    at [m, k] and are priced by the op-aware traffic model, giving
    ``smoke_check.check_transpose_regressions`` its same-config op=N
    baseline; sweeping ``gathers`` beyond ``("upfront",)`` appends a
    ``/gx=<mode>`` segment to the non-default compacted rows (the
    up-front baseline keeps its unsuffixed name) so
    ``smoke_check.check_gather_overlap`` can pair them.
    """
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_spmm_mesh
    from repro.roofline import (spmm_distributed_gather_s,
                                spmm_distributed_time,
                                spmm_distributed_traffic)
    from repro.spmm import (coo_to_sellcs, partition_sellcs_nnz,
                            partition_sellcs_rows, spmm_merge_distributed,
                            spmm_row_distributed)
    from . import harness

    m, n = coo.shape
    nnz = coo.nnz
    max_row = int(np.bincount(np.asarray(coo.rows), minlength=m).max()) \
        if nnz else 0
    sc = coo_to_sellcs(coo)
    rng = np.random.default_rng(1)
    # the mesh/compact gates need to know whether the mesh had per-device
    # memory: on a host-platform (cpu) mesh the "replicated" X is one
    # shared buffer and neither column-sharding nor compacting it saves
    # anything, so measured rows there are recorded but never gated
    # (smoke_check.check_mesh_regressions / check_compact_regressions)
    backend = jax.default_backend()
    tag_cx = tuple(compact_flags) != (False,)
    tag_op = tuple(ops) != ("N",)
    for pd, pm in mesh_shapes:
        mesh = make_spmm_mesh((pd, pm))
        for cf in compact_flags:
            def mean_nt(sh):
                # the map the multiply EXECUTES: a baked chunk plan
                # gathers through its re-dealt map, not the base one
                if not cf:
                    return None
                src = (sh.chunk_plan[3] if sh.chunk_plan is not None
                       else sh.n_touched)
                return float(np.mean(np.asarray(src)))
            row_sharded = partition_sellcs_rows(sc, pd, compact_x=cf)
            # one shared merge partition for every replicated depth: the
            # span re-deal happens at trace time inside the jitted
            # closure, so no per-depth copies of the base device-dealt
            # arrays stay alive. Compacted depths > 1 bake the plan
            # instead — its re-dealt col_map is what the multiply gathers
            # through, and the model must price THAT map's n_touched
            mrg_sharded = partition_sellcs_nnz(sc, pd, compact_x=cf)
            # the gather schedule is a compact-only knob: replicated-X
            # rows have no X gather to hide, so they sweep "upfront" only
            gs = tuple(gathers) if cf else ("upfront",)
            variants = []
            for opv in ops:
                for g in gs:
                    variants.append(
                        ("row", None, mean_nt(row_sharded), opv, g,
                         jax.jit(lambda X, rs=row_sharded, me=mesh, o=opv,
                                 g=g:
                                 spmm_row_distributed(rs, X, me, op=o,
                                                      gather=g))))
                for c in chunk_counts:
                    ms = mrg_sharded
                    if cf and int(c) > 1:
                        ms = partition_sellcs_nnz(sc, pd, num_chunks=int(c),
                                                  compact_x=True)
                    for g in gs:
                        variants.append(
                            ("merge", int(c), mean_nt(ms), opv, g,
                             jax.jit(lambda X, ms=ms, me=mesh, c=int(c),
                                     o=opv, g=g:
                                     spmm_merge_distributed(ms, X, me,
                                                            num_chunks=c,
                                                            op=o,
                                                            gather=g))))
            cx = f"/cx={'on' if cf else 'off'}" if tag_cx else ""
            for sched, nc, n_touched, opv, g, jitted in variants:
                gx = f"/gx={g}" if g != "upfront" else ""
                tag = f"{name}/sellcs+{sched}{tag_of(pd, pm)}" + \
                    (f"/chunks={nc}" if nc is not None else "") + cx + \
                    gx + (f"/op={opv}" if tag_op else "")
                for k in ks:
                    X = jnp.asarray(rng.standard_normal(
                        (m if opv == "T" else n, k)).astype(np.float32))
                    sec = harness.time_fn(lambda: jitted(X), reps=reps,
                                          warmup=1)
                    gflops = 2.0 * nnz * k / sec / 1e9
                    hbm, coll = spmm_distributed_traffic(
                        m, n, k, pd, sched, nnz=nnz, max_row_nnz=max_row,
                        model_devices=pm, compact_x=cf,
                        n_touched=n_touched, op=opv)
                    model_s = spmm_distributed_time(
                        m, n, k, pd, sched, nnz=nnz, max_row_nnz=max_row,
                        num_chunks=nc or 1, model_devices=pm,
                        compact_x=cf, n_touched=n_touched, op=opv,
                        gather=g)
                    # residual = observed/modeled — the same quantity the
                    # serve-path ResidualLedger records, stamped per row
                    # so smoke_check's residual gate reads sweep JSON and
                    # serve metrics dumps identically
                    derived = (f"gflops={gflops:.4g};"
                               f"hbm_mb={hbm / 1e6:.4g};"
                               f"coll_mb={coll / 1e6:.4g};"
                               f"model_us={model_s * 1e6:.4g};"
                               f"residual={sec / model_s:.4g};"
                               f"backend={backend}")
                    if cf:
                        exposed_s = spmm_distributed_gather_s(
                            m, n, k, pd, sched, nnz=nnz,
                            max_row_nnz=max_row, num_chunks=nc or 1,
                            model_devices=pm, compact_x=cf,
                            n_touched=n_touched, op=opv, gather=g)
                        derived += (f";n_touched={n_touched:.4g}"
                                    f";exposed_gather_us="
                                    f"{exposed_s * 1e6:.4g}")
                    csv.row(f"{tag}/k={k}", sec, derived)


def sweep_distributed(name: str, coo, ks, devices: int, reps: int,
                      csv, chunk_counts=(1,), compact_flags=(False,),
                      ops=("N",), gathers=("upfront",)) -> None:
    """Distributed schedules on a 1-D `devices`-wide data mesh: the
    ``@{P}dev`` row family ``smoke_check``'s chunk gate consumes."""
    _sweep_shapes(name, coo, ks, ((devices, 1),), reps, csv, chunk_counts,
                  lambda pd, pm: f"@{pd}dev", compact_flags=compact_flags,
                  ops=ops, gathers=gathers)


def sweep_mesh2d(name: str, coo, ks, mesh_shapes, reps: int, csv,
                 chunk_counts=(1,), compact_flags=(False,),
                 ops=("N",), gathers=("upfront",)) -> None:
    """Both schedules over 2-D (data, model) mesh factorizations: the
    ``@{Pd}x{Pm}mesh`` row family — include a ``Pm = 1`` shape to give
    ``smoke_check``'s model-axis gate its pure-data baseline."""
    _sweep_shapes(name, coo, ks, mesh_shapes, reps, csv, chunk_counts,
                  lambda pd, pm: f"@{pd}x{pm}mesh",
                  compact_flags=compact_flags, ops=ops, gathers=gathers)


def run(suite_scale: float = 0.02, kmax: int = 256, impl: str = "ref",
        reps: int = 3, matrices_only=None, devices: int = 1,
        chunk_counts=(1,), mesh_shapes=(), compact_flags=(False,),
        ops=("N",), gathers=("upfront",)) -> None:
    from repro.data import matrices
    from . import harness

    ks = []
    k = 1
    while k <= kmax:
        ks.append(k)
        k *= 2
    suite = matrices.test_suite(scale=suite_scale)
    names = matrices_only or ["hhh_like", "livejournal_like", "mawi_like"]
    extra = ""
    if devices > 1:
        extra += f", devices={devices}, chunks={list(chunk_counts)}"
    if mesh_shapes:
        extra += f", meshes={['%dx%d' % s for s in mesh_shapes]}"
    if tuple(compact_flags) != (False,):
        extra += (", compact_x="
                  f"{[('on' if f else 'off') for f in compact_flags]}")
    if tuple(ops) != ("N",):
        extra += f", ops={list(ops)}"
    if tuple(gathers) != ("upfront",):
        extra += f", gathers={list(gathers)}"
    title = f"SpMM k-sweep (impl={impl}, k in {ks}{extra})"
    csv = harness.Csv(title)
    for name in names:
        if name not in suite:
            raise SystemExit(f"unknown matrix {name}; one of {sorted(suite)}")
        coo = matrices.as_coo(suite[name].make())
        sweep_matrix(name, coo, ks, impl, reps, csv)
        if devices > 1:
            sweep_distributed(name, coo, ks, devices, reps, csv,
                              chunk_counts=chunk_counts,
                              compact_flags=compact_flags, ops=ops,
                              gathers=gathers)
        if mesh_shapes:
            sweep_mesh2d(name, coo, ks, mesh_shapes, reps, csv,
                         chunk_counts=chunk_counts,
                         compact_flags=compact_flags, ops=ops,
                         gathers=gathers)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--kmax", type=int, default=256)
    ap.add_argument("--impl", default="ref",
                    choices=("auto", "ref", "pallas", "pallas_interpret"))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--matrices", default=None,
                    help="comma-separated subset of the matrix suite")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as JSON (harness schema)")
    ap.add_argument("--devices", type=int, default=1,
                    help="also sweep the distributed schedules over a mesh "
                         "of this many devices")
    ap.add_argument("--chunks", default="1",
                    help="comma-separated merge-psum pipelining depths to "
                         "sweep (with --devices); each count emits its own "
                         "chunks=<c> rows next to the monolithic chunks=1")
    ap.add_argument("--mesh", default=None,
                    help="comma-separated 2-D (data, model) mesh shapes to "
                         "sweep as PdxPm, e.g. 8x1,4x2 — include a Pm=1 "
                         "shape so smoke_check's model-axis gate has its "
                         "pure-data baseline")
    ap.add_argument("--compact-x", default="off", dest="compact_x",
                    help="comma-separated on/off: sweep the sparsity-aware "
                         "X gather next to replication — 'on,off' emits a "
                         "cx=on row per cx=off row so smoke_check's "
                         "compact gate has its replicated baseline")
    ap.add_argument("--gather", default="upfront",
                    help="comma-separated subset of upfront,overlap: "
                         "sweep the compact-X gather schedule (needs "
                         "--compact-x on) — 'upfront,overlap' emits a "
                         "gx=overlap row per compacted baseline row so "
                         "smoke_check's gather gate can pair them")
    ap.add_argument("--op", default="N",
                    help="comma-separated subset of N,T: sweep the "
                         "transpose multiply (A^T X) next to the forward "
                         "one — 'N,T' emits an op=T row per op=N row so "
                         "smoke_check's transpose gate has its forward "
                         "baseline")
    args = ap.parse_args(argv)
    try:
        chunk_counts = tuple(int(c) for c in args.chunks.split(",") if c)
    except ValueError:
        raise SystemExit(f"--chunks must be comma-separated ints, got "
                         f"{args.chunks!r}")
    if not chunk_counts or any(c < 1 for c in chunk_counts):
        raise SystemExit(f"--chunks entries must be >= 1, got {args.chunks!r}")
    cx_entries = tuple(s for s in args.compact_x.split(",") if s)
    if not cx_entries or any(s not in ("on", "off") for s in cx_entries):
        raise SystemExit(f"--compact-x must be comma-separated on/off "
                         f"entries, got {args.compact_x!r}")
    compact_flags = tuple(s == "on" for s in cx_entries)
    ops = tuple(s for s in args.op.split(",") if s)
    if not ops or any(o not in ("N", "T") for o in ops):
        raise SystemExit(f"--op must be comma-separated N/T entries, "
                         f"got {args.op!r}")
    gathers = tuple(s for s in args.gather.split(",") if s)
    if not gathers or any(g not in ("upfront", "overlap")
                          for g in gathers):
        raise SystemExit(f"--gather must be comma-separated "
                         f"upfront/overlap entries, got "
                         f"{args.gather!r}")
    if gathers != ("upfront",) and True not in compact_flags:
        raise SystemExit("--gather beyond 'upfront' needs --compact-x on "
                         "rows — a replicated-X stream has no X gather "
                         "to hide")
    mesh_shapes = ()
    if args.mesh:
        try:
            mesh_shapes = tuple(
                tuple(int(p) for p in s.split("x"))
                for s in args.mesh.split(",") if s)
        except ValueError:
            raise SystemExit(f"--mesh must be comma-separated PdxPm "
                             f"entries, got {args.mesh!r}")
        if any(len(s) != 2 or s[0] < 1 or s[1] < 1 for s in mesh_shapes):
            raise SystemExit(f"--mesh entries must be PdxPm with both "
                             f">= 1, got {args.mesh!r}")

    need = max([args.devices] + [pd * pm for pd, pm in mesh_shapes])
    if need > 1 and "jax" not in sys.modules:
        # must happen before the first jax import anywhere in the process
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={need} "
            + os.environ.get("XLA_FLAGS", ""))
    if need > 1:
        import jax
        if len(jax.devices()) < need:
            raise SystemExit(
                f"the sweep needs {need} devices but jax sees "
                f"{len(jax.devices())}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} "
                "before any jax import")

    from . import harness
    harness.reset_records()
    run(suite_scale=args.scale, kmax=args.kmax, impl=args.impl,
        reps=args.reps,
        matrices_only=args.matrices.split(",") if args.matrices else None,
        devices=args.devices, chunk_counts=chunk_counts,
        mesh_shapes=mesh_shapes, compact_flags=compact_flags, ops=ops,
        gathers=gathers)
    if args.json:
        harness.dump_json(args.json)


if __name__ == "__main__":
    main()
