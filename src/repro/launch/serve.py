"""Serving entry points.

LM mode — batched prefill + greedy decode with KV caches (CPU-scale demo,
reduced config, real execution):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --batch 4 --prompt-len 32 --gen 16

SpMV mode — the repro.spmm request batcher serving single-vector requests:
queued ``A @ x`` requests aggregate into one SpMM per flush (matrix stream
amortized over the batch), measured against serving them one by one:
  PYTHONPATH=src python -m repro.launch.serve --mode spmv \
      --matrix mawi_like --requests 64 --max-batch 32

Mesh serving — ``--devices P`` answers each flush with a *distributed*
SpMM over a P-device mesh (``repro.spmm.distributed``); format,
cross-device schedule and the merge-psum pipelining depth come from the
``core.select_distributed`` grid (``--chunks c`` pins the depth).
``--mesh Pd,Pm`` pins a 2-D (data, model) factorization instead: the model
axis column-shards the X/Y k-slabs so per-device psum and replicated-X
bytes drop by Pm — the k ≫ 128 scaling axis. ``--compact-x on`` partitions
with per-shard column compaction (each data shard gathers only the X rows
its nonzeros touch instead of reading the replicated slab; ``auto`` asks
the traffic model whether the gather pays). ``--gather upfront|overlap``
schedules that gather's exposed latency — up-front ahead of the mesh
region, or hidden under the chunked merge span loop (``auto`` lets the
exposed-gather-seconds roofline term pick). On CPU, force host-platform
devices first:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --mode spmv --matrix mawi_like \
      --requests 64 --max-batch 32 --mesh 4,2 --impl ref --chunks 4

Online migration — ``--migrate auto`` serves through one
``repro.spmm.SparseOperator`` handle that starts in the zero-conversion
merge-path format, counts served multiplies, and converts to the
SELL-C-σ target plan **in a background thread** once the live break-even
estimate (measured conversion cost over measured-and-residual-corrected
per-multiply saving, cold-started from the ``selector.break_even_spmvs``
priors — the paper's §7 "472 multiplications" economics) clears the
projected remaining traffic; the new plan is swapped in atomically
between flushes. ``force`` converts unconditionally (still off the flush
path), ``off`` (default) pins the start format forever. Decision inputs
land in the metrics document: ``serve/multiplies_total``,
``serve/breakeven_estimate``, ``serve/plan_swaps``,
``serve/swap_at_multiply``, ``serve/convert_s`` and the pre/post-swap
flush histograms.

Fleet mode — ``--mode fleet --tenants N`` serves N matrices from one
process through a :class:`repro.spmm.Fleet` (fingerprint-keyed plan cache;
returning tenants skip partitioning) and a
:class:`repro.spmm.FleetBatcher` (per-tenant queues; flushes scheduled by
SLO-deadline urgency × batch-efficiency under ``--slo-ms``).
``--fail-device auto`` kills a data-shard device mid-stream: the fleet
re-deals the lost shard's width-row spans across the survivors
(``redeal_sellcs`` — no re-conversion) and keeps serving:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --mode fleet --tenants 3 --slo-ms 50 \
      --matrix mawi_like --devices 8 --impl ref --fail-device auto \
      --metrics BENCH_serve_slo.json

Observability — ``--metrics out.json`` installs a ``repro.obs`` registry
for the run and dumps it at the end: per-flush phase spans (the
``batcher/*`` series plus, on a mesh, an eager phase-profile pass through
``spmm/gather_x`` / ``spmm/mesh`` / ``spmm/kernel`` / ``spmm/psum`` /
``spmm/fixup``), p50/p95/p99 flush latency (``serve/flush_s``, exact
order statistics at serve batch counts), and one ``ResidualLedger``
record per flush pairing the measured wall time with the roofline
prediction (``spmm_distributed_time``) for the chosen
``DistributedChoice`` — the observed-vs-modeled residuals that feed
``core.autotune(feedback=)``. Headline timings follow the paper's §5.2
min-of-N protocol (``--reps``), never a single ``perf_counter`` pair.
"""
from __future__ import annotations

import argparse
import functools
import math
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.models.model import decode_step, init_params, prefill


class _MigrationController:
    """The online break-even loop — the paper's "472 multiplications" §7
    economics as a live control law over the serving traffic.

    Between flushes it (a) counts served multiplies (SpMV-equivalents —
    the unit of the paper's break-even), (b) feeds the live
    ``ResidualLedger`` back into ``select_distributed(feedback=)`` to
    re-pick the target plan's knobs with ledger-corrected scores, and (c)
    maintains the break-even estimate ``convert_cost_s / per-multiply
    saving``: the saving is the *measured* per-multiply latency of the
    current plan times the modeled (and residual-corrected) improvement
    ratio to the target, the conversion cost starts as the
    ``selector.break_even_spmvs`` priors (in measured seconds) and is
    replaced by the measured build time once the conversion runs. When
    the projected remaining traffic clears the estimate (``--migrate
    auto``; ``force`` skips the test, ``off`` disables the loop), the
    target plan is built in a **background thread** — never on the flush
    path — and installed through ``SparseOperator.swap`` between flushes.
    """

    def __init__(self, op, stats, args, target_spec, ledger, reg=None):
        from repro.core.selector import (DEFAULT_CONVERSION_COST,
                                         DEFAULT_THROUGHPUT,
                                         DENSITY_THRESHOLD,
                                         ZERO_CONVERSION_ALGO,
                                         _augment_sellcs, break_even_spmvs)
        self.op = op
        self.stats = stats
        self.mode = args.migrate
        self.max_batch = int(args.max_batch)
        self.projected_total = int(args.requests)
        self.target_spec = target_spec
        self.ledger = ledger
        self.reg = reg
        self.multiplies = 0
        self.swapped = False
        self.swap_unix_s = None
        self.swap_at_multiply = None
        self.convert_s = None
        self.error = None
        self._min_per_mul = math.inf
        self._last_saving = None
        self._target_choice = None
        self._worker = None
        self._pending = None
        # cold-start break-even from the paper's priors: the target is
        # SELL-C-σ, the baseline is the zero-conversion start whose
        # conversion is already paid (hence cost 0). Often inf on these
        # priors (the tables do not flatter sellcs) — the first flush
        # replaces it with the measured/ledger-corrected estimate.
        low = stats.density < DENSITY_THRESHOLD
        numa = (target_spec.num_devices or 1) > 1
        self._thr, self._conv = _augment_sellcs(
            dict(DEFAULT_THROUGHPUT[(numa, low)]),
            dict(DEFAULT_CONVERSION_COST), stats)
        self.breakeven = break_even_spmvs(
            "sellcs", baseline=ZERO_CONVERSION_ALGO, numa_like=numa,
            low_density=low, throughput=self._thr,
            conversion_cost={**self._conv, ZERO_CONVERSION_ALGO: 0.0})
        self._publish()

    def note_flush(self, k, dt, rp):
        """Called after every flush (k served columns in dt seconds on
        plan ``rp``): update counters and the break-even estimate, start
        the background build when the projection clears it, and install a
        finished build before the next flush."""
        k = int(k)
        self.multiplies += k
        if self.reg is not None:
            self.reg.counter("serve/multiplies_total").inc(k)
        if self.mode == "off" or self.error is not None:
            return
        if not self.swapped:
            self._min_per_mul = min(self._min_per_mul, dt / max(k, 1))
            self._update_estimate(rp)
            remaining = self.projected_total - self.multiplies
            if self._worker is None and (self.mode == "force"
                                         or remaining > self.breakeven):
                self._start_build()
        self._install_pending()
        self._publish()

    def finish(self):
        """End of the traffic: a build still in flight is joined and
        installed (a forced migration must land even when the traffic
        runs out first), and a background failure surfaces here instead
        of dying silently in the worker thread."""
        if self._worker is not None:
            self._worker.join()
        self._install_pending()
        self._publish()
        if self.error is not None:
            raise self.error

    def _update_estimate(self, rp):
        """Ledger-corrected live break-even: measured per-multiply on the
        current plan, modeled (and residual-corrected) per-multiply on
        the re-selected target, conversion priced by the priors until the
        build measures it."""
        if not math.isfinite(self._min_per_mul):
            return
        from repro.core.selector import (_matrix_bytes_est,
                                         select_distributed)
        from repro.obs import choice_labels
        from repro.roofline import spmm_distributed_time
        st, kb = self.stats, self.max_batch
        # the current plan's measured per-shard touched-column mean (None
        # when it has no compact plan) replaces the nnz-proportional bound
        # in the target's score — the same matrix, so the measurement
        # carries
        nt = rp.n_touched
        ch = select_distributed(st, k=kb,
                                num_spmvs=max(self.projected_total, 1),
                                spec=self.target_spec,
                                feedback=self.ledger, n_touched=nt)
        self._target_choice = ch
        pd, pm = ch.mesh_shape
        gx = ch.gather if ch.compact_x else "upfront"
        t_model = spmm_distributed_time(
            st.m, st.n, kb, pd, ch.schedule,
            matrix_bytes=_matrix_bytes_est(ch.algorithm, st),
            max_row_nnz=st.max_row_nnz, num_chunks=ch.num_chunks,
            model_devices=pm, compact_x=ch.compact_x, nnz=st.nnz,
            n_touched=nt if ch.compact_x else None, gather=gx)
        t_corr = self.ledger.correction(**choice_labels(
            schedule=ch.schedule, num_chunks=ch.num_chunks,
            mesh_shape=ch.mesh_shape, compact_x=ch.compact_x,
            gather=gx if ch.compact_x else None))
        c_model = rp.model_s(kb) * self.ledger.correction(**rp.labels())
        per_now = self._min_per_mul
        per_target = per_now * (t_model * t_corr) / max(c_model, 1e-30)
        saving = per_now - per_target        # seconds saved per multiply
        self._last_saving = saving
        if saving <= 0:
            self.breakeven = math.inf
            return
        convert_s = self.convert_s
        if convert_s is None:
            # prior units are ParCRS SpMVs; the current plan runs one
            # multiply at thr[parcrs]/thr[cur] of a ParCRS one
            cur = rp.spec.algorithm or "merge"
            per_parcrs = per_now * (
                self._thr.get(cur, self._thr["parcrs"])
                / self._thr["parcrs"])
            convert_s = self._conv["sellcs"] * per_parcrs
        self.breakeven = convert_s / saving

    def _start_build(self):
        from repro.core import PlanSpec
        ch = self._target_choice
        if ch is None:
            spec = self.target_spec
        else:
            spec = PlanSpec(num_devices=ch.mesh_shape[0] * ch.mesh_shape[1],
                            mesh_shape=ch.mesh_shape,
                            num_chunks=ch.num_chunks,
                            compact_x=ch.compact_x, schedule=ch.schedule,
                            algorithm=ch.algorithm,
                            gather=ch.gather if ch.compact_x else None)

        def build():
            try:
                t0 = time.perf_counter()
                rp = self.op.realize(spec, feedback=self.ledger)
                self.convert_s = time.perf_counter() - t0
                self._pending = rp
            except BaseException as e:       # surface in finish()
                self.error = e

        self._worker = threading.Thread(target=build, name="serve-migrate",
                                        daemon=True)
        self._worker.start()

    def _install_pending(self):
        rp = self._pending
        if rp is None:
            return
        self._pending = None
        self.op.swap(rp)
        self.swapped = True
        self.swap_unix_s = self.op.stats.last_swap_unix_s
        self.swap_at_multiply = self.multiplies
        if self.convert_s is not None and self._last_saving is not None \
                and self._last_saving > 0:
            # both sides measured now: real build seconds over real saving
            self.breakeven = self.convert_s / self._last_saving
        if self.reg is not None:
            self.reg.counter("serve/plan_swaps").inc()
            self.reg.gauge("serve/swap_unix_s").set(
                float(self.swap_unix_s))
            self.reg.gauge("serve/swap_at_multiply").set(
                float(self.swap_at_multiply))
            if self.convert_s is not None:
                self.reg.gauge("serve/convert_s").set(
                    float(self.convert_s))
        conv_ms = (self.convert_s or 0.0) * 1e3
        print(f"[serve-spmv] migrated to {rp.label} after "
              f"{self.swap_at_multiply} multiplies (convert "
              f"{conv_ms:.1f} ms in background, break-even "
              f"~{self.breakeven:.3g} multiplies)")

    def _publish(self):
        if self.reg is not None:
            self.reg.gauge("serve/breakeven_estimate").set(
                float(self.breakeven))


class SpmvServeResult(NamedTuple):
    """What one ``--mode spmv`` run served: the matrix, the requests in
    submission order, their batched answers in the same order, the plan
    installed at the end, and the headline timings."""
    coo: object                     # COO the operator was built from
    xs: list
    ys: list
    plan: object                    # RealizedPlan
    t_batched: float
    t_seq: float
    flush_p50_s: Optional[float]    # serve/flush_s p50; None without
                                    #   --metrics


def _serving_pass(op, xs, args, reg=None, controller=None):
    """The flush-by-flush serving loop: per-flush wall times into the
    ``serve/flush_s`` histogram (split pre/post-migration when a
    controller runs), one :class:`~repro.obs.ResidualRecord` per flush
    pairing the measured latency with the roofline prediction of the plan
    that served it, and the migration controller's between-flush hook —
    the observed side of the selector's model AND the feedback signal the
    break-even decision consumes."""
    from repro.spmm import RequestBatcher

    batcher = RequestBatcher(op, max_batch=args.max_batch, impl=args.impl,
                             spmm_fn=lambda _m, X: op.matmul(X))
    for x in xs:
        batcher.submit(x)
    ledger = reg.ledger if reg is not None else (
        controller.ledger if controller is not None else None)
    while batcher.pending:
        rp = op.plan        # one read: the plan this flush executes on
        k = min(batcher.pending, args.max_batch)
        t0 = time.perf_counter()
        out = batcher.flush()
        jax.block_until_ready(list(out.values()))
        dt = time.perf_counter() - t0
        if reg is not None:
            reg.histogram("serve/flush_s").observe(dt)
            if controller is not None:
                phase = ("serve/flush_postmigrate_s" if controller.swapped
                         else "serve/flush_premigrate_s")
                reg.histogram(phase).observe(dt)
        if ledger is not None:
            ledger.record("serve/flush", dt, rp.model_s(k), k=k,
                          **rp.labels(matrix=args.matrix, algo=rp.label,
                                      backend=jax.default_backend()))
        if controller is not None:
            controller.note_flush(k, dt, rp)
    if controller is not None:
        controller.finish()


def _print_metrics_summary(reg):
    flush = reg.histogram("serve/flush_s")
    if flush.count:
        p = flush.percentiles()
        print(f"[serve-spmv] flush latency over {flush.count} flushes: "
              f"p50 {p['p50']*1e3:.2f} ms, p95 {p['p95']*1e3:.2f} ms, "
              f"p99 {p['p99']*1e3:.2f} ms"
              f"{' (exact)' if flush.exact else ''}")
    phases = [h for h in reg.histograms()
              if h.count and (h.name.startswith("spmm/")
                              or h.name.startswith("batcher/"))]
    for h in sorted(phases, key=lambda h: h.name):
        print(f"[serve-spmv]   phase {h.name:<24} n={h.count:<4} "
              f"mean {h.mean*1e3:8.3f} ms  p95 "
              f"{h.quantile(0.95)*1e3:8.3f} ms")
    ledger = reg.ledger
    if len(ledger):
        corr = ledger.correction()
        print(f"[serve-spmv] residual (observed/modeled) over "
              f"{len(ledger)} flushes: geomean {corr:.3g} — the factor "
              "autotune(feedback=) will apply to this config's score")


@functools.lru_cache(maxsize=1)
def _suite_coo(matrix: str, scale: float):
    """The suite matrix as a device COO. The last one is kept, so a
    process that serves one matrix in turn (``chip_smoke.py``'s row and
    merge schedules) generates and canonicalizes it once."""
    from repro.data import matrices
    return matrices.as_coo(matrices.test_suite(scale=scale)[matrix].make())


def serve_spmv(args):
    """Sparse serving demo: batched (one SpMM per flush) vs sequential,
    optionally over a --devices mesh, all through one
    :class:`repro.spmm.SparseOperator` handle. ``--migrate auto`` starts
    in the zero-conversion format and converts online once the measured
    break-even clears the remaining traffic (``force`` converts
    unconditionally, in the background either way). Headline numbers use
    the paper's §5.2 min-of-N discipline; ``--metrics`` additionally
    records phase spans, flush-latency percentiles, migration decision
    inputs and observed-vs-modeled residuals, then dumps them as one
    ``repro.obs/v1`` JSON document. Returns a :class:`SpmvServeResult`."""
    from repro import obs
    from repro.core import PlanSpec, spmv
    from repro.core.selector import ZERO_CONVERSION_ALGO
    from repro.data import matrices
    from repro.roofline import spmm_arithmetic_intensity
    from repro.spmm import RequestBatcher, SparseOperator

    if args.matrix not in matrices.test_suite():
        raise SystemExit(f"--matrix must be one of "
                         f"{sorted(matrices.test_suite())}")
    t0 = time.perf_counter()
    coo = _suite_coo(args.matrix, args.scale)
    t_matrix = time.perf_counter() - t0
    # num_spmvs counts k-RHS multiplies: batching turns `requests` SpMVs
    # into ceil(requests / max_batch) SpMM calls
    num_spmms = -(-args.requests // args.max_batch)
    mesh_shape = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_shape
        mesh_shape = parse_mesh_shape(args.mesh)
        args.devices = mesh_shape[0] * mesh_shape[1]
    if args.devices > 1:
        ndev = len(jax.devices())
        if ndev < args.devices:
            raise SystemExit(
                f"the mesh needs {args.devices} devices but jax sees only "
                f"{ndev}; on CPU set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={args.devices} before launching")
        if args.algorithm and args.algorithm != "sellcs":
            raise SystemExit(
                f"--algorithm {args.algorithm} cannot be served on a mesh: "
                "the --devices path multiplies the SELL-C-σ slice stream "
                "(repro.spmm.distributed); drop --algorithm or pass sellcs")
    if args.migrate != "off" and args.algorithm:
        raise SystemExit(
            "--algorithm pins the format, --migrate lets the break-even "
            "economics choose it; drop one of the two")

    # the target the migration converts TO (and what --migrate off serves
    # directly): SELL-C-σ over the requested mesh, with --mesh / --chunks
    # / --compact-x pinning knobs the selector would otherwise sweep
    compact = {"auto": None, "on": True, "off": False}[args.compact_x]
    gather = None if args.gather == "auto" else args.gather
    if args.devices > 1:
        target_spec = PlanSpec(
            num_devices=args.devices,
            mesh_shape=mesh_shape or (args.devices, 1),
            num_chunks=args.chunks if args.chunks > 0 else None,
            compact_x=compact, algorithm="sellcs", gather=gather,
            schedule=None if args.schedule == "auto" else args.schedule)
    else:
        target_spec = PlanSpec(num_devices=1, algorithm="sellcs")
    if args.migrate != "off":
        # zero-conversion start: merge-path CSR on one device; the
        # controller decides if/when the target plan pays for itself
        initial_spec = PlanSpec(num_devices=1,
                                algorithm=ZERO_CONVERSION_ALGO)
    elif args.devices > 1:
        initial_spec = target_spec
    else:
        initial_spec = PlanSpec(num_devices=1, algorithm=args.algorithm)

    op = SparseOperator.from_coo(coo, initial_spec, impl=args.impl,
                                 k_hint=args.max_batch,
                                 num_spmvs=num_spmms)
    t_plan = time.perf_counter() - t0 - t_matrix
    stats = op.matrix_stats
    algo = op.plan.label
    print(f"[serve-spmv] matrix={args.matrix} m={stats.m} n={stats.n} "
          f"nnz={stats.nnz} algo={algo} impl={op.plan.impl} "
          f"max_batch={args.max_batch}"
          + (f" migrate={args.migrate}" if args.migrate != "off" else ""))
    print(f"[serve-spmv] setup: matrix {t_matrix:.3f} s (generate, or "
          f"reuse the last), plan {t_plan:.3f} s (stats, convert, "
          f"partition)")

    rng = np.random.default_rng(args.seed)
    xs = [jnp.asarray(rng.standard_normal(stats.n).astype(np.float32))
          for _ in range(args.requests)]

    reg = None
    if args.metrics:
        reg = obs.install(obs.MetricRegistry(
            backend=jax.default_backend(), mode="spmv",
            matrix=args.matrix, algo=algo, devices=args.devices,
            max_batch=args.max_batch, migrate=args.migrate,
            requests=args.requests))
    controller = None
    if args.migrate != "off":
        ledger = reg.ledger if reg is not None else obs.ResidualLedger()
        controller = _MigrationController(op, stats, args, target_spec,
                                          ledger, reg=reg)

    # headline timing, the paper's §5.2 way: min over --reps runs after a
    # warmup/compile run — never a single first-flush perf_counter pair
    def batched_run():
        b = RequestBatcher(op, max_batch=args.max_batch, impl=args.impl,
                           spmm_fn=lambda _m, X: op.matmul(X))
        rids = [b.submit(x) for x in xs]
        return b.drain(), rids, b.flushes

    t_b = obs.time_min_of_n(batched_run, reps=args.reps, warmup=1)
    out, rids, num_flushes = t_b.last_result
    t_batched = t_b.best_s

    t_s = obs.time_min_of_n(
        lambda: [spmv(op.plan.local_matrix, x, impl=args.impl)
                 for x in xs],
        reps=args.reps, warmup=1)
    seq, t_seq = t_s.last_result, t_s.best_s

    for rid, y in zip(rids, seq):
        np.testing.assert_allclose(np.asarray(out[rid]), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)
    ai1 = spmm_arithmetic_intensity(stats.nnz, stats.m, stats.n, 1)
    aik = spmm_arithmetic_intensity(stats.nnz, stats.m, stats.n,
                                    args.max_batch)
    print(f"[serve-spmv] batched {t_batched*1e3:.1f} ms "
          f"({num_flushes} SpMM calls) vs sequential "
          f"{t_seq*1e3:.1f} ms ({len(xs)} SpMV calls) — "
          f"speedup {t_seq/max(t_batched, 1e-9):.2f}x "
          f"(min of {t_b.reps}, warmup {t_b.warmup})")
    print(f"[serve-spmv] modelled intensity {ai1:.3f} -> {aik:.3f} "
          f"flop/byte at k={args.max_batch}")
    _print_traffic_model(op.spec, op.plan.n_touched, stats, args)

    flush_p50 = None
    if reg is not None or controller is not None:
        # the measured side: per-flush latencies + residual ledger records
        # against the roofline prediction of the plan serving each flush,
        # and the migration controller's between-flush decision hook
        _serving_pass(op, xs, args, reg=reg, controller=controller)
    if reg is not None:
        if op.plan.eager is not None:
            # one eager pass so the spmm/* phase spans time real execution
            # (inside the jitted flush they only see tracing); op.plan is
            # the post-migration plan when a swap landed
            with obs.span("serve/eager_profile"):
                jax.block_until_ready(op.plan.eager(
                    jnp.stack([x for x in xs[:args.max_batch]], axis=1)))
        flush = reg.histogram("serve/flush_s")
        if flush.count:
            flush_p50 = flush.percentiles()["p50"]
        _print_metrics_summary(reg)
        reg.dump(args.metrics)
        print(f"[serve-spmv] metrics -> {args.metrics}")
        obs.uninstall()
    return SpmvServeResult(coo, xs, [out[rid] for rid in rids], op.plan,
                           t_batched, t_seq, flush_p50)


def _print_traffic_model(sp, n_touched, stats, args):
    """The modelled per-device traffic printout for a distributed plan
    (no-op on a single device): HBM + collective bytes per flush, the
    compact-gather saving, and the merge psum pipelining win."""
    if (sp.num_devices or 1) <= 1:
        return
    from repro.roofline import (spmm_distributed_collective_s,
                                spmm_distributed_gather_s,
                                spmm_distributed_traffic)
    sched, chunks = sp.schedule, sp.num_chunks or 1
    compact = bool(sp.compact_x)
    gx = (sp.gather or "upfront") if compact else "upfront"
    pd, pm = sp.mesh_shape
    hbm, coll = spmm_distributed_traffic(
        stats.m, stats.n, args.max_batch, pd, sched,
        nnz=stats.nnz, max_row_nnz=stats.max_row_nnz, model_devices=pm,
        compact_x=compact, n_touched=n_touched)
    print(f"[serve-spmv] modelled per-device traffic: {hbm / 1e6:.2f} MB "
          f"HBM + {coll / 1e6:.2f} MB collective per flush "
          f"(mesh=({pd},{pm}), schedule={sched}, chunks={chunks}, "
          f"compact_x={'on' if compact else 'off'}"
          + (f", gather={gx}" if compact else "") + ")")
    if compact:
        hbm_rep, _ = spmm_distributed_traffic(
            stats.m, stats.n, args.max_batch, pd, sched,
            nnz=stats.nnz, max_row_nnz=stats.max_row_nnz,
            model_devices=pm)
        print(f"[serve-spmv] compact gather: mean n_touched "
              f"{n_touched:.0f} of n={stats.n} rows per shard — "
              f"{(hbm_rep - hbm) / 1e6:.2f} MB HBM saved vs "
              "replicated X per flush")
        up, here = (spmm_distributed_gather_s(
            stats.m, stats.n, args.max_batch, pd, sched,
            nnz=stats.nnz, max_row_nnz=stats.max_row_nnz,
            num_chunks=chunks, model_devices=pm, compact_x=True,
            n_touched=n_touched, gather=g)
            for g in ("upfront", gx))
        print(f"[serve-spmv] exposed gather_s: {up * 1e6:.2f} us up-front "
              f"-> {here * 1e6:.2f} us with gather={gx}")
    if sched == "merge":
        mono, over = (spmm_distributed_collective_s(
            stats.m, stats.n, args.max_batch, pd, sched,
            nnz=stats.nnz, max_row_nnz=stats.max_row_nnz, num_chunks=c,
            model_devices=pm)
            for c in (1, chunks))
        print(f"[serve-spmv] exposed collective_s: {mono * 1e6:.2f} us "
              f"monolithic -> {over * 1e6:.2f} us with {chunks} "
              "chunk(s) pipelined under the slice stream")


def _fleet_target_spec(args, mesh_shape):
    """The same distributed-knob plumbing serve_spmv uses, shared by every
    tenant registration."""
    from repro.core import PlanSpec
    compact = {"auto": None, "on": True, "off": False}[args.compact_x]
    gather = None if args.gather == "auto" else args.gather
    if args.devices > 1:
        return PlanSpec(num_devices=args.devices,
                        mesh_shape=mesh_shape or (args.devices, 1),
                        num_chunks=args.chunks if args.chunks > 0 else None,
                        compact_x=compact, algorithm="sellcs",
                        gather=gather)
    return PlanSpec(num_devices=1, algorithm="sellcs")


def serve_fleet(args):
    """Multi-tenant fault-tolerant serving: N tenants over a
    :class:`repro.spmm.Fleet` (fingerprint-keyed plan cache — tenants
    cycle over two distinct matrices, so with >= 3 tenants at least one
    registration is a cache hit) fronted by a
    :class:`repro.spmm.FleetBatcher` whose scheduler picks each flush by
    SLO-deadline urgency × batch-efficiency. ``--fail-device`` kills one
    data-shard device mid-stream: the fleet re-deals every distributed
    tenant's width-row stream across the survivors
    (``SparseOperator.shrink_to`` → ``redeal_sellcs``) and keeps serving;
    every request queued before, during and after the loss is answered
    and checked against the COO oracle. Per-tenant flush latency lands in
    ``fleet/flush_s`` (split ``fleet/flush_preloss_s`` /
    ``fleet/flush_postloss_s`` around the loss) — the
    ``BENCH_serve_slo.json`` series ``smoke_check.check_slo`` gates."""
    from repro import obs
    from repro.data import matrices
    from repro.spmm import Fleet, FleetBatcher, spmm_coo

    if args.tenants < 1:
        raise SystemExit("--tenants must be >= 1")
    suite = matrices.test_suite(scale=args.scale)
    if args.matrix not in suite:
        raise SystemExit(f"--matrix must be one of {sorted(suite)}")
    # two distinct matrices cycled across the tenants: same-matrix tenants
    # exercise the fingerprint plan cache, the other matrix proves the
    # fleet really multiplexes independent operators
    alt = "hhh_like" if args.matrix != "hhh_like" else "road_like"
    names = [args.matrix, alt]
    mesh_shape = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_shape
        mesh_shape = parse_mesh_shape(args.mesh)
        args.devices = mesh_shape[0] * mesh_shape[1]
    if args.devices > 1 and len(jax.devices()) < args.devices:
        raise SystemExit(
            f"the mesh needs {args.devices} devices but jax sees only "
            f"{len(jax.devices())}; on CPU set XLA_FLAGS=--xla_force_"
            f"host_platform_device_count={args.devices} before launching")
    per_tenant = max(1, args.requests // args.tenants)
    fail_device = None
    if args.fail_device is not None:
        fail_device = (args.devices - 1 if args.fail_device == "auto"
                       else int(args.fail_device))
        if args.devices <= 1:
            raise SystemExit("--fail-device needs a --devices mesh")

    reg = None
    if args.metrics:
        reg = obs.install(obs.MetricRegistry(
            backend=jax.default_backend(), mode="fleet",
            matrix=args.matrix, devices=args.devices,
            max_batch=args.max_batch, tenants=args.tenants,
            slo_ms=args.slo_ms, requests=per_tenant,
            fail_device="" if fail_device is None else fail_device))

    spec = _fleet_target_spec(args, mesh_shape)
    fleet = Fleet(impl=args.impl)
    front = FleetBatcher()
    coos = {}
    for i in range(args.tenants):
        tenant = f"t{i}"
        coo = matrices.as_coo(suite[names[i % len(names)]].make())
        coos[tenant] = coo
        op = fleet.register(tenant, coo, spec, k_hint=args.max_batch,
                            num_spmvs=-(-per_tenant // args.max_batch))
        front.add_tenant(tenant, op, max_batch=args.max_batch,
                         slo_s=args.slo_ms / 1e3,
                         max_pending=args.max_pending or None,
                         overflow="block")
        print(f"[serve-fleet] {tenant}: {names[i % len(names)]} "
              f"plan={op.plan.label} builds="
              f"(sellcs={op.stats.sellcs_builds}, "
              f"partition={op.stats.partition_builds})")
    print(f"[serve-fleet] plan cache: {fleet.stats.plan_cache_hits} hits, "
          f"{fleet.stats.plan_cache_misses} misses over "
          f"{fleet.stats.registered} registrations")

    rng = np.random.default_rng(args.seed)
    sent = {}                                # (tenant, rid) -> x
    for j in range(per_tenant):
        for i in range(args.tenants):
            tenant = f"t{i}"
            x = jnp.asarray(rng.standard_normal(
                coos[tenant].shape[1]).astype(np.float32))
            rid = front.submit(tenant, x)
            sent[(tenant, rid)] = x

    total = per_tenant * args.tenants
    half = total // 2
    served = 0
    lost = False
    results = {}                             # (tenant, rid) -> y
    while front.total_pending:
        if fail_device is not None and not lost and served >= half:
            t0 = time.perf_counter()
            redone = fleet.handle_device_loss([fail_device])
            dt = time.perf_counter() - t0
            lost = True
            print(f"[serve-fleet] device {fail_device} lost after "
                  f"{served}/{total} served — re-dealt "
                  f"{len(redone)} tenant plan(s) across "
                  f"{args.devices - 1} survivors in {dt*1e3:.1f} ms")
        t0 = time.perf_counter()
        tenant, out = front.flush_next()
        if tenant is None:
            break
        jax.block_until_ready(list(out.values()))
        dt = time.perf_counter() - t0
        served += len(out)
        for rid, y in out.items():
            results[(tenant, rid)] = y
        fleet.observe_flush(tenant, dt)
        if reg is not None:
            lab = {"tenant": tenant}
            reg.histogram("fleet/flush_s", lab).observe(dt)
            phase = ("fleet/flush_postloss_s" if lost
                     else "fleet/flush_preloss_s")
            reg.histogram(phase, lab).observe(dt)

    # the no-drop + correctness contract: every queued request answered,
    # every answer equal to the COO oracle of its tenant's matrix —
    # including everything served after the device loss
    assert len(results) == total, (len(results), total)
    for (tenant, rid), x in sent.items():
        y_ref = spmm_coo(coos[tenant], x[:, None])[:, 0]
        np.testing.assert_allclose(np.asarray(results[(tenant, rid)]),
                                   np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
    print(f"[serve-fleet] {total} requests served across "
          f"{args.tenants} tenants, all oracle-checked"
          + (" (incl. post-loss traffic)" if lost else ""))

    for i in range(args.tenants):
        tenant = f"t{i}"
        lane = front.lane(tenant)
        line = (f"[serve-fleet] {tenant}: served={lane.served} "
                f"flushes={lane.flushes} "
                f"slo_violations={lane.slo_violations}")
        if reg is not None:
            h = reg.histogram("fleet/flush_s", {"tenant": tenant})
            if h.count:
                p = h.percentiles()
                line += (f" | flush p50 {p['p50']*1e3:.2f} ms "
                         f"p95 {p['p95']*1e3:.2f} ms "
                         f"p99 {p['p99']*1e3:.2f} ms")
        print(line)
    if reg is not None:
        reg.dump(args.metrics)
        print(f"[serve-fleet] metrics -> {args.metrics}")
        obs.uninstall()
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "spmv", "fleet"), default="lm")
    ap.add_argument("--arch")
    # spmv-mode arguments (repro.spmm request batching)
    ap.add_argument("--matrix", default="mawi_like")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--algorithm", default=None,
                    help="force a format (default: core.select with k)")
    ap.add_argument("--devices", type=int, default=1,
                    help="serve each flush with a distributed SpMM over a "
                         "1-D data mesh of this many devices (schedule "
                         "chosen by core.select_distributed)")
    ap.add_argument("--mesh", default=None, metavar="Pd,Pm",
                    help="pin a 2-D (data, model) mesh factorization for "
                         "the distributed SpMM, e.g. 4,2 — the model axis "
                         "column-shards the X/Y k-slabs so per-device psum "
                         "and replicated-X bytes drop by Pm (overrides "
                         "--devices with Pd*Pm)")
    ap.add_argument("--schedule", default="auto",
                    choices=("auto", "row", "merge"),
                    help="pin the distributed schedule: row bands or "
                         "merge spans (auto = core.select_distributed)")
    ap.add_argument("--chunks", type=int, default=0,
                    help="pipeline the merge-schedule psum into this many "
                         "chunks (0 = pick by the roofline overlap model; "
                         "ignored by the row schedule)")
    ap.add_argument("--compact-x", default="auto",
                    choices=("auto", "on", "off"), dest="compact_x",
                    help="sparsity-aware X gather for the distributed SpMM:"
                         " partition with per-shard column compaction so "
                         "each data shard gathers only the X rows its "
                         "nonzeros touch (auto = let the traffic model "
                         "decide when the gather beats replication)")
    ap.add_argument("--gather", default="auto",
                    choices=("auto", "upfront", "overlap"),
                    help="compact-X gather schedule: materialize the slab "
                         "up-front ahead of the mesh region, or hide "
                         "per-span rebuilds under the chunked merge span "
                         "loop (overlap); auto = let the "
                         "exposed-gather-seconds roofline term pick")
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "ref", "pallas", "pallas_interpret"))
    ap.add_argument("--migrate", default="off",
                    choices=("auto", "off", "force"),
                    help="online break-even format migration: start in the "
                         "zero-conversion merge-path format, count served "
                         "multiplies, and convert to the SELL-C-σ target "
                         "plan in a background thread once the measured "
                         "convert-cost / per-multiply-saving ratio clears "
                         "the projected remaining traffic (auto), "
                         "unconditionally (force), or never (off)")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="install a repro.obs registry for the run and dump "
                         "it here: phase spans, p50/p95/p99 flush latency, "
                         "and observed-vs-modeled residuals (repro.obs/v1)")
    ap.add_argument("--reps", type=int, default=5,
                    help="min-of-N repetitions for the headline batched-vs-"
                         "sequential timing (the paper's §5.2 protocol)")
    # fleet-mode arguments (multi-tenant serving with device-loss re-deal)
    ap.add_argument("--tenants", type=int, default=3,
                    help="fleet mode: number of tenants; they cycle over "
                         "two distinct matrices so >= 3 tenants exercise "
                         "the fingerprint plan cache")
    ap.add_argument("--slo-ms", type=float, default=50.0, dest="slo_ms",
                    help="fleet mode: per-request latency budget driving "
                         "the cross-tenant flush scheduler (urgency = "
                         "oldest queue wait / budget)")
    ap.add_argument("--fail-device", default=None, dest="fail_device",
                    help="fleet mode: kill this device index midway "
                         "through the stream ('auto' = the last mesh "
                         "device) and re-deal its spans across survivors")
    ap.add_argument("--max-pending", type=int, default=0,
                    dest="max_pending",
                    help="fleet mode: per-tenant queue bound (0 = "
                         "unbounded); submits past it block until a flush "
                         "makes room")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if argv is None:
        # the command line; a library caller (a test, chip_smoke.py)
        # chooses its own cache
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()

    if args.mode == "spmv":
        return serve_spmv(args)
    if args.mode == "fleet":
        return serve_fleet(args)
    if not args.arch:
        ap.error("--arch is required in lm mode")

    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    B, P, G = args.batch, args.prompt_len, args.gen
    S_max = P + G + (cfg.vision_tokens if cfg.frontend == "vision" else 0)
    rng = jax.random.PRNGKey(args.seed + 1)
    prompts = jax.random.randint(rng, (B, P), 0, cfg.vocab)
    vis = None
    if cfg.frontend == "vision":
        vis = jax.random.normal(rng, (B, cfg.vision_tokens, cfg.vision_dim))

    prefill_fn = jax.jit(lambda p, t, v: prefill(
        p, cfg, t, S_max, cache_dtype=jnp.float32, vision_embeds=v))
    decode_fn = jax.jit(lambda p, tok, c, pos: decode_step(
        p, cfg, tok, c, pos))

    t0 = time.perf_counter()
    logits, caches = prefill_fn(params, prompts, vis)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    offset = cfg.vision_tokens if cfg.frontend == "vision" else 0
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(G - 1):
        pos = jnp.full((B,), offset + P + i, jnp.int32)
        logits, caches = decode_fn(params, tok, caches, pos)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    tok.block_until_ready()
    t_decode = time.perf_counter() - t0

    gen = np.asarray(jnp.concatenate(out_tokens, axis=1))
    tps = B * (G - 1) / max(t_decode, 1e-9)
    print(f"[serve] arch={cfg.name} batch={B} prompt={P} gen={G}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; decode "
          f"{t_decode*1e3:.1f} ms ({tps:.1f} tok/s incl. compile)")
    print(f"[serve] sample generations (first 2 rows): {gen[:2].tolist()}")
    assert np.all(gen >= 0) and np.all(gen < cfg.vocab)
    return gen


if __name__ == "__main__":
    main()
