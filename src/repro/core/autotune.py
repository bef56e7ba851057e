"""Automatic parameter tuning — the paper's §8 future work, implemented.

"We suspect that these optimizations can still provide a relevant speedup,
 but they will be largely machine-specific ... it would be interesting to
 look into automatically tuning these parameters, like performed in the
 pOSKI library." (paper, §8)

The tuner sweeps (algorithm, block size beta) over a measurement budget,
scoring each candidate with the paper's own economics: total cost =
conversion + num_spmvs × per-multiply, where per-multiply is either
measured (jitted XLA wall time on this backend) or modelled (the TPU
tile-stream roofline from benchmarks.spmv_tables) — pOSKI-style hybrid
offline/online tuning.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .convert import ALGORITHM_SPECS, block_size_for, convert
from .formats import COO
from .spmv import spmv

DEFAULT_ALGOS = ("parcrs", "csb", "csbh", "bcohc", "bcohch", "mergeb")


@dataclasses.dataclass(frozen=True)
class TuneResult:
    algorithm: str
    beta: Optional[int]
    convert_s: float
    spmv_s: float                # per-multiply (one SpMM when k > 1),
                                 #   measured on ONE device
    total_s: float               # convert + num_spmvs * spmv (modelled
                                 #   distributed per-multiply when
                                 #   num_devices > 1)
    tpu_model_s: Optional[float] = None
    k: int = 1                   # right-hand sides per multiply
    k_tile: Optional[int] = None  # roofline-chosen column block (k > 1)
    num_devices: int = 1          # mesh size the score targets
    schedule: Optional[str] = None  # "row" | "merge" (num_devices > 1)
    dist_model_s: Optional[float] = None  # modelled distributed multiply
    num_chunks: Optional[int] = None  # psum pipelining depth ("merge";
                                      #   1 = monolithic fixup)
    mesh_shape: Optional[Tuple[int, int]] = None
                                      # (P_data, P_model) factorization the
                                      #   distributed score picked
    compact_x: Optional[bool] = None  # sparsity-aware X gather picked by
                                      #   the distributed score (sellcs
                                      #   only; None off the mesh)
    structure: Optional[str] = None   # "symmetric" when one-triangle
                                      #   storage won the distributed score
                                      #   (sellcs on A == A^T only)
    gather: Optional[str] = None      # compact-X gather schedule the
                                      #   distributed score picked
                                      #   ("upfront"|"overlap";
                                      #   None off the mesh)
    residual: Optional[float] = None  # observed/modeled correction the
                                      #   feedback ledger applied to this
                                      #   result's winning distributed
                                      #   score (None: no feedback, or no
                                      #   matching measurement yet)


def _measure(fn: Callable, reps: int = 5, warmup: int = 2) -> float:
    """One timing protocol for the whole repo: ``obs.timing.time_min_of_n``
    (the paper's §5.2 min-of-N discipline) — autotune measurements stamp
    the same reps/warmup semantics as the harness and serve headlines."""
    from repro.obs.timing import time_min_of_n
    return time_min_of_n(fn, reps=reps, warmup=warmup).best_s


def autotune(coo: COO, *, num_spmvs: int = 100,
             algorithms: Tuple[str, ...] = DEFAULT_ALGOS,
             betas: Optional[List[int]] = None,
             reps: int = 5, tpu_model: bool = False, k: int = 1,
             num_devices: int = 1, feedback=None, spec=None
             ) -> Tuple[TuneResult, List[TuneResult]]:
    """Return (best, all_results) over the candidate grid.

    ``k > 1`` tunes the SpMM engine instead: each measured multiply is one
    ``A @ X`` with ``X: [n, k]`` (via ``repro.spmm``), ``algorithms`` may
    include ``"sellcs"``, and every result records the roofline-chosen
    ``k_tile``. ``k = 1`` is byte-for-byte the original SpMV tuner.

    ``num_devices > 1`` scores the (format × schedule × k) grid jointly,
    pOSKI-style hybrid: the per-multiply time is still *measured* on this
    one device, then scaled by the ``repro.roofline`` distributed traffic
    model (replicated-X bytes, dense-row imbalance for "row", psum bytes
    for "merge") — the tuner cannot run the mesh it is tuning for, but the
    model ratio carries the measured stream rate across. Each result then
    records the winning cross-device ``schedule`` and the modelled
    distributed per-multiply seconds in ``dist_model_s``.

    ``feedback`` closes the loop: pass a ``repro.obs.ResidualLedger``
    (e.g. loaded from a ``serve --metrics`` run) and every distributed
    grid candidate's modelled seconds are multiplied by
    ``feedback.correction(**choice_labels(schedule, num_chunks,
    mesh_shape, compact_x))`` — the geometric-mean observed/modeled
    residual of matching measurements — before the grid min is taken, so
    a config the model flatters gets re-ranked by what the machine
    actually did. The applied factor is recorded in
    ``TuneResult.residual`` (None where no measurement matched).

    ``spec`` (a :class:`repro.core.PlanSpec`) carries the distributed pins
    in one object: its ``num_devices`` replaces the kwarg and its
    ``mesh_shape`` / ``num_chunks`` / ``schedule`` / ``compact_x`` fields
    restrict the rescoring grid — the old kwargs stay as shims."""
    if spec is not None:
        spec = spec.canonical()
        num_devices = spec.num_devices
    rng = np.random.default_rng(0)
    if k > 1:
        from repro.spmm import choose_k_tile, spmm
        x = jnp.asarray(rng.standard_normal(
            (coo.shape[1], k)).astype(np.float32))
        k_tile = choose_k_tile(k)

        def measure(mat):
            return _measure(lambda: spmm(mat, x, impl="ref"), reps)
    else:
        x = jnp.asarray(rng.standard_normal(
            coo.shape[1]).astype(np.float32))
        k_tile = None

        def measure(mat):
            return _measure(lambda: spmv(mat, x, impl="ref"), reps)

    results: List[TuneResult] = []
    for algo in algorithms:
        aspec = ALGORITHM_SPECS[algo]
        if not aspec.blocked:
            t0 = time.perf_counter()
            mat = convert(coo, algo)
            conv_s = time.perf_counter() - t0
            spmv_s = measure(mat)
            results.append(TuneResult(algo, None, conv_s, spmv_s,
                                      conv_s + num_spmvs * spmv_s,
                                      k=k, k_tile=k_tile))
            continue
        base = block_size_for(coo.shape,
                              in_block_format=aspec.in_block_format)
        cand = betas or sorted({max(base // 4, 16), max(base // 2, 16),
                                base, min(base * 2, 1 << 16)})
        for beta in cand:
            kw = dict(beta=beta)
            if aspec.scheduling == "static_rows":
                kw["num_bands"] = 8
            t0 = time.perf_counter()
            mat = convert(coo, algo, **kw)
            conv_s = time.perf_counter() - t0
            spmv_s = measure(mat)
            model_s = None
            # the TPU tile-stream model prices a single-vector SpMV; at
            # k > 1 the measurement is one k-RHS SpMM — different units, so
            # the model is only recorded for the SpMV case.
            if tpu_model and k == 1:
                from repro.kernels.tiling import coo_to_tiled
                from benchmarks.spmv_tables import tpu_model_time
                try:
                    model_s = tpu_model_time(
                        coo_to_tiled(coo, algo, beta=max(beta, 128)))
                except MemoryError:
                    model_s = float("inf")
            results.append(TuneResult(algo, beta, conv_s, spmv_s,
                                      conv_s + num_spmvs * spmv_s,
                                      model_s, k=k, k_tile=k_tile))
    if num_devices > 1:
        from .selector import matrix_stats
        stats = matrix_stats(coo)       # one O(nnz) pass for all results
        results = [_rescore_distributed(r, stats, k, num_devices, num_spmvs,
                                        feedback=feedback, spec=spec)
                   for r in results]
    best = min(results, key=lambda r: r.total_s)
    return best, results


def _rescore_distributed(r: TuneResult, stats, k: int, num_devices: int,
                         num_spmvs: int, feedback=None,
                         spec=None) -> TuneResult:
    """Scale a measured single-device result across the mesh with the
    roofline traffic model and pick the best (schedule, mesh shape,
    num_chunks, compact_x) for it — "merge" sweeps the psum pipelining
    depths, "row" has no collective to chunk, both sweep every
    (P_data, P_model) factorization of the mesh, and the SELL-C-σ format
    additionally scores the sparsity-aware X gather (compact=False is
    scored first, so a dense-columns tie refuses compaction).

    With ``feedback`` (a ``repro.obs.ResidualLedger``), each candidate's
    modelled seconds are multiplied by the ledger's geometric-mean
    observed/modeled residual for that candidate's labels before the min
    — measured reality outvotes the streaming-bytes story wherever a
    measurement exists. The winning candidate's correction lands in
    ``TuneResult.residual``."""
    from repro.roofline.analysis import spmm_distributed_time
    from .selector import (GATHER_CANDIDATES, _matrix_bytes_est,
                           distributed_schedule_grid)
    mat_bytes = _matrix_bytes_est(r.algorithm, stats)
    base_s = spmm_distributed_time(stats.m, stats.n, k, 1, "row",
                                   matrix_bytes=mat_bytes)
    grid = distributed_schedule_grid(num_devices, spec=spec)
    compacts = (False, True) if r.algorithm == "sellcs" else (False,)
    if spec is not None and spec.compact_x is not None:
        compacts = ((spec.compact_x,) if r.algorithm == "sellcs"
                    else (False,))
    # one-triangle storage: executable on sellcs, convertible only when
    # A == A^T; "general" scored first so symmetry must strictly win
    structures = ("general",)
    if r.algorithm == "sellcs" and getattr(stats, "symmetric", False):
        structures = ("general", "symmetric")
    if spec is not None and spec.structure is not None:
        structures = ((spec.structure,) if r.algorithm == "sellcs"
                      else ("general",))

    def gathers_for(cf):
        # the gather schedule only exists on the compact SELL-C-σ path;
        # "upfront" first so min()'s first-wins tie-break refuses hiding
        # that buys nothing
        if not (cf and r.algorithm == "sellcs"):
            return ("upfront",)
        if spec is not None and spec.gather is not None:
            return (spec.gather,)
        return GATHER_CANDIDATES

    def corrected(s, nc, mesh, cf, st, gm):
        model_s = spmm_distributed_time(
            stats.m, stats.n, k, mesh[0], s, matrix_bytes=mat_bytes,
            max_row_nnz=stats.max_row_nnz, num_chunks=nc,
            model_devices=mesh[1], compact_x=cf, nnz=stats.nnz,
            structure=st, gather=gm)
        corr = 1.0
        if feedback is not None:
            from repro.obs import choice_labels
            corr = feedback.correction(**choice_labels(
                schedule=s, num_chunks=nc, mesh_shape=mesh, compact_x=cf,
                structure=st, gather=gm))
        return model_s * corr, corr

    ((schedule, num_chunks, mesh_shape, compact, structure, gmode),
     (model_s, corr)) = min(
        (((s, nc, mesh, cf, st, gm), corrected(s, nc, mesh, cf, st, gm))
         for s, nc, mesh in grid for cf in compacts for st in structures
         for gm in gathers_for(cf)),
        key=lambda t: t[1][0])
    per_multiply = r.spmv_s * (model_s / max(base_s, 1e-30))
    return dataclasses.replace(
        r, total_s=r.convert_s + num_spmvs * per_multiply,
        num_devices=num_devices, schedule=schedule, dist_model_s=model_s,
        num_chunks=num_chunks, mesh_shape=mesh_shape, compact_x=compact,
        structure=structure, gather=gmode if compact else None,
        residual=corr if feedback is not None and corr != 1.0 else None)
