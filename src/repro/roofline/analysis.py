"""Roofline terms from compiled dry-run artifacts (TPU v5e targets).

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

cost_analysis() of the SPMD-partitioned executable reports *per-device*
FLOPs/bytes, so the per-chip terms divide by one chip's peaks directly.
collective_bytes is parsed from the post-optimization HLO text: we sum the
output bytes of every collective op (all-reduce counted twice — ring
all-reduce moves 2(g-1)/g x size; the (g-1)/g ≈ 1 approximation is applied
to every op kind)."""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

# TPU v5e hardware constants (per chip / per link)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64"
                       r"|u64|f64|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: {'bytes': Σ output bytes, 'count': n}.
    Works on post-optimization HLO (sync or -start async forms)."""
    out = {k: {"bytes": 0.0, "count": 0} for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        for kind in COLLECTIVE_OPS:
            # match "<op>(" or "<op>-start(" as the instruction name
            if f" {kind}(" in line or f" {kind}-start(" in line:
                lhs = line.split("=", 1)[1]
                op_pos = lhs.find(kind)
                shapes = _SHAPE_RE.findall(lhs[:op_pos])
                nbytes = sum(_shape_bytes(d, s) for d, s in shapes)
                out[kind]["bytes"] += nbytes
                out[kind]["count"] += 1
                break
    return out


def collective_bytes_total(parsed: Dict[str, Dict[str, float]]) -> float:
    total = 0.0
    for kind, rec in parsed.items():
        mult = 2.0 if kind == "all-reduce" else 1.0
        total += mult * rec["bytes"]
    return total


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    chips: int
    model_flops: float = 0.0          # analytic 6*N_active*D (global)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / ICI_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline lower bound on step time = max of the three terms
        (perfect overlap assumption)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips): how much compiled compute is
        'useful' (catches remat/redundancy waste)."""
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU at the roofline bound: useful FLOPs / (chips x
        peak x step_time)."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS_BF16 * t)

    def to_dict(self) -> Dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


# --------------------------------------------------------------------------
# SpMM (multi-RHS) roofline terms — used by repro.spmm to pick the k-tile
# and by benchmarks/spmm_sweep.py to print prediction next to measurement.
# --------------------------------------------------------------------------
def ridge_intensity(peak_flops: float = PEAK_FLOPS_BF16,
                    hbm_bw: float = HBM_BW) -> float:
    """FLOP/byte at the roofline ridge: intensity beyond this is
    compute-bound and more RHS reuse buys nothing."""
    return peak_flops / hbm_bw


def csr_stream_bytes(nnz: int, m: int, dtype_bytes: int = 4) -> int:
    """Ideal CSR matrix-stream footprint of one multiply: values + column
    indices + row pointer. The single source of truth for the traffic model
    (shared by the selector's k-scaling and the sweep)."""
    return nnz * (4 + dtype_bytes) + 4 * (m + 1)


def spmm_arithmetic_intensity(nnz: int, m: int, n: int, k: int,
                              matrix_bytes: Optional[int] = None,
                              dtype_bytes: int = 4) -> float:
    """Modelled FLOP/byte of one SpMM with k right-hand sides: every
    streamed matrix byte is reused across k columns, so intensity grows
    monotonically in k toward 2*nnz/(m+n)/dtype_bytes. ``matrix_bytes``
    defaults to the ideal CSR footprint."""
    if matrix_bytes is None:
        matrix_bytes = csr_stream_bytes(nnz, m, dtype_bytes)
    flops = 2.0 * nnz * k
    traffic = matrix_bytes + k * (m + n) * dtype_bytes
    return flops / max(traffic, 1)


def spmm_roofline_gflops(ai: float, peak_flops: float = PEAK_FLOPS_BF16,
                         hbm_bw: float = HBM_BW) -> float:
    """Attainable GFLOP/s at arithmetic intensity ``ai``."""
    return min(peak_flops, ai * hbm_bw) / 1e9


# --------------------------------------------------------------------------
# Distributed SpMM traffic model — used by core.selector.select_distributed
# and core.autotune(num_devices=) to score (format x schedule x k) jointly.
# --------------------------------------------------------------------------
def spmm_touched_fraction(n: int, nnz: int, num_devices: int = 1) -> float:
    """Modelled fraction of the ``n`` X rows one *data* shard's compacted
    gather reads: a shard holding ``nnz / P`` nonzeros touches at most that
    many distinct columns (and never more than ``n``) — the exactly
    nnz-proportional bound the ``compact_x`` traffic term prices when no
    measured per-shard ``n_touched`` is supplied."""
    if n <= 0:
        return 0.0
    P = max(int(num_devices), 1)
    return min(float(nnz) / P, float(n)) / float(n)


def spmm_distributed_traffic(m: int, n: int, k: int, num_devices: int,
                             schedule: str,
                             matrix_bytes: Optional[float] = None,
                             nnz: int = 0, dtype_bytes: int = 4,
                             max_row_nnz: int = 0, model_devices: int = 1,
                             compact_x: bool = False,
                             n_touched: Optional[float] = None,
                             op: str = "N",
                             structure: str = "general"
                             ) -> Tuple[float, float]:
    """(per-device HBM bytes, per-device collective bytes) of one k-RHS
    distributed SpMM under the two paper schedules.

    * ``"row"`` (BCOH banding): the slowest shard streams
      max(matrix_bytes/P, the dense-row footprint) — static banding never
      splits a row, so one mawi-style row lower-bounds the critical shard.
      X is fully replicated (every device reads all n*k X bytes per
      multiply — the paper's interleaved allocation priced honestly), Y is
      written shard-locally (~m/P rows). Zero collective bytes.

    * ``"merge"`` (equal-nnz spans): perfect nnz balance (matrix_bytes/P
      even with a dense row), but every device writes a full [m, k] partial
      and the carry-out fixup is an all-reduce on Y — 2*(P-1)/P*m*k bytes
      on the ring, ≈ 2*m*k (the same approximation ``collective_bytes_total``
      applies to compiled HLO). The bytes price the TRUE k: the kernel
      slices the k-tile padding (kp - k columns) off before the collective,
      so model and wire agree. Chunking the fixup does not change the bytes
      — only when they are paid; see ``spmm_distributed_collective_s``.

    ``num_devices`` counts the DATA mesh axis. ``model_devices > 1`` prices
    the 2-D (data, model) mesh of ``repro.spmm.distributed``: the X/Y
    k-slabs are column-sharded across ``model``, so every k-proportional
    term — the replicated-X read, the Y write, and the merge psum — divides
    by ``P_model`` exactly, while the matrix stream (replicated along
    ``model``) and the dense-row floor do not. Total devices are
    ``num_devices * model_devices``. The bytes price the ideal
    ``k / P_model`` column share; the executable's k_tile-aligned column
    split can ship up to ``k_tile * P_model`` extra padding columns —
    negligible at the k ≫ 128 sizes the model axis exists for.

    ``compact_x=True`` prices the sparsity-aware gather of
    ``repro.spmm.distributed``: each data shard reads only the X rows its
    nonzeros name, so the X term becomes ``min(n_touched, n) * kc``
    bytes — exactly nnz-proportional via :func:`spmm_touched_fraction`
    when no measured per-shard mean ``n_touched`` is supplied, and never
    above the replicated figure (near-dense columns cap at ``n``, where
    the gather is a wash and the selector keeps replication). The int32
    map read and the convert-time relabel are priced by
    ``ShardedSellCS.storage_bytes``, not per multiply — like the k-tile
    padding, they are below the model's resolution.

    ``op='T'`` prices ``Y = A^T X`` over the same stored stream: X is read
    in slot space (a dense ``m * kc`` read — the σ-permutation gather was
    paid when X entered slot order, and ``compact_x`` cannot shrink it),
    every data shard scatters a full ``[n, kc]`` column partial, and BOTH
    schedules pay a carry-out collective on it — column ownership is never
    banded, so the transpose adds ``2 * n * kc`` all-reduce bytes even to
    "row" (whose normal fixup is free). Under ``compact_x`` the partial
    lives in the shard's touched-column space instead: ``n`` shrinks to the
    touched count in the Y and wire terms (the stacked per-shard outputs
    are gathered and scatter-added once, not all-reduced).

    ``structure='symmetric'`` prices one-triangle storage (``m == n``
    required): the streamed matrix halves (plus a dense ``m`` diagonal) and
    the multiply pays the collectives of BOTH passes — the stored triangle
    must be carried out in row space (the N fixup) and column space (the T
    scatter fixup). The HBM vector terms are priced once: the model prices
    the fused one-pass ideal (each stored byte emits both contributions);
    the executable two-pass combine re-reads X — a gap the residual ledger
    measures rather than the model hiding the halved stream. ``op`` is
    moot under symmetry (``A^T == A``).

    ``num_devices == 1`` degrades to the single-device stream for both
    (per model shard when ``model_devices > 1``: full matrix stream, a
    ``k / P_model`` column slab, no collective — the psum axis is trivial).
    """
    if schedule not in ("row", "merge"):
        raise ValueError(f"schedule must be 'row' or 'merge', got "
                         f"{schedule!r}")
    if op not in ("N", "T"):
        raise ValueError(f"op must be 'N' or 'T', got {op!r}")
    if structure not in ("general", "symmetric"):
        raise ValueError(f"structure must be 'general' or 'symmetric', "
                         f"got {structure!r}")
    if matrix_bytes is None:
        matrix_bytes = float(csr_stream_bytes(nnz, m, dtype_bytes))
    if structure == "symmetric":
        if m != n:
            raise ValueError(f"structure='symmetric' needs a square "
                             f"matrix, got {m}x{n}")
        half = 0.5 * float(matrix_bytes) + float(m) * dtype_bytes
        hbm, coll_n = spmm_distributed_traffic(
            m, n, k, num_devices, schedule, matrix_bytes=half, nnz=nnz,
            dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
            model_devices=model_devices, compact_x=compact_x,
            n_touched=n_touched, op="N")
        _, coll_t = spmm_distributed_traffic(
            m, n, k, num_devices, schedule, matrix_bytes=half, nnz=nnz,
            dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
            model_devices=model_devices, compact_x=compact_x,
            n_touched=n_touched, op="T")
        return hbm, coll_n + coll_t
    P = max(int(num_devices), 1)
    Pm = max(int(model_devices), 1)
    kc = float(k) / Pm                   # X/Y columns owned per model shard
    if op == "T":
        x_bytes = float(m) * kc * dtype_bytes      # dense slot-space read
        if P == 1:
            return (matrix_bytes + x_bytes
                    + float(n) * kc * dtype_bytes), 0.0
        stream = matrix_bytes / P
        if schedule == "row":
            # banding splits the stream but not column ownership; the
            # dense-row floor still binds the critical shard's stream
            stream = max(stream, float(max_row_nnz) * (4 + dtype_bytes))
        if compact_x:
            nt = (min(float(n_touched), float(n)) if n_touched is not None
                  else spmm_touched_fraction(n, nnz, P) * float(n))
            # touched-space partial, gathered + scatter-added once
            return stream + x_bytes + nt * kc * dtype_bytes, \
                nt * kc * dtype_bytes
        y_bytes = float(n) * kc * dtype_bytes      # full column partial
        return stream + x_bytes + y_bytes, 2.0 * float(n) * kc * dtype_bytes
    if compact_x:
        nt = (min(float(n_touched), float(n)) if n_touched is not None
              else spmm_touched_fraction(n, nnz, P) * float(n))
        x_bytes = nt * kc * dtype_bytes
    else:
        x_bytes = float(n) * kc * dtype_bytes
    if P == 1:
        return matrix_bytes + x_bytes + float(m) * kc * dtype_bytes, 0.0
    if schedule == "row":
        stream = max(matrix_bytes / P,
                     float(max_row_nnz) * (4 + dtype_bytes))
        y_bytes = (float(m) / P) * kc * dtype_bytes
        return stream + x_bytes + y_bytes, 0.0
    stream = matrix_bytes / P
    y_bytes = float(m) * kc * dtype_bytes         # full partial per device
    psum_bytes = 2.0 * float(m) * kc * dtype_bytes
    return stream + x_bytes + y_bytes, psum_bytes


# Fixed cost of issuing one collective (launch + ring sync). Keeps the
# chunked model honest: more chunks shrink the exposed wire time but pay
# this per psum, so the modelled optimum is interior, not "always max".
COLLECTIVE_LAUNCH_S = 1e-6


def spmm_distributed_collective_s(m: int, n: int, k: int, num_devices: int,
                                  schedule: str,
                                  matrix_bytes: Optional[float] = None,
                                  nnz: int = 0, dtype_bytes: int = 4,
                                  max_row_nnz: int = 0, num_chunks: int = 1,
                                  hbm_bw: float = HBM_BW,
                                  link_bw: float = ICI_LINK_BW,
                                  model_devices: int = 1,
                                  compact_x: bool = False,
                                  n_touched: Optional[float] = None,
                                  op: str = "N",
                                  structure: str = "general") -> float:
    """EXPOSED collective seconds of one distributed multiply — the part of
    the wire time that does not hide under the slice stream.

    Monolithic (``num_chunks = 1``): the whole all-reduce serializes after
    all local compute, so everything is exposed (plus one launch).

    Chunked (``num_chunks = c``): the slice stream is split into c spans
    and each span's psum is issued while the next span computes — the
    standard communication/computation overlap of distributed-memory SpMV
    (Eckstein & Mátyásfalvi, arXiv:1812.00904). Per-chunk wire time
    ``tl = coll_s/c + launch`` overlaps per-chunk compute ``tc = hbm_s/c``;
    the pipeline exposes ``(c-1) * max(0, tl - tc) + tl``: the last chunk's
    collective always drains after the stream ends, earlier chunks only
    leak what compute cannot cover.
    """
    if num_chunks < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    hbm, coll = spmm_distributed_traffic(
        m, n, k, num_devices, schedule, matrix_bytes=matrix_bytes, nnz=nnz,
        dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
        model_devices=model_devices, compact_x=compact_x,
        n_touched=n_touched, op=op, structure=structure)
    if coll <= 0.0:
        return 0.0                    # "row" / single device: no wire time
    c = int(num_chunks)
    tl = coll / link_bw / c + COLLECTIVE_LAUNCH_S
    tc = (hbm / hbm_bw) / c
    return (c - 1) * max(0.0, tl - tc) + tl


def spmm_distributed_gather_s(m: int, n: int, k: int, num_devices: int,
                              schedule: str,
                              matrix_bytes: Optional[float] = None,
                              nnz: int = 0, dtype_bytes: int = 4,
                              max_row_nnz: int = 0, num_chunks: int = 1,
                              hbm_bw: float = HBM_BW,
                              model_devices: int = 1,
                              compact_x: bool = False,
                              n_touched: Optional[float] = None,
                              op: str = "N",
                              structure: str = "general",
                              gather: str = "upfront") -> float:
    """EXPOSED gather seconds of one distributed multiply — the serialized
    latency of building the compact-X ``[n_touched, kc]`` slab that does
    not hide under the slice stream.

    The slab build reads the touched X rows and writes them back
    (``t_g = 2 * n_touched * kc * dtype_bytes / hbm_bw``); how much of it
    lands on the critical path depends on the schedule:

    * ``"upfront"``: one monolithic ``x_pad[col_map]`` ahead of the mesh
      region — fully exposed before the first kernel launch.
    * ``"overlap"`` (chunked merge only): each span rebuilds its own piece
      of the slab inside the span loop, so span i+1's gather hides under
      span i's kernel — exposed is span 0's share plus whatever per-span
      compute cannot cover: ``t_g/c + (c-1) * max(0, t_g/c - tc)`` with
      ``tc = (hbm_s)/c``, mirroring the psum pipeline model of
      :func:`spmm_distributed_collective_s`. Where the executable
      degenerates to up-front (row schedule, ``num_chunks == 1``), so does
      the price.

    Zero when the partition is not compact or ``op='T'`` (the transpose
    path has no X gather: X enters slot-permuted). By construction
    ``overlap <= upfront`` for any inputs, so a strict-< selector keeps
    ``upfront`` on ties.
    """
    if gather not in ("upfront", "overlap"):
        raise ValueError(f"gather must be 'upfront' or 'overlap', "
                         f"got {gather!r}")
    if not compact_x or op == "T":
        return 0.0
    P = max(int(num_devices), 1)
    Pm = max(int(model_devices), 1)
    kc = float(k) / Pm
    nt = (min(float(n_touched), float(n)) if n_touched is not None
          else spmm_touched_fraction(n, nnz, P) * float(n))
    t_g = 2.0 * nt * kc * dtype_bytes / hbm_bw
    c = int(num_chunks)
    if gather == "overlap" and schedule == "merge" and c > 1:
        hbm, _ = spmm_distributed_traffic(
            m, n, k, num_devices, schedule, matrix_bytes=matrix_bytes,
            nnz=nnz, dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
            model_devices=model_devices, compact_x=compact_x,
            n_touched=n_touched, op=op, structure=structure)
        tc = (hbm / hbm_bw) / c
        return t_g / c + (c - 1) * max(0.0, t_g / c - tc)
    return t_g


def spmm_distributed_time(m: int, n: int, k: int, num_devices: int,
                          schedule: str,
                          matrix_bytes: Optional[float] = None,
                          nnz: int = 0, dtype_bytes: int = 4,
                          max_row_nnz: int = 0, num_chunks: int = 1,
                          hbm_bw: float = HBM_BW,
                          link_bw: float = ICI_LINK_BW,
                          model_devices: int = 1,
                          compact_x: bool = False,
                          n_touched: Optional[float] = None,
                          op: str = "N",
                          structure: str = "general",
                          gather: str = "upfront") -> float:
    """Modelled seconds per distributed multiply: HBM term + the *exposed*
    collective term + the *exposed* gather term. ``num_chunks = 1`` keeps
    the PR-2 no-overlap model (both terms on the Y critical path, plus one
    launch); ``num_chunks > 1`` prices the pipelined fixup of
    ``spmm_merge_distributed(num_chunks=)``; ``model_devices > 1`` prices
    the 2-D (data, model) mesh (k-proportional terms divide by
    ``P_model``); ``compact_x=True`` prices the sparsity-aware X gather
    (the X term becomes nnz-proportional — ``n_touched`` supplies a
    measured per-shard mean) with ``gather=`` scheduling its exposed
    latency (see :func:`spmm_distributed_gather_s`); ``op='T'`` prices the
    transpose scatter fixup; ``structure='symmetric'`` the one-triangle
    stream (see :func:`spmm_distributed_traffic`)."""
    hbm, _ = spmm_distributed_traffic(
        m, n, k, num_devices, schedule, matrix_bytes=matrix_bytes, nnz=nnz,
        dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
        model_devices=model_devices, compact_x=compact_x,
        n_touched=n_touched, op=op, structure=structure)
    return hbm / hbm_bw + spmm_distributed_collective_s(
        m, n, k, num_devices, schedule, matrix_bytes=matrix_bytes, nnz=nnz,
        dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
        num_chunks=num_chunks, hbm_bw=hbm_bw, link_bw=link_bw,
        model_devices=model_devices, compact_x=compact_x,
        n_touched=n_touched, op=op, structure=structure
    ) + spmm_distributed_gather_s(
        m, n, k, num_devices, schedule, matrix_bytes=matrix_bytes, nnz=nnz,
        dtype_bytes=dtype_bytes, max_row_nnz=max_row_nnz,
        num_chunks=num_chunks, hbm_bw=hbm_bw,
        model_devices=model_devices, compact_x=compact_x,
        n_touched=n_touched, op=op, structure=structure, gather=gather)


def from_compiled(compiled, chips: int, model_flops: float = 0.0,
                  hlo_text: Optional[str] = None) -> Roofline:
    """Roofline terms via the trip-count-aware HLO parser (hlo_parse).
    XLA's own cost_analysis() counts while bodies once — wrong for a
    scanned-layer model — so it is recorded only as a cross-check."""
    from . import hlo_parse
    text = hlo_text if hlo_text is not None else compiled.as_text()
    parsed = hlo_parse.analyze(text)
    return Roofline(flops_per_device=parsed["flops"],
                    bytes_per_device=parsed["bytes"],
                    collective_bytes_per_device=parsed["collective_bytes"],
                    chips=chips, model_flops=model_flops)
