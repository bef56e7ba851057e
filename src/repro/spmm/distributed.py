"""Distributed multi-RHS SpMM over shard_map — the product of the paper's
two winning parallel schedules (BCOH row banding §3.2, merge-path equal-nnz
spans §3.3) and the SpMM engine's SELL-C-σ slice stream.

The σ-sorted slice stream is already a sequence of uniform work quanta
(one width-row = C padded nonzeros), which makes both cross-device
schedules one-liners over it:

* ``partition_sellcs_rows`` + ``spmm_row_distributed`` — BCOH across the
  mesh: contiguous *slice* bands balanced by width-row count, the k-block X
  replicated per shard (the paper's interleaved x allocation), Y written
  shard-local in slot space — **zero collectives**. Loses only when one
  slice dominates (a mawi-style dense row never splits).

* ``partition_sellcs_nnz`` + ``spmm_merge_distributed`` — merge-path
  across the mesh: equal spans of *width-rows* regardless of slice
  boundaries (a dense row's slice is split mid-stream), partial slot
  contributions combined with a ``psum`` — the cross-device carry-out
  fixup, at the cost of an all-reduce on Y. With ``num_chunks > 1`` the
  fixup is *pipelined*: the slot space is split into spans of consecutive
  slices and each span's psum is issued right after its local compute, so
  the collective hides under the next span's slice stream instead of
  serializing after all of it (Eckstein & Mátyásfalvi, arXiv:1812.00904).

Both shard_map bodies reuse the PR-1 compute verbatim: the k-tiled Pallas
kernel (``kernels.sellcs_slots``) on TPU, its jnp twin
(``reference.sellcs_slots_ref``) off-TPU — a shard's slice stream is just a
shorter stream with its own ``slice_of`` relabeling. The σ-sort row
permutation is global, so it is undone once, *after* the mesh region, by
the same single scatter the single-device path uses.

2-D (``data``, ``model``) meshes — the k ≫ 128 scaling axis: when the mesh
carries a ``model`` axis, both multiplies additionally shard the padded X
and Y k-slabs across it. Each model shard owns ``kp / P_model`` columns of
X (and computes only those columns of Y), the slice stream is replicated
along ``model``, and every psum of the merge fixup runs on the ``data``
axis alone — so per-device collective bytes AND per-device replicated-X
read bytes both drop by ``P_model``. The column split composes with the
chunked pipeline orthogonally: columns are independent, so no extra
collective appears. This is the distributed-memory cure of Eckstein &
Mátyásfalvi applied to the vector dimension: shrink what crosses the wire
instead of pushing it harder.

Sparsity-aware X gather (``compact_x``) — the remaining un-shrunk traffic
term: a data shard's slice stream touches only the columns its nonzeros
name, yet the replicated X slab makes every shard read all ``n`` rows.
Partitioning with ``compact_x=True`` computes each shard's touched-column
map at convert time (``col_map``/``n_touched``), relabels the shard's
``cols`` into the compacted index space ``[0, n_touched)``, and the
multiply gathers the touched X rows once per call into a per-shard
``[n_touched, kc]`` slab (still column-sharded across ``model``) — the
replicated-X read becomes nnz-proportional on both mesh axes, the
hypergraph-partitioning move of Eckstein & Mátyásfalvi applied to the
vector reads. Compaction composes with ``num_chunks`` pipelining (the
span re-deal builds its own touched map over the re-dealt rows) and costs
one int32 map per shard, priced by ``ShardedSellCS.storage_bytes`` and
``roofline.spmm_distributed_traffic(compact_x=True)``.

Gather scheduling (``gather=``) — hiding the compact-X gather: the
up-front ``x_pad[col_map]`` slab build is one XLA gather serialized on
the critical path before the first kernel launch. ``gather="overlap"``
(chunked merge) rebuilds each span's piece of the slab inside the mesh
region from the plan's per-span touched split, so span ``i+1``'s gather
hides under span ``i``'s kernel/psum. Both modes are bitwise-identical; ``roofline.spmm_distributed_gather_s`` prices the
exposed seconds of each so the selector can choose.

Phase tracing (``repro.obs``): both multiplies carry ``span()`` markers at
the phase boundaries the structure already has — ``spmm/gather_x`` (the
compact-X gather ahead of the mesh region; under ``gather="overlap"`` it
splits into per-span ``spmm/gather_x/span<i>`` sub-spans inside the mesh
body), ``spmm/mesh`` (the whole
shard_map region), ``spmm/kernel`` / ``spmm/psum`` (inside the mesh body,
which is traced: they record nothing, and their names ride into the
compiled HLO via ``jax.named_scope`` so device profiles show them), and
``spmm/fixup`` (the σ-unpermute scatter). With no registry installed the
spans are allocation-free no-ops; with one installed the host-level
spans additionally block on their outputs so they time execution, not
async dispatch (``obs.maybe_block``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mergepath import balanced_row_bands
from repro.obs import maybe_block, span
from .kernels import (LANE, choose_k_tile, sellcs_slots, sellcs_slots_chunk,
                      sellcs_slots_t)
from .reference import (_as_2d, sellcs_slots_chunk_ref, sellcs_slots_ref,
                        sellcs_slots_t_ref)
from .sellcs import SellCS


class ShardedSellCS(NamedTuple):
    """Per-device SELL-C-σ width-row shards, stacked on a leading device
    axis. ``schedule`` records which partitioner built it (the two
    schedules index slices differently)."""
    data: jax.Array          # f32[Pdev, Wp, C] — zero-padded width-rows
    cols: jax.Array          # int32[Pdev, Wp, C] — global column indices
    slice_of: jax.Array      # int32[Pdev, Wp] — LOCAL slice ids ("row")
                             #   or GLOBAL slice ids ("merge")
    slice_offset: jax.Array  # int32[Pdev] — first global slice per shard
                             #   ("row"; zeros for "merge")
    row_perm: jax.Array      # int32[S*C] — global σ-sort permutation
    shape: Tuple[int, int]
    chunk: int               # C — slice height
    num_slices: int          # S — GLOBAL slice count
    slices_per_shard: int    # local slot space height ("row"; S for "merge")
    nnz: int
    schedule: str            # "row" | "merge"
    chunk_plan: Optional[Tuple] = None
                             # (num_chunks, spans, plan col_map, plan
                             #   n_touched) precomputed by
                             #   partition_sellcs_nnz(num_chunks=) so the
                             #   pipelined multiply never re-deals the
                             #   stream host-side per call; the map entries
                             #   are None unless compact_x (the span
                             #   re-deal owns different rows than the base
                             #   partition, hence its own map)
    row_counts: Optional[jax.Array] = None
                             # int32[Pdev] — REAL width-rows per shard,
                             #   recorded at partition time. The stream can
                             #   carry width-rows whose stored values are
                             #   all explicit zeros (SellCS.to_coo
                             #   round-trips them by design), so real vs
                             #   padding is NOT derivable from the values.
    col_map: Optional[jax.Array] = None
                             # int32[Pdev, Ntc] — sorted global column ids
                             #   each shard touches (compact_x=True only):
                             #   the multiply gathers X rows through this
                             #   map instead of replicating all n rows.
                             #   cols above are relabeled into its index
                             #   space; padding entries point at slot 0.
    n_touched: Optional[jax.Array] = None
                             # int32[Pdev] — true distinct-column count per
                             #   shard (the real prefix of each col_map row)
    structure: str = "general"
                             # "general" | "symmetric" — symmetric shards
                             #   carry one stored triangle (row >= col) and
                             #   the dense diagonal below; the multiply
                             #   combines the normal and transpose passes
    diag: Optional[jax.Array] = None
                             # f32[m] dense diagonal (symmetric mode only)

    def storage_bytes(self) -> int:
        """Faithful device-side cost of the partitioned stream: every
        member array, the ``compact_x`` column maps, and any baked chunk
        plan. Kept equal to the sum of the member arrays' ``nbytes``
        (asserted in the tests) so the paper's conversion-amortization
        comparisons ("472 multiplications" §7) never flatter the
        distributed format — the col_map is storage the compaction buys
        its gather with, not free metadata."""
        total = (self.data.nbytes + self.cols.nbytes + self.slice_of.nbytes
                 + self.slice_offset.nbytes + self.row_perm.nbytes)
        for opt in (self.row_counts, self.col_map, self.n_touched,
                    self.diag):
            if opt is not None:
                total += opt.nbytes
        if self.chunk_plan is not None:
            for sp in self.chunk_plan[1]:
                total += sp.data.nbytes + sp.cols.nbytes + sp.slice_of.nbytes
                for opt in (sp.sub, sp.col_map, sp.n_touched):
                    if opt is not None:
                        total += opt.nbytes
            for opt in self.chunk_plan[2:]:
                if opt is not None:
                    total += opt.nbytes
        return int(total)


def _check_devices(num_devices: int) -> None:
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")


def _check_unsplit(sc: SellCS) -> None:
    """The mesh schedules assume each row owns one slot: the row bands
    scatter their slots with no collective. A stream with split rows
    (``coo_to_sellcs(split_long_rows=True)``) is for one device only."""
    if sc.split_lanes:
        raise ValueError("a SELL-C-σ stream with split rows cannot be "
                         "partitioned over a mesh; build it without "
                         "split_long_rows")


def _compact_columns(Cc: np.ndarray, counts: np.ndarray):
    """Host-side, convert time: per-shard touched-column maps over the
    device-dealt ``cols`` blocks.

    ``Cc[p, :counts[p]]`` holds shard ``p``'s REAL width-rows (lane padding
    inside a real width-row carries col 0 with data 0 — the harmless-FMA
    convention — so col 0 joins the touched set whenever the shard is
    nonempty: the kernel really does read that X row). Returns
    ``(relabeled Cc, col_map int32[P, Ntc], n_touched int32[P])`` where
    ``col_map[p]`` is the sorted touched set (zero-padded to the widest
    shard) and ``Cc`` is rewritten in-place into its index space.
    Padding width-rows keep col 0 — in range of every gathered slab.
    """
    P = Cc.shape[0]
    touched = [np.unique(Cc[p, :int(counts[p])]) if int(counts[p])
               else np.zeros(0, np.int64) for p in range(P)]
    col_map, n_touched = _pack_maps(touched)
    for p, t in enumerate(touched):
        ln = int(counts[p])
        if ln:
            Cc[p, :ln] = np.searchsorted(t, Cc[p, :ln])
    return Cc, col_map, n_touched


def _pack_maps(touched):
    """Stack per-device sorted touched sets into the dense
    ``(col_map int64[P, Ntc], n_touched int64[P])`` pair (zero-padded to
    the widest shard; Ntc >= 1 so an all-empty mesh still gathers a
    1-row slab).

    Ntc is rounded up to the Pallas lane width HERE, at bake time, so the
    multiply-time gather is a single ``x_pad[col_map]`` — no per-call
    ``jnp.concatenate`` pad inside the jitted hot path. Padding entries
    point at row 0 (the harmless-FMA convention: only data == 0 lanes ever
    index them); the invariant is asserted host-side once, where it is
    cheap, instead of trusted inside every trace."""
    n_touched = np.array([t.size for t in touched], np.int64)
    Ntc = max(int(n_touched.max()) if len(touched) else 0, 1)
    Ntc = -(-Ntc // LANE) * LANE
    col_map = np.zeros((len(touched), Ntc), np.int64)
    for p, t in enumerate(touched):
        col_map[p, :t.size] = t
        assert not col_map[p, t.size:].any(), \
            "col_map padding must point at row 0"
    return col_map, n_touched


def _deal_slice_bands(data: np.ndarray, cols: np.ndarray,
                      slice_of: np.ndarray, slice_ptr: np.ndarray,
                      num_devices: int, C: int):
    """The BCOH deal over a global width-row stream: contiguous slice
    bands balanced by width-row count (``balanced_row_bands`` — slice_ptr
    IS the cumulative width, so "rows" = slices and "nnz" = width-rows).
    Slice ids come out LOCAL (rebased per band). Shared by the convert-time
    partitioner and the device-loss re-deal. Returns
    ``(D, Cc, So, bounds, Sp, counts)``."""
    bounds = balanced_row_bands(slice_ptr, num_devices).astype(np.int64)
    w_start = slice_ptr[bounds]
    Wp = max(int(np.diff(w_start).max()) if num_devices else 1, 1)
    Sp = max(int(np.diff(bounds).max()), 1)
    D = np.zeros((num_devices, Wp, C), data.dtype if data.size else
                 np.float32)
    Cc = np.zeros((num_devices, Wp, C), np.int32)
    So = np.zeros((num_devices, Wp), np.int32)
    for p in range(num_devices):
        a, b = int(w_start[p]), int(w_start[p + 1])
        ln = b - a
        if ln:
            D[p, :ln] = data[a:b]
            Cc[p, :ln] = cols[a:b]
            So[p, :ln] = (slice_of[a:b] - bounds[p]).astype(np.int32)
    return D, Cc, So, bounds, Sp, np.diff(w_start)


def _deal_width_rows(data: np.ndarray, cols: np.ndarray,
                     slice_of: np.ndarray, num_devices: int, C: int):
    """The merge deal over a global width-row stream: equal spans of
    width-rows regardless of slice boundaries; slice ids stay GLOBAL.
    Shared by the convert-time partitioner and the device-loss re-deal.
    Returns ``(D, Cc, So, counts)``."""
    W = data.shape[0]
    bounds = (np.arange(num_devices + 1, dtype=np.int64) * W) // num_devices
    Wp = max(int(np.diff(bounds).max()), 1)
    D = np.zeros((num_devices, Wp, C), data.dtype if data.size else
                 np.float32)
    Cc = np.zeros((num_devices, Wp, C), np.int32)
    So = np.zeros((num_devices, Wp), np.int32)
    for p in range(num_devices):
        a, b = int(bounds[p]), int(bounds[p + 1])
        ln = b - a
        if ln:
            D[p, :ln] = data[a:b]
            Cc[p, :ln] = cols[a:b]
            So[p, :ln] = slice_of[a:b].astype(np.int32)
    return D, Cc, So, np.diff(bounds)


def partition_sellcs_rows(sc: SellCS, num_devices: int, *,
                          compact_x: bool = False) -> ShardedSellCS:
    """BCOH banding over the slice stream: contiguous slice ranges balanced
    by width-row count (each width-row is C padded nonzeros, so equal width
    is equal work). Host-side, convert time.

    Slices own disjoint row slots, so slice bands shard the (σ-permuted)
    rows — Y needs no collective.

    ``compact_x=True`` additionally computes each shard's touched-column
    map and relabels ``cols`` into its compacted index space: the multiply
    then gathers only the X rows this shard's nonzeros name instead of
    reading the full replicated slab (see the module docstring).
    """
    _check_devices(num_devices)
    _check_unsplit(sc)
    C = sc.chunk
    S = sc.num_slices
    D, Cc, So, bounds, Sp, counts = _deal_slice_bands(
        np.asarray(sc.data), np.asarray(sc.cols),
        np.asarray(sc.slice_of, np.int64),
        np.asarray(sc.slice_ptr, np.int64), num_devices, C)
    col_map = n_touched = None
    if compact_x:
        Cc, cm, nt = _compact_columns(Cc.astype(np.int64), counts)
        Cc = Cc.astype(np.int32)
        col_map = jnp.asarray(cm.astype(np.int32))
        n_touched = jnp.asarray(nt.astype(np.int32))
    return ShardedSellCS(
        jnp.asarray(D), jnp.asarray(Cc), jnp.asarray(So),
        jnp.asarray(bounds[:-1].astype(np.int32)), sc.row_perm,
        sc.shape, C, S, Sp, sc.nnz, "row",
        row_counts=jnp.asarray(counts.astype(np.int32)),
        col_map=col_map, n_touched=n_touched,
        structure=sc.structure, diag=sc.diag)


def partition_sellcs_nnz(sc: SellCS, num_devices: int, *,
                         num_chunks: int = 1,
                         compact_x: bool = False) -> ShardedSellCS:
    """Merge-style equal spans over the width-row stream (slices — and with
    them dense rows — may straddle devices). ``slice_of`` stays global:
    every device scatters into the full slot space and the carry-out is
    fixed with a psum.

    ``num_chunks > 1`` additionally precomputes the pipelined-fixup span
    plan (``_chunk_substreams``) here, at convert time, so
    ``spmm_merge_distributed(..., num_chunks=num_chunks)`` reuses it
    instead of re-dealing the stream host-side on every multiply.

    ``compact_x=True`` relabels each shard's ``cols`` through its
    touched-column map (see ``partition_sellcs_rows``); the chunk plan,
    which re-deals width-rows across devices, carries its *own* map over
    the re-dealt ownership.
    """
    _check_devices(num_devices)
    _check_unsplit(sc)
    if num_chunks < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    C = sc.chunk
    S = sc.num_slices
    D, Cc, So, counts = _deal_width_rows(
        np.asarray(sc.data), np.asarray(sc.cols),
        np.asarray(sc.slice_of, np.int64), num_devices, C)
    sharded = ShardedSellCS(
        jnp.asarray(D), jnp.asarray(Cc), jnp.asarray(So),
        jnp.zeros((num_devices,), jnp.int32), sc.row_perm,
        sc.shape, C, S, S, sc.nnz, "merge",
        row_counts=jnp.asarray(counts.astype(np.int32)),
        structure=sc.structure, diag=sc.diag)
    plan = None
    if num_chunks > 1:
        # baked BEFORE the base relabel: the plan needs global column ids
        # anyway (its own map covers the re-dealt ownership), so building
        # it first spares the relabel -> un-relabel round trip the
        # multiply-time recompute path has to pay
        plan = _chunk_substreams(sharded, num_chunks, compact=compact_x)
    if compact_x:
        Cc2, cm, nt = _compact_columns(Cc.astype(np.int64), counts)
        sharded = sharded._replace(
            cols=jnp.asarray(Cc2.astype(np.int32)),
            col_map=jnp.asarray(cm.astype(np.int32)),
            n_touched=jnp.asarray(nt.astype(np.int32)))
    if plan is not None:
        sharded = sharded._replace(
            chunk_plan=(int(num_chunks), plan.spans, plan.col_map,
                        plan.n_touched))
    return sharded


def rechunk_sellcs(sharded: ShardedSellCS,
                   num_chunks: int) -> ShardedSellCS:
    """Swap-path partition reuse: re-bake ONLY the pipelined-fixup span
    plan of an existing "merge" partition. The expensive convert-time
    artifacts — the device-dealt data/cols blocks, the σ permutation, the
    ``compact_x`` column maps — are reused untouched, so an online plan
    swap that changes just the psum pipelining depth
    (``launch.serve --migrate``, ``SparseOperator.swap``) costs one
    host-side span re-deal instead of a full repartition.

    ``num_chunks = 1`` drops the plan (the monolithic fixup needs none);
    a matching baked plan is returned as-is. The re-baked plan is
    byte-identical to what ``partition_sellcs_nnz(num_chunks=...)`` would
    have produced at convert time: ``_chunk_substreams`` re-deals the same
    global width-row stream either way (a compacted base is un-relabeled
    through its ``col_map`` first)."""
    if sharded.schedule != "merge":
        raise ValueError("rechunk_sellcs needs a 'merge' partition, got "
                         f"{sharded.schedule!r}")
    nc = int(num_chunks)
    if nc < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    if nc == 1:
        return sharded._replace(chunk_plan=None)
    if sharded.chunk_plan is not None and sharded.chunk_plan[0] == nc:
        return sharded
    plan = _chunk_substreams(sharded, nc)
    return sharded._replace(chunk_plan=(nc, plan.spans, plan.col_map,
                                        plan.n_touched))


def redeal_sellcs(sharded: ShardedSellCS, num_devices: int, *,
                  num_chunks: Optional[int] = None) -> ShardedSellCS:
    """Device-loss re-deal: rebuild an existing partition over a NEW device
    count without the original ``SellCS``. The global σ-sorted width-row
    stream is reconstructed from the shards (``_global_stream``: un-relabel
    a compacted base, globalize "row" slice ids, mask padding via
    ``row_counts``) and dealt again with the same machinery the convert-time
    partitioners use — the result is byte-identical to what
    ``partition_sellcs_rows`` / ``partition_sellcs_nnz`` would have produced
    from the original stream at ``num_devices``, so a mid-flight shrink
    (``runtime/elastic``: a device dies, survivors absorb its spans) never
    pays the σ-sort or the COO→SELL-C-σ conversion again.

    ``compact_x`` state is inherited from the input (the re-dealt ownership
    gets fresh touched-column maps); ``num_chunks`` defaults to the input's
    baked chunk depth ("merge" only)."""
    _check_devices(num_devices)
    compact = sharded.col_map is not None
    g_data, g_cols, g_so = _global_stream(sharded)
    C = sharded.chunk
    S = sharded.num_slices
    if sharded.schedule == "row":
        widths = (np.bincount(g_so, minlength=S) if g_so.size
                  else np.zeros(S, np.int64))
        slice_ptr = np.zeros(S + 1, np.int64)
        np.cumsum(widths, out=slice_ptr[1:])
        D, Cc, So, bounds, Sp, counts = _deal_slice_bands(
            g_data, g_cols, g_so, slice_ptr, num_devices, C)
        col_map = n_touched = None
        if compact:
            Cc, cm, nt = _compact_columns(Cc.astype(np.int64), counts)
            Cc = Cc.astype(np.int32)
            col_map = jnp.asarray(cm.astype(np.int32))
            n_touched = jnp.asarray(nt.astype(np.int32))
        return ShardedSellCS(
            jnp.asarray(D), jnp.asarray(Cc), jnp.asarray(So),
            jnp.asarray(bounds[:-1].astype(np.int32)), sharded.row_perm,
            sharded.shape, C, S, Sp, sharded.nnz, "row",
            row_counts=jnp.asarray(counts.astype(np.int32)),
            col_map=col_map, n_touched=n_touched,
            structure=sharded.structure, diag=sharded.diag)
    nc = (int(num_chunks) if num_chunks is not None
          else (sharded.chunk_plan[0] if sharded.chunk_plan is not None
                else 1))
    if nc < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    D, Cc, So, counts = _deal_width_rows(g_data, g_cols, g_so,
                                         num_devices, C)
    out = ShardedSellCS(
        jnp.asarray(D), jnp.asarray(Cc), jnp.asarray(So),
        jnp.zeros((num_devices,), jnp.int32), sharded.row_perm,
        sharded.shape, C, S, S, sharded.nnz, "merge",
        row_counts=jnp.asarray(counts.astype(np.int32)),
        structure=sharded.structure, diag=sharded.diag)
    plan = None
    if nc > 1:
        # same ordering as partition_sellcs_nnz: plan baked before the base
        # relabel, on global column ids
        plan = _chunk_substreams(out, nc, compact=compact)
    if compact:
        Cc2, cm, nt = _compact_columns(Cc.astype(np.int64), counts)
        out = out._replace(
            cols=jnp.asarray(Cc2.astype(np.int32)),
            col_map=jnp.asarray(cm.astype(np.int32)),
            n_touched=jnp.asarray(nt.astype(np.int32)))
    if plan is not None:
        out = out._replace(chunk_plan=(nc, plan.spans, plan.col_map,
                                       plan.n_touched))
    return out


def _resolve_model_axis(mesh: Mesh, axis: str,
                        model_axis: Optional[str]) -> Tuple[Optional[str],
                                                            int]:
    """(model axis name or None, P_model). An explicit ``model_axis`` must
    exist in the mesh; ``None`` auto-adopts a ``"model"`` mesh axis when
    present (the 2-D (data, model) mesh convention of ``launch.mesh``)."""
    if model_axis is None:
        model_axis = "model" if "model" in mesh.axis_names else None
    elif model_axis not in mesh.axis_names:
        raise ValueError(f"model_axis {model_axis!r} is not a mesh axis; "
                         f"mesh has {tuple(mesh.axis_names)}")
    if model_axis == axis:
        raise ValueError(f"model_axis {model_axis!r} collides with the "
                         f"data axis {axis!r}")
    return model_axis, (int(mesh.shape[model_axis]) if model_axis else 1)


def _prep(sharded: ShardedSellCS, x: jax.Array, mesh: Mesh, axis: str,
          impl: str, k_tile: Optional[int], expect: str,
          model_axis: Optional[str], compact_x: Optional[bool] = None,
          op: str = "N"):
    if op not in ("N", "T"):
        raise ValueError(f"op must be 'N' or 'T', got {op!r}")
    if sharded.schedule != expect:
        raise ValueError(
            f"sharded matrix was partitioned for the {sharded.schedule!r} "
            f"schedule; build it with partition_sellcs_"
            f"{'rows' if expect == 'row' else 'nnz'} instead")
    ndev = sharded.data.shape[0]
    if ndev != mesh.shape[axis]:
        raise ValueError(
            f"matrix is partitioned over {ndev} devices but mesh axis "
            f"{axis!r} has {mesh.shape[axis]}")
    compact = sharded.col_map is not None
    if compact_x is not None and compact_x != compact:
        # cols are relabeled (or not) at partition time — a multiply-time
        # override cannot re-derive the other index space
        raise ValueError(
            f"compact_x={compact_x} but the matrix was partitioned with "
            f"compact_x={compact}; repartition with partition_sellcs_"
            f"{'rows' if expect == 'row' else 'nnz'}(..., "
            f"compact_x={compact_x})")
    maxis, pm = _resolve_model_axis(mesh, axis, model_axis)
    if impl not in ("ref", "pallas", "pallas_interpret"):
        raise ValueError(f"impl must be ref|pallas|pallas_interpret, "
                         f"got {impl!r}")
    x2, squeeze = _as_2d(x)
    m, n = sharded.shape
    n_in = m if op == "T" else n      # A^T X consumes m-row inputs
    if x2.shape[0] != n_in:
        raise ValueError(f"X rows {x2.shape[0]} != expected {n_in} "
                         f"(op={op!r}, matrix {m}x{n})")
    k = x2.shape[1]
    use_pallas = impl != "ref"
    # kc = X/Y columns owned by ONE model shard. The k-tile (and with it the
    # Pallas k-grid) lives inside a model shard, so it is chosen for kc, not
    # the global k; kp = kc * pm is the padded global slab width.
    kc = -(-k // pm)
    if use_pallas:
        kt = k_tile or choose_k_tile(kc, chunk=sharded.chunk)
        kc = -(-kc // kt) * kt
    else:
        kt = k_tile
    kp = kc * pm
    if op == "T":
        # permute X into slot space once, ahead of the mesh: every shard's
        # transpose kernel then reads contiguous C-blocks of it (padding
        # slots, row_perm == m, read a zero row); the σ-permutation is
        # consumed here, so the column-space output needs no unpermute
        xs = jnp.concatenate(
            [x2, jnp.zeros((1, k), x2.dtype)], axis=0)[sharded.row_perm]
        if kp != k:
            x_pad = jnp.zeros((xs.shape[0], kp), x2.dtype).at[:, :k].set(xs)
        else:
            x_pad = xs
    elif use_pallas:
        np_ = -(-max(n, 1) // LANE) * LANE
        x_pad = jnp.zeros((np_, kp), x2.dtype).at[:n, :k].set(x2)
    elif kp == k:
        x_pad = x2
    else:
        x_pad = jnp.zeros((n, kp), x2.dtype).at[:, :k].set(x2)
    return x2, squeeze, k, kt, x_pad, use_pallas, maxis, pm, compact


def _gather_x(x_pad: jax.Array, col_map: jax.Array) -> jax.Array:
    """The sparsity-aware X gather: one ``x_pad[col_map]`` per multiply
    builds the per-shard ``[Ntc, kp]`` compacted slabs, stacked on the
    device axis — each data shard reads only the X rows its relabeled
    ``cols`` name. The slab height was padded to the Pallas lane width at
    bake time (``_pack_maps``; padding map entries point at row 0 and only
    data==0 lanes ever index them), so the hot path is this one gather."""
    return x_pad[col_map]


def _out_dtype(sharded: ShardedSellCS, x2: jax.Array, use_pallas: bool):
    """The dtype the nonzero compute path would produce: the Pallas kernel
    accumulates in float32; the jnp twin promotes (data, X)."""
    if use_pallas:
        return jnp.float32
    return jnp.promote_types(sharded.data.dtype, x2.dtype)


class _ChunkSpan(NamedTuple):
    """One pipelined span of the slice stream: the merge partitioning
    applied to a slice range (every device holds an equal share of THIS
    span's width-rows, so all devices finish a span together and its psum
    overlaps the next span's compute).

    For a ``compact_x`` plan each span additionally carries its own
    touched-column split (the overlapped-gather feed): ``sub`` holds the
    sorted plan-space positions this span's re-dealt rows touch on each
    device, ``col_map`` the matching GLOBAL column ids
    (``col_map == plan col_map[sub]`` row-wise), and ``n_touched`` the true
    per-device count. The overlapped multiply rebuilds span ``i``'s piece
    of the gathered slab *inside* the mesh region —
    ``slab.at[sub].set(x[col_map])`` — so XLA can run span ``i+1``'s
    gather under span ``i``'s kernel/psum instead of serializing one
    monolithic gather ahead of the first launch. Padding entries carry the
    consistent pair (``sub == 0``, ``col_map == plan col_map[:, 0]``):
    duplicate scatter writes then all carry the identical value, keeping
    the slab deterministic and bitwise-equal to the up-front gather."""
    slice_start: int         # first global slice of the span
    num_slices: int          # slices in the span (> 0)
    data: jax.Array          # [P, Wc, C] — zero-padded equal shares
    cols: jax.Array          # int32[P, Wc, C]
    slice_of: jax.Array      # int32[P, Wc] — GLOBAL slice ids
    sub: Optional[jax.Array] = None
                             # int32[P, Nsub] — plan-space positions this
                             #   span touches (compact plans only)
    col_map: Optional[jax.Array] = None
                             # int32[P, Nsub] — their global column ids
    n_touched: Optional[jax.Array] = None
                             # int32[P] — true touched count per device


class _ChunkPlan(NamedTuple):
    """The pipelined span plan plus — for a ``compact_x`` stream — the
    touched-column map of the RE-DEALT ownership: the span deal gives each
    device different width-rows than the base partition, so the base
    ``col_map`` does not cover them; one map per device spans all its rows
    across every span (one gathered slab per multiply, not one per span).
    Each span also carries its own per-span split of that map (see
    ``_ChunkSpan``) so the gather can be overlapped with the span loop."""
    spans: Tuple[_ChunkSpan, ...]
    col_map: Optional[jax.Array]     # int32[P, Ntc'] — None when uncompacted
    n_touched: Optional[jax.Array]   # int32[P]


def _global_stream(sharded: ShardedSellCS):
    """Host-side: flatten a partitioned stream back into the global
    σ-sorted width-row stream it was dealt from. Device spans are
    contiguous and ordered, and the partitioner recorded how many REAL
    width-rows each shard holds. Real vs padding must come from those
    counts, never from the values — a width-row whose stored entries are
    all explicit zeros (SellCS.to_coo round-trips them by design) is real
    work with real column indices, and dropping it silently skews any
    downstream width accounting.

    A compacted base is un-relabeled through its ``col_map`` (the global
    stream must carry global column ids); "row" shards carry LOCAL slice
    ids, which are globalized back through ``slice_offset``. Returns
    ``(g_data [W', C], g_cols [W', C], g_so [W'])``."""
    data = np.asarray(sharded.data)                  # [P, Wp, C]
    cols = np.asarray(sharded.cols)
    if sharded.col_map is not None:
        # back to global ids: device p's relabeled cols index its own map
        cm = np.asarray(sharded.col_map, np.int64)
        cols = cm[np.arange(cm.shape[0])[:, None, None],
                  cols.astype(np.int64)]
    so = np.asarray(sharded.slice_of, np.int64)      # [P, Wp]
    if sharded.schedule == "row":
        so = so + np.asarray(sharded.slice_offset, np.int64)[:, None]
    if sharded.row_counts is None:
        raise ValueError(
            "sharded matrix carries no row_counts; rebuild it with "
            "partition_sellcs_nnz (older ShardedSellCS values cannot be "
            "chunked — real rows are not derivable from the stored values)")
    counts = np.asarray(sharded.row_counts, np.int64)          # [P]
    real = (np.arange(data.shape[1], dtype=np.int64)[None]
            < counts[:, None])                                 # [P, Wp]
    return data[real], cols[real], so[real]


def _chunk_substreams(sharded: ShardedSellCS, num_chunks: int, *,
                      compact: Optional[bool] = None) -> _ChunkPlan:
    """Host-side: split the σ-sorted slice stream into ``num_chunks``
    width-balanced slice spans (``balanced_row_bands`` over the cumulative
    width, the same splitter both partitioners use) and re-partition EACH
    span's width-rows equally across all devices.

    The per-span re-partitioning is what makes the pipeline honest: the
    merge psum sums slot partials over every device anyway, so a width-row
    may live on any device — giving each device ``W_span / P`` rows of
    every span keeps per-device compute at the monolithic ``W / P`` total
    (no cross-span padding blow-up) and lets all devices reach span ``i``'s
    psum at the same time, with span ``i+1``'s compute ready to hide it.

    ``num_chunks > S`` degenerates to one span per nonempty slice (empty
    bands are dropped); the spans exactly tile ``[0, S)`` in order.

    For a ``compact`` plan (default: follow the shard's own
    ``compact_x`` state; ``partition_sellcs_nnz`` passes it explicitly to
    bake plans before the base relabel) the finished spans are relabeled
    through a fresh per-device map over the re-dealt ownership
    (``_ChunkPlan.col_map``). A stream whose base is already compacted is
    first un-relabeled through its ``col_map`` — the global stream must
    carry global column ids.
    """
    if compact is None:
        compact = sharded.col_map is not None
    g_data, g_cols, g_so = _global_stream(sharded)
    Pdev = sharded.data.shape[0]
    C = sharded.chunk
    S = sharded.num_slices
    nc = int(num_chunks)
    widths = (np.bincount(g_so, minlength=S) if g_so.size
              else np.zeros(S, np.int64))
    slice_ptr = np.zeros(S + 1, np.int64)
    np.cumsum(widths, out=slice_ptr[1:])
    bounds = balanced_row_bands(slice_ptr, nc).astype(np.int64)
    raw = []                 # (s0, ns, D, Cc, So, per-device real lengths)
    for i in range(nc):
        s0, s1 = int(bounds[i]), int(bounds[i + 1])
        if s1 <= s0:
            continue                                 # empty band (nc > S)
        a, b = int(slice_ptr[s0]), int(slice_ptr[s1])
        Wi = b - a
        Wc = max(-(-Wi // Pdev), 1)
        D = np.zeros((Pdev, Wc, C), g_data.dtype)
        Cc = np.zeros((Pdev, Wc, C), np.int64)
        So = np.full((Pdev, Wc), s0, np.int32)       # padding rebases to 0
        db = (np.arange(Pdev + 1, dtype=np.int64) * Wi) // Pdev
        for p in range(Pdev):
            ln = int(db[p + 1] - db[p])
            if ln:
                D[p, :ln] = g_data[a + db[p]:a + db[p + 1]]
                Cc[p, :ln] = g_cols[a + db[p]:a + db[p + 1]]
                So[p, :ln] = g_so[a + db[p]:a + db[p + 1]].astype(np.int32)
        raw.append((s0, s1 - s0, D, Cc, So, np.diff(db)))
    plan_map = plan_nt = None
    span_maps = [() for _ in raw]
    if compact:
        # touched set of the RE-DEALT ownership: device p's rows across all
        # spans, then one searchsorted relabel per (span, device) block
        touched = []
        for p in range(Pdev):
            vals = [Cc[p, :int(lens[p])].ravel()
                    for _, _, _, Cc, _, lens in raw if int(lens[p])]
            touched.append(np.unique(np.concatenate(vals)) if vals
                           else np.zeros(0, np.int64))
        cm, nt = _pack_maps(touched)
        for _, _, _, Cc, _, lens in raw:
            for p in range(Pdev):
                ln = int(lens[p])
                if ln:
                    Cc[p, :ln] = np.searchsorted(touched[p], Cc[p, :ln])
        plan_map = jnp.asarray(cm.astype(np.int32))
        plan_nt = jnp.asarray(nt.astype(np.int32))
        # per-span touched split for the overlapped gather: the sorted
        # plan-space positions span i's rows touch on each device, plus
        # their global ids. Padding rows carry the consistent pair
        # (sub == 0, col_map == cm[p, 0]) so every duplicate scatter write
        # lands the same value (deterministic slab; see _ChunkSpan).
        span_maps = []
        for _, _, _, Cc, _, lens in raw:
            subs = [np.unique(Cc[p, :int(lens[p])].ravel())
                    if int(lens[p]) else np.zeros(0, np.int64)
                    for p in range(Pdev)]
            ns = np.array([s.size for s in subs], np.int64)
            Wsub = max(int(ns.max()), 1)
            sub = np.zeros((Pdev, Wsub), np.int64)
            gcm = np.zeros((Pdev, Wsub), np.int64)
            for p, s in enumerate(subs):
                sub[p, :s.size] = s
                gcm[p, :s.size] = cm[p][s]
                gcm[p, s.size:] = cm[p, 0]
            span_maps.append((jnp.asarray(sub.astype(np.int32)),
                              jnp.asarray(gcm.astype(np.int32)),
                              jnp.asarray(ns.astype(np.int32))))
    spans = tuple(
        _ChunkSpan(s0, ns, jnp.asarray(D), jnp.asarray(Cc.astype(np.int32)),
                   jnp.asarray(So), *sm)
        for (s0, ns, D, Cc, So, _), sm in zip(raw, span_maps))
    # spans nonempty: bounds pin [0, S] and S >= 1
    return _ChunkPlan(spans, plan_map, plan_nt)



GATHER_MODES = ("upfront", "overlap")


def _resolve_gather(gather: Optional[str], compact: bool) -> str:
    """Validate the gather-scheduling knob. ``None`` (the default) is the
    up-front gather — byte-identical to the pre-knob behavior. The
    overlapped mode only exists where a gather exists: a replicated-X
    stream has nothing to hide."""
    if gather is None:
        return "upfront"
    if gather not in GATHER_MODES:
        raise ValueError(
            f"gather must be one of {GATHER_MODES} or None, got {gather!r}")
    if gather != "upfront" and not compact:
        raise ValueError(
            f"gather={gather!r} needs a compact_x partition — a "
            "replicated-X stream has no X gather to hide; repartition "
            "with compact_x=True")
    return gather


def _local_slots(data, cols, slice_of, x_rep, *, num_slices, chunk,
                 use_pallas, k_tile, interpret):
    """Shard-local compute: the k-tiled Pallas kernel, or its jnp twin
    off-TPU. Inputs carry a leading length-1 device-block axis."""
    if use_pallas:
        return sellcs_slots(data[0], cols[0], slice_of[0], x_rep,
                            num_slices=num_slices, chunk=chunk,
                            k_tile=k_tile, interpret=interpret)
    return sellcs_slots_ref(data[0], cols[0], slice_of[0], x_rep,
                            num_slices=num_slices, chunk=chunk)


def _local_slots_t(data, cols, slice_of, x_slots, *, n_out, chunk,
                   use_pallas, k_tile, interpret):
    """Shard-local transpose compute over one width-row block: the Pallas
    scatter-accumulate kernel on TPU, its jnp twin off-TPU. ``slice_of``
    must already be global (the callers globalize "row" shards through
    ``slice_offset``); ``x_slots`` is the slot-permuted X."""
    if use_pallas:
        return sellcs_slots_t(data, cols, slice_of, x_slots, n_out=n_out,
                              chunk=chunk, k_tile=k_tile,
                              interpret=interpret)
    return sellcs_slots_t_ref(data, cols, slice_of, x_slots, n_out=n_out,
                              chunk=chunk)


def _scatter_touched(yb: jax.Array, col_map: jax.Array,
                     n_touched: jax.Array, n: int, k: int,
                     squeeze: bool) -> jax.Array:
    """Post-mesh fixup for ``op='T'`` under ``compact_x``: the relabeled
    ``cols`` made each shard's transpose output land in its compacted
    index space ``[0, n_touched)`` — the touched-column map read the
    paper's gather forward now runs backward as a scatter-add into the
    global output rows. Padding map entries (past ``n_touched``) dump into
    row ``n``, which is dropped."""
    Pdev, ntc = col_map.shape
    yb = yb.reshape(Pdev, ntc, -1)
    mask = (jnp.arange(ntc, dtype=jnp.int32)[None]
            < n_touched[:, None])                               # [P, Ntc]
    tgt = jnp.where(mask, col_map, n)
    y = jnp.zeros((n + 1, yb.shape[-1]), yb.dtype).at[tgt].add(
        jnp.where(mask[..., None], yb, 0))[:n, :k]
    return y[:, 0] if squeeze else y


def _symmetric_combine(multiply, sharded: ShardedSellCS, x: jax.Array,
                       **kw) -> jax.Array:
    """One-triangle symmetric multiply: run the normal and transpose
    passes over the stored triangle and subtract the double-counted
    diagonal (``A X = N(X) + T(X) - diag * X``). ``op='N'`` and ``op='T'``
    coincide — ``A == A^T``.

    The diag term is cast to the kernel-path output dtype BEFORE the
    multiply: a wider stored diagonal (e.g. f64 diag over an f32 pallas
    result) must not out-promote the combine and silently hand back a
    different dtype than the general path would."""
    x2, squeeze = _as_2d(x)
    general = sharded._replace(structure="general")
    y_n = multiply(general, x2, op="N", **kw)
    y_t = multiply(general, x2, op="T", **kw)
    y = y_n + y_t - (sharded.diag.astype(y_n.dtype)[:, None]
                     * x2.astype(y_n.dtype))
    return y[:, 0] if squeeze else y


def _unpermute(sharded: ShardedSellCS, y_slots: jax.Array, k: int,
               squeeze: bool) -> jax.Array:
    """Undo the global σ-sort with one scatter (padding slots target row m,
    which is dropped)."""
    m = sharded.shape[0]
    y = jnp.zeros((m + 1, y_slots.shape[1]), y_slots.dtype
                  ).at[sharded.row_perm].add(y_slots)[:m, :k]
    return y[:, 0] if squeeze else y


def spmm_row_distributed(sharded: ShardedSellCS, x: jax.Array, mesh: Mesh,
                         axis: str = "data", *, impl: str = "ref",
                         k_tile: Optional[int] = None,
                         model_axis: Optional[str] = None,
                         compact_x: Optional[bool] = None,
                         op: str = "N",
                         gather: Optional[str] = None) -> jax.Array:
    """Y = A @ X with slice banding: X replicated along ``axis``, Y
    shard-local slots, zero collectives inside the mesh region.

    On a mesh carrying a ``model`` axis (or an explicit ``model_axis``),
    the X/Y k-slabs are additionally column-sharded across it: each model
    shard reads ``1/P_model`` of the replicated X and writes its own column
    block of Y — the slice stream itself is replicated along ``model``.

    A matrix partitioned with ``compact_x=True`` swaps the replicated X
    read for the sparsity-aware gather: ``_gather_x`` builds each shard's
    ``[n_touched, kc]`` slab once per call and the slab rides the ``data``
    axis next to the slice stream. ``compact_x=`` here only *asserts* the
    partition-time choice (None follows it) — the relabeled stream cannot
    consume a replicated X, nor the reverse.

    ``op='T'`` computes ``Y = A^T X`` (``X: [m, k]``, ``Y: [n, k]``) over
    the same partition: X is permuted into slot space ahead of the mesh,
    each shard scatter-accumulates into column space (its local slice ids
    globalized through ``slice_offset``), and — since column ownership
    overlaps arbitrarily across shards — the fixup is a psum on the data
    axis (the zero-collective property is a row-space property; transpose
    outputs live in column space). Under ``compact_x`` the relabeled cols
    make each shard's output land in its compacted index space, so the
    psum is replaced by a per-shard ``[n_touched, kc]`` stack that
    scatter-adds through the touched-column map after the mesh region —
    the touched-*column* map becomes a touched-*output-row* map.

    ``gather=`` schedules the compact-X gather: ``"upfront"`` (default)
    materializes the slab ahead of the mesh region, and ``"overlap"``
    degenerates to up-front here — the row schedule has no span loop to
    hide the gather under. Both modes are bitwise-identical;
    the knob only moves WHEN the touched rows are read. ``op='T'`` has no
    gather (X enters slot-permuted), so the knob is validated and ignored.

    Symmetric one-triangle partitions combine both passes over the stored
    triangle (``A X = N(X) + T(X) - diag * X``); ``op`` is then moot.
    """
    if sharded.structure == "symmetric":
        return _symmetric_combine(
            lambda s, xx, **kw: spmm_row_distributed(
                s, xx, mesh, axis, impl=impl, k_tile=k_tile,
                model_axis=model_axis, compact_x=compact_x, gather=gather,
                **kw),
            sharded, x)
    m, n = sharded.shape
    C, S, Sp = sharded.chunk, sharded.num_slices, sharded.slices_per_shard
    ndev = sharded.data.shape[0]
    x2, squeeze, k, kt, x_pad, use_pallas, maxis, pm, compact = _prep(
        sharded, x, mesh, axis, impl, k_tile, "row", model_axis, compact_x,
        op)
    _resolve_gather(gather, compact)      # validated; one gather here
    if sharded.nnz == 0:
        y = jnp.zeros((n if op == "T" else m, k),
                      _out_dtype(sharded, x2, use_pallas))
        return y[:, 0] if squeeze else y
    interpret = impl == "pallas_interpret"
    # with one model shard the k-tile padding columns stop at the kernel:
    # the fixup and the unpermute move the true k only
    k_keep = k if pm == 1 else x_pad.shape[1] // pm
    if op == "T":
        n_eff = int(sharded.col_map.shape[1]) if compact else n

        def local_t(data, cols, slice_of, offs, x_loc):
            gso = slice_of[0] + offs          # globalize the band's slices
            with span("spmm/kernel"):
                y_loc = _local_slots_t(data[0], cols[0], gso, x_loc,
                                       n_out=n_eff, chunk=C,
                                       use_pallas=use_pallas, k_tile=kt,
                                       interpret=interpret)
            if compact:
                return y_loc[:, :k_keep]
            with span("spmm/psum"):
                return jax.lax.psum(y_loc[:, :k_keep], axis)

        with span("spmm/mesh"):
            yb = maybe_block(jax.shard_map(
                local_t, mesh=mesh,
                in_specs=(P(axis, None, None), P(axis, None, None),
                          P(axis, None), P(axis), P(None, maxis)),
                out_specs=P(axis, maxis) if compact else P(None, maxis),
                check_vma=not use_pallas)(
                    sharded.data, sharded.cols, sharded.slice_of,
                    sharded.slice_offset, x_pad))
        with span("spmm/fixup"):
            if compact:
                return maybe_block(_scatter_touched(
                    yb, sharded.col_map, sharded.n_touched, n, k, squeeze))
            y = yb[:n, :k]
            return maybe_block(y[:, 0] if squeeze else y)
    if compact:
        # up-front gather ("overlap" degenerates here: no span loop)
        with span("spmm/gather_x"):
            x_feed = maybe_block(_gather_x(x_pad, sharded.col_map))
        x_spec = P(axis, None, maxis)
    else:
        x_feed, x_spec = x_pad, P(None, maxis)

    def local(data, cols, slice_of, x_loc):
        with span("spmm/kernel"):
            return _local_slots(data, cols, slice_of,
                                x_loc[0] if compact else x_loc,
                                num_slices=Sp, chunk=C,
                                use_pallas=use_pallas, k_tile=kt,
                                interpret=interpret)[:, :k_keep]

    in_specs = (P(axis, None, None), P(axis, None, None),
                P(axis, None), x_spec)
    args = (sharded.data, sharded.cols, sharded.slice_of, x_feed)

    # pallas_call has no replication rule inside shard_map — skip the check
    with span("spmm/mesh"):
        yb = maybe_block(jax.shard_map(
            local, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(axis, maxis),
            check_vma=not use_pallas)(*args))
    with span("spmm/fixup"):
        yb = yb.reshape(ndev, Sp * C, -1)
        # shard p owns global slices [slice_offset[p], slice_offset[p+1]);
        # scatter its local slots there, dumping padding slots past S*C.
        offs = sharded.slice_offset
        valid_slices = jnp.concatenate(
            [offs[1:], jnp.array([S], jnp.int32)]) - offs       # [Pdev]
        local_slice = jnp.arange(Sp * C, dtype=jnp.int32) // C
        gslot = (offs[:, None] + local_slice[None]) * C \
            + (jnp.arange(Sp * C, dtype=jnp.int32) % C)[None]   # [Pdev, SpC]
        mask = local_slice[None] < valid_slices[:, None]
        y_slots = jnp.zeros((S * C + 1, yb.shape[-1]), yb.dtype).at[
            jnp.where(mask, gslot, S * C)].add(
                jnp.where(mask[..., None], yb, 0))[:S * C]
        return maybe_block(_unpermute(sharded, y_slots, k, squeeze))


def spmm_merge_distributed(sharded: ShardedSellCS, x: jax.Array, mesh: Mesh,
                           axis: str = "data", *, impl: str = "ref",
                           k_tile: Optional[int] = None,
                           num_chunks: int = 1,
                           model_axis: Optional[str] = None,
                           compact_x: Optional[bool] = None,
                           op: str = "N",
                           gather: Optional[str] = None) -> jax.Array:
    """Y = A @ X with equal-width spans: per-device slot partials + psum
    carry-out fixup (the only collective). Survives the mawi dense-row
    pathology — the dense slice splits mid-stream.

    ``num_chunks > 1`` pipelines the fixup: the slice stream is split into
    width-balanced spans of consecutive slices and each span's width-rows
    are re-dealt equally across the devices (``_chunk_substreams``), so
    every device reaches span ``i``'s psum together and XLA's async
    all-reduce of span ``i`` overlaps the kernel of span ``i+1`` instead of
    serializing after all local work. Only the true ``k`` columns cross the
    wire — the ``kp - k`` k-tile padding columns never enter the
    collective. Each slot is still reduced by exactly one psum, so the
    result equals the monolithic schedule up to fp summation order.
    ``num_chunks = 1`` is the monolithic schedule; ``num_chunks > S``
    degenerates to one span per nonempty slice.

    On a mesh carrying a ``model`` axis (or an explicit ``model_axis``),
    the X/Y k-slabs are column-sharded across it and **every psum runs on
    the data axis alone** — the model shards hold disjoint Y columns, so
    nothing of theirs needs reducing. Per-device collective bytes drop by
    ``P_model``: each device all-reduces only its own ``kc = kp / P_model``
    column block. Unlike the 1-D path, the tail padding columns (fewer
    than ``k_tile * P_model`` in aggregate, from rounding ``k`` up to a
    ``k_tile``-aligned per-shard width) DO ride the wire — a uniform local
    slice cannot single out the global column ``k`` — which is noise in
    the k ≫ 128 regime this axis targets; the roofline model prices the
    ideal ``k / P_model``.

    A matrix partitioned with ``compact_x=True`` feeds each shard a
    gathered ``[n_touched, kc]`` slab instead of the replicated X (see
    ``spmm_row_distributed``); with ``num_chunks > 1`` the gather runs
    through the chunk plan's own map — the span re-deal changes which
    device owns which width-rows, so the plan carries a touched set over
    the re-dealt ownership. The psum is untouched: compaction shrinks
    reads, not the carry-out. ``compact_x=`` only asserts the
    partition-time choice; ``None`` follows it.

    ``op='T'`` computes ``Y = A^T X`` over the same spans: X enters the
    mesh slot-permuted, each span scatter-accumulates into column space
    through its global slice ids, and each span's ``[n, kc]`` partial is
    psum'd on the data axis as soon as it is ready (the same pipelined
    overlap as the normal fixup) and summed — column ownership overlaps
    across spans, so partials add instead of concatenating. Under
    ``compact_x`` the span outputs live in the (plan) touched-column index
    space: they are summed locally, stacked per shard, and scatter-added
    through the map after the mesh region (see ``spmm_row_distributed``).
    Symmetric one-triangle partitions combine both passes; ``op`` is moot.

    ``gather=`` schedules the compact-X gather: ``"upfront"`` (default)
    materializes the per-shard slab ahead of the mesh region — one XLA
    gather serialized before the first kernel launch. ``"overlap"``
    (``num_chunks > 1`` only; degenerates to up-front otherwise) rebuilds
    each span's piece of the slab INSIDE the mesh region from the plan's
    per-span touched split (``_ChunkSpan.sub``/``col_map``) — the span
    slabs have no cross-span data dependency, so span ``i+1``'s gather
    runs under span ``i``'s kernel/psum, the same overlap the pipelined
    fixup already exploits. Both modes are bitwise-identical (the gather
    only re-indexes X rows; untouched slab positions are read only by data == 0 padding
    lanes); the knob moves WHEN the touched rows are read, and the
    roofline prices the exposed seconds of each choice
    (``spmm_distributed_gather_s``). ``op='T'`` has no gather, so the
    knob is validated and ignored.
    """
    if sharded.structure == "symmetric":
        return _symmetric_combine(
            lambda s, xx, **kw: spmm_merge_distributed(
                s, xx, mesh, axis, impl=impl, k_tile=k_tile,
                num_chunks=num_chunks, model_axis=model_axis,
                compact_x=compact_x, gather=gather, **kw),
            sharded, x)
    m, n = sharded.shape
    C, S = sharded.chunk, sharded.num_slices
    nc = int(num_chunks)
    if nc < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    x2, squeeze, k, kt, x_pad, use_pallas, maxis, pm, compact = _prep(
        sharded, x, mesh, axis, impl, k_tile, "merge", model_axis,
        compact_x, op)
    gmode = _resolve_gather(gather, compact)
    if sharded.nnz == 0:
        y = jnp.zeros((n if op == "T" else m, k),
                      _out_dtype(sharded, x2, use_pallas))
        return y[:, 0] if squeeze else y
    interpret = impl == "pallas_interpret"
    # Columns to keep of each local slot block before its psum: with one
    # model shard the true k (the k-tile padding never crosses the wire);
    # with P_model > 1 every local column block is a distinct slice of the
    # global slab, so all kc local columns ship and the (kp - k) tail
    # padding is dropped after the mesh region by _unpermute.
    k_keep = k if pm == 1 else x_pad.shape[1] // pm

    if op == "T":
        if nc == 1:
            spans = None
            plan_map, plan_nt = sharded.col_map, sharded.n_touched
        else:
            if sharded.chunk_plan is not None and \
                    sharded.chunk_plan[0] == nc:
                spans, plan_map, plan_nt = (sharded.chunk_plan[1],
                                            sharded.chunk_plan[2],
                                            sharded.chunk_plan[3])
            else:
                plan = _chunk_substreams(sharded, nc)
                spans, plan_map, plan_nt = (plan.spans, plan.col_map,
                                            plan.n_touched)
        n_eff = int(plan_map.shape[1]) if compact else n

        def local_t(datas, colss, sos, x_loc):
            # one column-space partial per span; partials ADD (column
            # ownership overlaps across spans), each psum still issued
            # right after its span's kernel so it hides under the next
            total = None
            for data, cols, slice_of in zip(datas, colss, sos):
                with span("spmm/kernel"):
                    y_c = _local_slots_t(data[0], cols[0], slice_of[0],
                                         x_loc, n_out=n_eff, chunk=C,
                                         use_pallas=use_pallas, k_tile=kt,
                                         interpret=interpret)
                part = y_c[:, :k_keep]
                if not compact:
                    with span("spmm/psum"):
                        part = jax.lax.psum(part, axis)
                total = part if total is None else total + part
            return total

        if nc == 1:
            args = ((sharded.data,), (sharded.cols,), (sharded.slice_of,))
        else:
            args = (tuple(sp.data for sp in spans),
                    tuple(sp.cols for sp in spans),
                    tuple(sp.slice_of for sp in spans))
        nspan = len(args[0])
        blk = tuple(P(axis, None, None) for _ in range(nspan))
        with span("spmm/mesh"):
            yb = maybe_block(jax.shard_map(
                local_t, mesh=mesh,
                in_specs=(blk, blk,
                          tuple(P(axis, None) for _ in range(nspan)),
                          P(None, maxis)),
                out_specs=P(axis, maxis) if compact else P(None, maxis),
                check_vma=not use_pallas)(
                    *args, x_pad))
        with span("spmm/fixup"):
            if compact:
                return maybe_block(_scatter_touched(
                    yb, plan_map, plan_nt, n, k, squeeze))
            y = yb[:n, :k]
            return maybe_block(y[:, 0] if squeeze else y)

    if nc == 1:
        if compact:
            # up-front gather ("overlap" degenerates: no span loop)
            with span("spmm/gather_x"):
                x_feed = maybe_block(_gather_x(x_pad, sharded.col_map))
            x_spec = P(axis, None, maxis)
        else:
            x_feed, x_spec = x_pad, P(None, maxis)

        def local(data, cols, slice_of, x_loc):
            with span("spmm/kernel"):
                y_loc = _local_slots(data, cols, slice_of,
                                     x_loc[0] if compact else x_loc,
                                     num_slices=S, chunk=C,
                                     use_pallas=use_pallas, k_tile=kt,
                                     interpret=interpret)
            # carry-out fixup on the data axis ONLY: model shards own
            # disjoint Y columns and never enter the collective
            with span("spmm/psum"):
                return jax.lax.psum(y_loc[:, :k_keep], axis)

        in_specs = (P(axis, None, None), P(axis, None, None),
                    P(axis, None), x_spec)
        args = (sharded.data, sharded.cols, sharded.slice_of, x_feed)

        with span("spmm/mesh"):
            y_slots = maybe_block(jax.shard_map(
                local, mesh=mesh,
                in_specs=in_specs,
                out_specs=P(None, maxis),
                check_vma=not use_pallas)(*args))
        with span("spmm/fixup"):
            return maybe_block(_unpermute(sharded, y_slots, k, squeeze))

    if sharded.chunk_plan is not None and sharded.chunk_plan[0] == nc:
        # precomputed at partition time (spans + re-deal column map)
        spans, plan_map = sharded.chunk_plan[1], sharded.chunk_plan[2]
    else:
        plan = _chunk_substreams(sharded, nc)
        spans, plan_map = plan.spans, plan.col_map
    meta = [(sp.slice_start, sp.num_slices) for sp in spans]
    span_spec = tuple(P(axis, None, None) for _ in spans)
    so_spec = tuple(P(axis, None) for _ in spans)
    span_args = (tuple(sp.data for sp in spans),
                 tuple(sp.cols for sp in spans),
                 tuple(sp.slice_of for sp in spans))

    def _span_kernel(data, cols, slice_of, x_loc, s0, ns):
        if use_pallas:
            return sellcs_slots_chunk(
                data[0], cols[0], slice_of[0], x_loc,
                slice_start=s0, num_slices=ns, chunk=C, k_tile=kt,
                interpret=interpret)
        return sellcs_slots_chunk_ref(
            data[0], cols[0], slice_of[0], x_loc,
            slice_start=s0, num_slices=ns, chunk=C)

    if compact and gmode == "overlap" and \
            all(sp.sub is not None for sp in spans):
        # the overlapped gather: each span rebuilds its own piece of the
        # plan-space slab inside the mesh region — no data dependency
        # between span slabs, so XLA runs span i+1's gather (and its
        # kernel) under span i's psum, exactly like the pipelined fixup.
        # Untouched slab positions stay 0 and are only ever read by
        # data == 0 padding lanes, so the answer is bitwise-identical to
        # the up-front gather.
        ntc_plan = int(plan_map.shape[1])

        def local(datas, colss, sos, subs, cmaps, x_loc):
            outs = []
            for i, ((s0, ns), data, cols, slice_of, sub, cmap) in \
                    enumerate(zip(meta, datas, colss, sos, subs, cmaps)):
                with span(f"spmm/gather_x/span{i}"):
                    slab = jnp.zeros(
                        (ntc_plan, x_loc.shape[1]), x_loc.dtype
                    ).at[sub[0]].set(x_loc[cmap[0]])
                with span("spmm/kernel"):
                    y_c = _span_kernel(data, cols, slice_of, slab, s0, ns)
                with span("spmm/psum"):
                    outs.append(jax.lax.psum(y_c[:, :k_keep], axis))
            return jnp.concatenate(outs, axis=0)

        map_spec = tuple(P(axis, None) for _ in spans)
        in_specs = (span_spec, span_spec, so_spec, map_spec, map_spec,
                    P(None, maxis))
        args = span_args + (tuple(sp.sub for sp in spans),
                            tuple(sp.col_map for sp in spans), x_pad)
    else:
        if compact:
            # the spans' cols live in the chunk plan's index space, not
            # the base partition's — gather through the plan map
            with span("spmm/gather_x"):
                x_feed = maybe_block(_gather_x(x_pad, plan_map))
            x_spec = P(axis, None, maxis)
        else:
            x_feed, x_spec = x_pad, P(None, maxis)

        def local(datas, colss, sos, x_loc):
            # one (kernel -> psum) pair per span with no cross-span data
            # dependency: the span-i all-reduce-start can run under the
            # span-(i+1) kernel.
            x_loc = x_loc[0] if compact else x_loc
            outs = []
            for (s0, ns), data, cols, slice_of in zip(meta, datas, colss,
                                                      sos):
                with span("spmm/kernel"):
                    y_c = _span_kernel(data, cols, slice_of, x_loc, s0, ns)
                with span("spmm/psum"):
                    outs.append(jax.lax.psum(y_c[:, :k_keep], axis))
            # span i's rows sit at global slots [s0*C, (s0 + ns)*C); the
            # spans tile [0, S) in order, so concatenation IS the slot
            # array
            return jnp.concatenate(outs, axis=0)

        in_specs = (span_spec, span_spec, so_spec, x_spec)
        args = span_args + (x_feed,)

    with span("spmm/mesh"):
        y_slots = maybe_block(jax.shard_map(
            local, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(None, maxis),
            check_vma=not use_pallas)(*args))
    with span("spmm/fixup"):
        return maybe_block(_unpermute(sharded, y_slots, k, squeeze))
