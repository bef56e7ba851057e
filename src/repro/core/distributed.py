"""Distributed SpMV over a JAX mesh (the paper's multi-socket dimension,
scaled from 2 CPUs to pods).

Two strategies, mirroring the paper's two winning scheduling families:

* ``row_distributed``  (BCOH, §3.2): rows are statically banded so each
  device owns ~nnz/P nonzeros. x is replicated (the paper's interleaved
  allocation), y is written shard-locally — **zero collectives on y**. Wins
  when no single row dominates; this is why BCOH wins on NUMA machines.

* ``merge_distributed`` (Merge, §3.3): equal-nnz spans regardless of row
  boundaries; partial y contributions are combined with one ``psum`` — the
  carry-out fixup across devices. Survives the mawi single-dense-row case
  at the cost of an all-reduce on y.

Both are expressed with shard_map so the same code drives 8 host-platform
devices in tests and a 512-chip production mesh in the dry-run.

Multi-RHS: both multiply entry points accept ``x`` as ``[n]`` (SpMV,
today's behavior) or ``[n, k]`` (SpMM — each shard streams its nonzeros
once against the whole k-block, the same amortization ``repro.spmm``
exploits on one device). ``repro.spmm.distributed`` holds the SELL-C-σ
slice-stream versions of the same two schedules.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .formats import COO
from .mergepath import balanced_row_bands


class ShardedCOO(NamedTuple):
    """Per-device padded COO shards, stacked along a leading device axis."""
    rows: jax.Array        # int32[Pdev, nnz_pad] — LOCAL row indices
    cols: jax.Array        # int32[Pdev, nnz_pad] — global col indices
    vals: jax.Array        # f32[Pdev, nnz_pad]  — zero-padded
    row_offset: jax.Array  # int32[Pdev] — first global row of the shard
    shape: Tuple[int, int]
    rows_per_shard: int    # static: padded local row count


def _check_devices(num_devices: int) -> None:
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")


def partition_rows(coo: COO, num_devices: int) -> ShardedCOO:
    """BCOH static banding: equal-nnz row bands, zero-padded to uniform
    shard shapes (host-side, convert time).

    Degenerate inputs are well-formed: ``num_devices > m`` yields empty
    bands (zero-filled shards), and ``nnz == 0`` falls back to an even row
    split so shard shapes stay ~m/P instead of one band swallowing every
    row (the balanced-band math puts all of a zero-nnz matrix in the last
    band, which used to inflate ``rows_per_shard`` to m).
    """
    _check_devices(num_devices)
    m, n = coo.shape
    rows = np.asarray(coo.rows)
    cols = np.asarray(coo.cols)
    vals = np.asarray(coo.data)
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    row_ptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=m), out=row_ptr[1:])
    if rows.size:
        bands = balanced_row_bands(row_ptr, num_devices)
    else:
        bands = ((np.arange(num_devices + 1, dtype=np.int64) * m)
                 // num_devices).astype(np.int32)
    nnz_start = row_ptr[bands]
    nnz_per = np.diff(nnz_start)
    nnz_pad = max(int(nnz_per.max()) if nnz_per.size else 1, 1)
    rows_per = max(int(np.diff(bands).max()) if m else 1, 1)

    R = np.zeros((num_devices, nnz_pad), np.int32)
    C = np.zeros((num_devices, nnz_pad), np.int32)
    V = np.zeros((num_devices, nnz_pad), vals.dtype)
    for p in range(num_devices):
        a, b = int(nnz_start[p]), int(nnz_start[p + 1])
        ln = b - a
        R[p, :ln] = rows[a:b] - bands[p]       # local row ids
        C[p, :ln] = cols[a:b]
        V[p, :ln] = vals[a:b]
    return ShardedCOO(jnp.asarray(R), jnp.asarray(C), jnp.asarray(V),
                      jnp.asarray(bands[:-1].astype(np.int32)),
                      (m, n), rows_per)


def partition_nnz(coo: COO, num_devices: int) -> ShardedCOO:
    """Merge-style equal-nnz spans (rows may straddle devices).

    ``num_devices > nnz`` (empty spans) and ``nnz == 0`` produce zero-filled
    shards whose padded entries target local row 0 with value 0 — harmless
    under the scatter-add, and ``span_rows`` is clamped to ≥ 1 so shard
    buffers never collapse to zero-size."""
    _check_devices(num_devices)
    m, n = coo.shape
    rows = np.asarray(coo.rows)
    cols = np.asarray(coo.cols)
    vals = np.asarray(coo.data)
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    nnz = rows.size
    bounds = (np.arange(num_devices + 1, dtype=np.int64) * nnz
              ) // num_devices
    nnz_pad = max(int(np.diff(bounds).max()), 1)
    R = np.zeros((num_devices, nnz_pad), np.int32)
    C = np.zeros((num_devices, nnz_pad), np.int32)
    V = np.zeros((num_devices, nnz_pad), vals.dtype)
    offs = np.zeros(num_devices, np.int32)
    for p in range(num_devices):
        a, b = int(bounds[p]), int(bounds[p + 1])
        ln = b - a
        if ln:
            offs[p] = rows[a]
            R[p, :ln] = rows[a:b] - rows[a]
            C[p, :ln] = cols[a:b]
            V[p, :ln] = vals[a:b]
    # padded entries: vals 0 at local row 0 — harmless
    span_rows = max(int((R.max(axis=1) + 1).max()) if nnz else 1, 1)
    return ShardedCOO(jnp.asarray(R), jnp.asarray(C), jnp.asarray(V),
                      jnp.asarray(offs), (m, n), span_rows)


def _as_2d(x: jax.Array):
    """[n] or [n, k] — SpMV rides along as the k = 1 column."""
    if x.ndim == 1:
        return x[:, None], True
    if x.ndim != 2:
        raise ValueError(f"x must be [n] or [n, k], got shape {x.shape}")
    return x, False


def spmv_row_distributed(sharded: ShardedCOO, x: jax.Array, mesh: Mesh,
                         axis: str = "data") -> jax.Array:
    """Y = A @ X with BCOH row banding: X replicated, Y shard-local.
    ``x`` may be ``[n]`` (SpMV) or ``[n, k]`` (multi-RHS)."""
    m, n = sharded.shape
    ndev = sharded.rows.shape[0]
    if ndev != mesh.shape[axis]:
        raise ValueError(f"matrix is partitioned over {ndev} devices but "
                         f"mesh axis {axis!r} has {mesh.shape[axis]}")
    rp = sharded.rows_per_shard
    x2, squeeze = _as_2d(x)
    k = x2.shape[1]

    def local(rows, cols, vals, x_rep):
        # rows/cols/vals: [1, nnz_pad] local shard; X replicated [n, k]
        y_loc = jnp.zeros((1, rp, k), vals.dtype)
        contrib = vals[0][:, None] * x_rep[cols[0]]          # [nnz_pad, k]
        return y_loc.at[0, rows[0]].add(contrib)

    yb = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(None, None)),
        out_specs=P(axis, None, None))(
            sharded.rows, sharded.cols, sharded.vals, x2)
    # reassemble: band p covers global rows [row_offset[p], +rows_in_band);
    # rows past a band's end scatter to the dump row m (dropped below)
    idx = sharded.row_offset[:, None] + jnp.arange(rp, dtype=jnp.int32)[None]
    valid_len = jnp.concatenate(
        [sharded.row_offset[1:], jnp.array([m], jnp.int32)]
    ) - sharded.row_offset
    mask = jnp.arange(rp, dtype=jnp.int32)[None] < valid_len[:, None]
    y = jnp.zeros((m + 1, k), yb.dtype).at[jnp.where(mask, idx, m)].add(
        jnp.where(mask[..., None], yb, 0))[:m]
    return y[:, 0] if squeeze else y


def spmv_merge_distributed(sharded: ShardedCOO, x: jax.Array, mesh: Mesh,
                           axis: str = "data") -> jax.Array:
    """Y = A @ X with merge spans: per-device partials + psum fixup.
    ``x`` may be ``[n]`` (SpMV) or ``[n, k]`` (multi-RHS)."""
    m, n = sharded.shape
    ndev = sharded.rows.shape[0]
    if ndev != mesh.shape[axis]:
        raise ValueError(f"matrix is partitioned over {ndev} devices but "
                         f"mesh axis {axis!r} has {mesh.shape[axis]}")
    x2, squeeze = _as_2d(x)

    def local(rows, cols, vals, offs, x_rep):
        contrib = vals[0][:, None] * x_rep[cols[0]]          # [nnz_pad, k]
        # scatter directly at global rows (offs + local row); padded entries
        # carry vals == 0 so they add nothing. One psum = the cross-device
        # carry-out fixup.
        y_loc = jnp.zeros((m, x_rep.shape[1]), vals.dtype
                          ).at[offs[0] + rows[0]].add(contrib)
        return jax.lax.psum(y_loc, axis)

    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis),
                  P(None, None)),
        out_specs=P(None, None))(
            sharded.rows, sharded.cols, sharded.vals,
            sharded.row_offset[:, None], x2)
    return y[:, 0] if squeeze else y
