"""Pure-jnp SpMM oracles (``Y = A @ X``, ``X: [n, k]``).

These are the correctness baselines for every format's multi-RHS multiply
and the XLA fallback the dispatcher uses off-TPU. Each is the column-wise
generalization of the corresponding ``repro.core.spmv`` oracle: SpMV is
exactly the ``k = 1`` column of each of these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import COO, CSR, BlockedSparse
from .sellcs import SellCS

Array = jax.Array


def _as_2d(x: Array):
    """Return (X_2d, was_1d): SpMV inputs ride along as k = 1."""
    if x.ndim == 1:
        return x[:, None], True
    if x.ndim != 2:
        raise ValueError(f"X must be [n] or [n, k], got shape {x.shape}")
    return x, False


@jax.jit
def spmm_coo(coo: COO, x: Array) -> Array:
    x2, squeeze = _as_2d(x)
    m, _ = coo.shape
    k = x2.shape[1]
    dtype = jnp.promote_types(coo.data.dtype, x2.dtype)
    y = jnp.zeros((m, k), dtype)
    if coo.nnz:
        y = y.at[coo.rows].add(coo.data[:, None] * x2[coo.cols])
    return y[:, 0] if squeeze else y


@jax.jit
def spmm_csr(csr: CSR, x: Array) -> Array:
    x2, squeeze = _as_2d(x)
    m, _ = csr.shape
    k = x2.shape[1]
    dtype = jnp.promote_types(csr.data.dtype, x2.dtype)
    if csr.nnz == 0:
        y = jnp.zeros((m, k), dtype)
        return y[:, 0] if squeeze else y
    rows = csr.row_of_nnz()
    prod = csr.data[:, None] * x2[csr.col_ind]
    y = jax.ops.segment_sum(prod, rows, num_segments=m).astype(dtype)
    return y[:, 0] if squeeze else y


@jax.jit
def spmm_blocked(bs: BlockedSparse, x: Array) -> Array:
    x2, squeeze = _as_2d(x)
    m, _ = bs.shape
    k = x2.shape[1]
    dtype = jnp.promote_types(bs.data.dtype, x2.dtype)
    if bs.nnz == 0:
        y = jnp.zeros((m, k), dtype)
        return y[:, 0] if squeeze else y
    bid = bs.block_of_nnz()
    lr, lc = bs.local_rows_cols()
    rows = bs.block_rows[bid] * bs.beta + lr
    cols = bs.block_cols[bid] * bs.beta + lc
    prod = bs.data[:, None] * x2[cols]
    y = jax.ops.segment_sum(prod, rows, num_segments=m).astype(dtype)
    return y[:, 0] if squeeze else y


def sellcs_slots_ref(data: Array, cols: Array, slice_of: Array, x2: Array,
                     *, num_slices: int, chunk: int) -> Array:
    """Raw-array slot accumulation [num_slices*chunk, k] — the jnp twin of
    ``repro.spmm.kernels.sellcs_slots`` and the XLA body of the distributed
    schedules. No row permutation is applied."""
    dtype = jnp.promote_types(data.dtype, x2.dtype)
    k = x2.shape[1]
    xs = x2[cols]                                       # [W, C, k]
    contrib = data[:, :, None] * xs                     # [W, C, k]
    slot = (slice_of[:, None] * chunk
            + jnp.arange(chunk, dtype=jnp.int32)[None])  # [W, C]
    return jnp.zeros((num_slices * chunk, k), dtype).at[slot].add(contrib)


def sellcs_slots_chunk_ref(data: Array, cols: Array, slice_of: Array,
                           x2: Array, *, slice_start: int, num_slices: int,
                           chunk: int) -> Array:
    """jnp twin of ``kernels.sellcs_slots_chunk``: slot accumulation over a
    chunk sub-stream whose ``slice_of`` is still global, rebased to the
    chunk-local slot space starting at ``slice_start``."""
    local = jnp.clip(slice_of.astype(jnp.int32) - slice_start, 0,
                     max(num_slices - 1, 0))
    return sellcs_slots_ref(data, cols, local, x2, num_slices=num_slices,
                            chunk=chunk)


def sellcs_slot_x(row_perm: Array, x2: Array, m: int) -> Array:
    """Permute X into slot space for the transpose pass: ``x_slots[s] =
    X[row_perm[s]]``, with padding slots (``row_perm == m``) reading a zero
    row. After this gather the transpose kernel's X reads are contiguous
    C-blocks — the structured access moves from X to the output scatter."""
    x_pad = jnp.concatenate(
        [x2, jnp.zeros((1, x2.shape[1]), x2.dtype)], axis=0)
    return x_pad[row_perm]


def sellcs_slots_t_ref(data: Array, cols: Array, slice_of: Array,
                       x_slots: Array, *, n_out: int, chunk: int) -> Array:
    """Transpose slot pass [n_out, k] — the jnp twin of
    ``kernels.sellcs_slots_t``: each width-row reads its C-block of the
    slot-permuted X and scatter-accumulates into per-column slots. Output
    is in natural column order — the σ-permutation was consumed by the
    ``sellcs_slot_x`` gather, so no unpermute follows. Padding entries
    carry data == 0, cols == 0 (a harmless add into column 0). ``slice_of``
    must index the slot space ``x_slots`` was built over (globalize local
    slice ids before calling)."""
    dtype = jnp.promote_types(data.dtype, x_slots.dtype)
    k = x_slots.shape[1]
    slot = (slice_of[:, None] * chunk
            + jnp.arange(chunk, dtype=jnp.int32)[None])  # [W, C]
    contrib = data[:, :, None] * x_slots[slot]           # [W, C, k]
    return jnp.zeros((n_out, k), dtype).at[cols].add(contrib)


@jax.jit
def spmm_sellcs(sc: SellCS, x: Array) -> Array:
    """Slice-structured SpMM: one gather + FMA per width-row, then a single
    permutation scatter back to original row order. Padding entries carry
    data == 0, cols == 0 — they contribute nothing. Symmetric one-triangle
    storage combines the normal and transpose passes over the stored
    triangle: ``A X = N(X) + T(X) - diag * X``."""
    x2, squeeze = _as_2d(x)
    m, n = sc.shape
    k = x2.shape[1]
    dtype = jnp.promote_types(sc.data.dtype, x2.dtype)
    if sc.nnz == 0 or sc.data.shape[0] == 0:
        # nnz == 0 stores no diagonal either: the zero answer is exact
        y = jnp.zeros((m, k), dtype)
        return y[:, 0] if squeeze else y
    y_slots = sellcs_slots_ref(sc.data, sc.cols, sc.slice_of, x2,
                               num_slices=sc.num_slices, chunk=sc.chunk)
    # undo the σ-sort permutation; padding slots scatter to row m (dropped)
    y = jnp.zeros((m + 1, k), dtype).at[sc.row_perm].add(y_slots)
    y = y[:m]
    if sc.structure == "symmetric":
        xs = sellcs_slot_x(sc.row_perm, x2, m)
        y = (y + sellcs_slots_t_ref(sc.data, sc.cols, sc.slice_of, xs,
                                    n_out=n, chunk=sc.chunk)
             - sc.diag[:, None] * x2)
    return y[:, 0] if squeeze else y


@jax.jit
def spmm_sellcs_t(sc: SellCS, x: Array) -> Array:
    """``Y = A^T X`` over the same stored stream (``X: [m, k]``,
    ``Y: [n, k]``). For symmetric storage ``A^T == A``, so this is exactly
    the symmetric forward multiply."""
    if sc.structure == "symmetric":
        return spmm_sellcs(sc, x)
    x2, squeeze = _as_2d(x)
    m, n = sc.shape
    k = x2.shape[1]
    dtype = jnp.promote_types(sc.data.dtype, x2.dtype)
    if sc.nnz == 0 or sc.data.shape[0] == 0:
        y = jnp.zeros((n, k), dtype)
        return y[:, 0] if squeeze else y
    xs = sellcs_slot_x(sc.row_perm, x2, m)
    y = sellcs_slots_t_ref(sc.data, sc.cols, sc.slice_of, xs,
                           n_out=n, chunk=sc.chunk)
    return y[:, 0] if squeeze else y


def spmm_coo_t(coo: COO, x: Array) -> Array:
    """``Y = A^T X`` oracle on triplets (the transpose is a relabeling)."""
    m, n = coo.shape
    return spmm_coo(COO(coo.cols, coo.rows, coo.data, (n, m)), x)


def spmm_ref(mat, x: Array, *, op: str = "N") -> Array:
    """Oracle dispatch over every supported storage format. ``op='T'``
    computes ``A^T X`` (supported for SellCS and COO)."""
    from repro.kernels.ref import bsr_spmm_ref
    from repro.kernels.tiling import TiledSparse
    if op not in ("N", "T"):
        raise ValueError(f"op must be 'N' or 'T', got {op!r}")
    if op == "T":
        if isinstance(mat, SellCS):
            return spmm_sellcs_t(mat, x)
        if isinstance(mat, COO):
            return spmm_coo_t(mat, x)
        raise TypeError(
            f"no transpose SpMM oracle for {type(mat).__name__}")
    if isinstance(mat, TiledSparse):
        x2, squeeze = _as_2d(x)
        y = bsr_spmm_ref(mat, x2)
        return y[:, 0] if squeeze else y
    if isinstance(mat, SellCS):
        return spmm_sellcs(mat, x)
    if isinstance(mat, COO):
        return spmm_coo(mat, x)
    if isinstance(mat, CSR):
        return spmm_csr(mat, x)
    if isinstance(mat, BlockedSparse):
        return spmm_blocked(mat, x)
    raise TypeError(f"no SpMM oracle for {type(mat).__name__}")
