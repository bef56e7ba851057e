"""SpMV multiplication algorithms (paper §2-§4) — pure-JAX reference paths.

Every storage format lowers to the same contraction y[r] += v * x[c]; what the
paper's nine algorithms change is *storage layout*, *traversal order* and
*scheduling*. On TPU the jnp implementations below are the correctness oracles
and the XLA baseline. The performance path is the SELL-C-σ Pallas family behind
`repro.spmm.spmm`, where SpMV is the k = 1 column, and the distributed path is
`repro.spmm.distributed` (shard_map).
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from .formats import BICRS, COO, CSR, ICRS, BlockedSparse

Array = jax.Array
Matrix = Union[COO, CSR, ICRS, BICRS, BlockedSparse]


# --------------------------------------------------------------------------
# references
# --------------------------------------------------------------------------
@jax.jit
def spmv_coo(coo: COO, x: Array) -> Array:
    """Triplet-format SpMV (paper §2): y[row[i]] += data[i] * x[col[i]]."""
    m, _ = coo.shape
    y = jnp.zeros((m,), jnp.promote_types(coo.data.dtype, x.dtype))
    if coo.nnz == 0:
        return y
    return y.at[coo.rows].add(coo.data * x[coo.cols])


@jax.jit
def spmv_csr(csr: CSR, x: Array) -> Array:
    """CRS SpMV (Algorithm 2.1). Row loop -> vectorized decompress + one
    segment reduction; this is what ParCRS lowers to on an accelerator."""
    m, _ = csr.shape
    dtype = jnp.promote_types(csr.data.dtype, x.dtype)
    if csr.nnz == 0:
        return jnp.zeros((m,), dtype)
    rows = csr.row_of_nnz()
    prod = csr.data * x[csr.col_ind]
    return jax.ops.segment_sum(prod, rows, num_segments=m).astype(dtype)


@jax.jit
def spmv_incremental(mat: Union[ICRS, BICRS], x: Array) -> Array:
    """Faithful Algorithm 2.2: sequential increment-decoded traversal as a
    lax.scan. This is the *oracle* for the (B)ICRS encodings — DESIGN §2.4
    explains why it is not a TPU compute path."""
    m, n = mat.shape
    dtype = jnp.promote_types(mat.data.dtype, x.dtype)
    y0 = jnp.zeros((m,), dtype)
    if mat.nnz == 0:
        return y0

    col_inc, row_jump, data = mat.col_inc, mat.row_jump, mat.data

    def step(carry, k):
        y, j, i, r = carry
        y = y.at[i].add(data[k] * x[j])
        j = j + col_inc[k]
        overflow = j >= n
        j = jnp.where(overflow, j - n, j)
        i = jnp.where(
            overflow,
            i + row_jump[jnp.minimum(r + 1, row_jump.shape[0] - 1)], i)
        r = jnp.where(overflow, r + 1, r)
        return (y, j, i, r), None

    init = (y0, mat.col_start.astype(jnp.int32),
            row_jump[0].astype(jnp.int32), jnp.int32(0))
    (y, _, _, _), _ = jax.lax.scan(
        step, init, jnp.arange(mat.nnz, dtype=jnp.int32))
    return y


@jax.jit
def spmv_blocked(bs: BlockedSparse, x: Array) -> Array:
    """Blocked-format SpMV, XLA path: decode (block, local) -> global
    coordinates, gather/FMA, segment-reduce. Traversal order (Morton/Hilbert/
    row) is preserved in storage order — XLA sees the same stream a CPU
    would."""
    m, _ = bs.shape
    dtype = jnp.promote_types(bs.data.dtype, x.dtype)
    if bs.nnz == 0:
        return jnp.zeros((m,), dtype)
    bid = bs.block_of_nnz()
    lr, lc = bs.local_rows_cols()
    rows = bs.block_rows[bid] * bs.beta + lr
    cols = bs.block_cols[bid] * bs.beta + lc
    prod = bs.data * x[cols]
    return jax.ops.segment_sum(prod, rows, num_segments=m).astype(dtype)


def spmv_dense_oracle(mat: Matrix, x: Array) -> Array:
    """Densify + matmul. Only for small test matrices."""
    coo = mat if isinstance(mat, COO) else mat.to_coo()
    return coo.todense() @ x


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------
def spmv(mat: Matrix, x: Array, impl: str = "auto") -> Array:
    """Multiply. impl in {"auto", "ref", "pallas", "pallas_interpret"}.

    "auto" uses the Pallas kernel where one lowers on the running TPU
    (``repro.spmm.kernels.resolve_impl``: SELL-C-σ), otherwise the
    per-format XLA reference below. The Pallas impls delegate to
    ``repro.spmm.spmm``, the one dispatcher that picks a kernel; it takes
    a SELL-C-σ matrix only. Imported lazily to keep the core
    dependency-light."""
    from repro.kernels.tiling import TiledSparse
    from repro.spmm.kernels import resolve_impl   # late import: core <- spmm
    from repro.spmm.sellcs import SellCS
    impl = resolve_impl(impl, mat)
    if impl in ("pallas", "pallas_interpret"):
        from repro.spmm import spmm
        return spmm(mat, x, impl=impl)
    if isinstance(mat, TiledSparse):
        from repro.kernels.ref import bsr_spmv_ref
        return bsr_spmv_ref(mat, x)
    if isinstance(mat, SellCS):
        from repro.spmm.reference import spmm_sellcs
        return spmm_sellcs(mat, x)         # [n] in -> [m] out (k=1 case)
    if isinstance(mat, COO):
        return spmv_coo(mat, x)
    if isinstance(mat, CSR):
        return spmv_csr(mat, x)
    if isinstance(mat, (ICRS, BICRS)):
        return spmv_incremental(mat, x)
    if isinstance(mat, BlockedSparse):
        return spmv_blocked(mat, x)
    raise TypeError(f"unknown matrix type {type(mat).__name__}")
