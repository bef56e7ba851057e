"""repro.spmm — the multi-RHS SpMM engine (``Y = A @ X``, ``X: [n, k]``).

Layers (one module each):

  ``sellcs``     SELL-C-σ storage: lane-height slices, σ-window row sorting
  ``reference``  pure-jnp oracles per format (the XLA fallback path)
  ``kernels``    the SELL-C-σ Pallas kernels (forward, VMEM-resident
                 k = 1, transpose), each with a k-tile grid dimension
  ``batching``   request batching for the serve path (k SpMVs -> 1 SpMM)
  ``distributed``  shard_map schedules over a mesh (row bands / merge spans)
  ``operator``   SparseOperator: the stable partition-once/multiply-many
                 handle with an atomic plan swap (online format migration)
  ``fleet``      Fleet: multi-tenant operator registry — fingerprint-keyed
                 plan cache, device-loss re-deal onto the survivors

SpMV is the k = 1 special case throughout. ``spmm`` is the one dispatcher
that picks a Pallas kernel; ``repro.core.spmv.spmv`` keeps the paper's
per-format references and sends its Pallas impls here.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.formats import COO, CSR, BlockedSparse
from . import reference
from .batching import (FleetBatcher, QueueFull, RequestBatcher,
                       SpmvRequest, batch_spmv)
from .distributed import (ShardedSellCS, partition_sellcs_nnz,
                          partition_sellcs_rows, rechunk_sellcs,
                          redeal_sellcs, spmm_merge_distributed,
                          spmm_row_distributed)
from .kernels import choose_k_tile, resolve_impl, sellcs_spmm
from .operator import (OperatorStats, RealizedPlan, SparseOperator,
                       TransposedOperator, coo_fingerprint, sparse_matmul)
from .fleet import Fleet, FleetStats
from .reference import (spmm_blocked, spmm_coo, spmm_coo_t, spmm_csr,
                        spmm_ref, spmm_sellcs, spmm_sellcs_t)
from .sellcs import SellCS, coo_to_sellcs


def spmm(mat, x: jax.Array, *, impl: str = "auto",
         k_tile: Optional[int] = None, op: str = "N") -> jax.Array:
    """Multiply ``Y = A @ X`` for any supported format; ``x`` may be
    ``[n]`` (SpMV, the k = 1 column) or ``[n, k]``.

    impl in {"auto", "ref", "pallas", "pallas_interpret"}: "auto" takes
    the Pallas path on TPU where a kernel lowers
    (``kernels.resolve_impl``), the XLA reference otherwise. The Pallas
    kernels are the SELL-C-σ family, so the Pallas impls take a
    ``SellCS`` only and raise ``TypeError`` for any other format.
    ``core.spmv.spmv`` delegates its Pallas impls here.

    ``op='T'`` computes ``Y = A^T X`` over the same stored stream
    (``X: [m, k]``, ``Y: [n, k]``); the Pallas path supports it on
    SELL-C-σ (the scatter-accumulate transpose kernel), the reference
    path on SELL-C-σ and COO. A symmetric one-triangle SELL-C-σ matrix
    accepts either op (``A^T == A``).
    """
    if op not in ("N", "T"):
        raise ValueError(f"op must be 'N' or 'T', got {op!r}")
    impl = resolve_impl(impl, mat, op)
    if impl in ("pallas", "pallas_interpret"):
        if not isinstance(mat, SellCS):
            what = "transpose SpMM" if op == "T" else "SpMM"
            raise TypeError(
                f"no {what} kernel for {type(mat).__name__}; convert with "
                "coo_to_sellcs, or use impl='ref'")
        x2 = x[:, None] if x.ndim == 1 else x
        y = sellcs_spmm(mat, x2, k_tile=k_tile,
                        interpret=impl == "pallas_interpret", op=op)
        return y[:, 0] if x.ndim == 1 else y
    return spmm_ref(mat, x, op=op)


__all__ = [
    "SellCS", "coo_to_sellcs", "spmm", "choose_k_tile",
    "sellcs_spmm",
    "spmm_ref", "spmm_coo", "spmm_csr", "spmm_blocked", "spmm_sellcs",
    "spmm_sellcs_t", "spmm_coo_t",
    "RequestBatcher", "FleetBatcher", "QueueFull", "SpmvRequest",
    "batch_spmv", "reference",
    "ShardedSellCS", "partition_sellcs_rows", "partition_sellcs_nnz",
    "rechunk_sellcs", "redeal_sellcs",
    "spmm_row_distributed", "spmm_merge_distributed",
    "SparseOperator", "TransposedOperator", "RealizedPlan",
    "OperatorStats", "coo_fingerprint", "sparse_matmul",
    "Fleet", "FleetStats",
    "COO", "CSR", "BlockedSparse",
]
