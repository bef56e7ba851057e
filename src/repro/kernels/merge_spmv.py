"""Merge-path planning on flat CSR (paper §3.3).

Merrill & Garland's algorithm cuts the merge path over (row ends, nonzeros)
into P equal-diagonal spans, so every span does exactly the same number of
(FMA | row-close) operations — perfect load balance for any row distribution,
including the mawi single-dense-row pathology.

The binary searches and the row walk happen at *convert time*
(``merge_plan``): each span becomes a fixed-shape record (cols, vals, seg)
of D nonzeros with its local row offsets seg. ``ref.merge_spmv_xla``
multiplies from that plan in XLA, with the paper's sequential carry-out
fixup as one scatter-add; the Pallas path on the chip is SELL-C-σ
(``repro.spmm``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSR
from repro.core.mergepath import merge_path_partition_np


class MergePlan(NamedTuple):
    cols: jax.Array        # int32[P, D]
    vals: jax.Array        # f32[P, D]
    seg: jax.Array         # int32[P, D] — row index local to the span
    row_starts: jax.Array  # int32[P+1]
    r_width: int           # R — padded local row width (static)


def merge_plan(csr: CSR, num_spans: int) -> MergePlan:
    """Convert-time planning: equal-diagonal merge spans -> fixed-shape
    per-span records."""
    row_ptr = np.asarray(csr.row_ptr, np.int64)
    col_ind = np.asarray(csr.col_ind)
    data = np.asarray(csr.data)
    m = row_ptr.shape[0] - 1
    nnz = int(row_ptr[-1])
    P = num_spans
    D = max(-(-(m + nnz) // P), 1)
    R = max(-(-(D + 1) // 128) * 128, 128)

    row_starts, nnz_starts = merge_path_partition_np(row_ptr, P)
    row_of_nnz = (np.searchsorted(row_ptr, np.arange(nnz), side="right") - 1
                  ).astype(np.int64) if nnz else np.zeros(0, np.int64)

    cols = np.zeros((P, D), np.int32)
    vals = np.zeros((P, D), data.dtype if data.size else np.float32)
    seg = np.zeros((P, D), np.int32)
    for p in range(P):
        j0, j1 = int(nnz_starts[p]), int(nnz_starts[p + 1])
        ln = j1 - j0
        if ln == 0:
            continue
        cols[p, :ln] = col_ind[j0:j1]
        vals[p, :ln] = data[j0:j1]
        seg[p, :ln] = row_of_nnz[j0:j1] - row_starts[p]
    return MergePlan(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(seg),
                     jnp.asarray(np.asarray(row_starts, np.int32)), int(R))
