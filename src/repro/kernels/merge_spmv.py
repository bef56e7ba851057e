"""Pallas TPU kernel: merge-path SpMV on flat CSR (paper §3.3).

Merrill & Garland's algorithm cuts the merge path over (row ends, nonzeros)
into P equal-diagonal spans, so every program does exactly the same number of
(FMA | row-close) operations — perfect load balance for any row distribution,
including the mawi single-dense-row pathology.

TPU adaptation (DESIGN §2): the binary searches and the row walk move to
*convert time* (merge_plan below) — each span becomes a fixed-shape record
(cols, vals, seg) of D nonzeros with its local row offsets seg. In-kernel,
the per-row reduction is a one-hot matmul (D x R) — MXU work instead of a
scatter. Each program writes its partial rows to its own output slab; the
paper's sequential carry-out fixup becomes a jnp scatter-add epilogue over
the (P, R) partials (ops.merge_spmv).

The only irregular memory op left is the x-gather (x[cols]) from a
VMEM-resident x. Mosaic refuses both that in-kernel gather and the (1, D)
span blocks, so the kernel runs in interpret mode only (``interpret_only``);
the TPU path is SELL-C-σ.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.formats import CSR
from repro.core.mergepath import merge_path_partition_np
from .tiling import interpret_only


def default_num_spans(m: int, nnz: int) -> int:
    """Span-count heuristic shared by the SpMV and SpMM merge paths: one
    span per ~4096 merge items, clamped to [8, 1024]."""
    return max(min((m + nnz) // 4096, 1024), 8)


def carry_out_fixup(partials: jax.Array, row_starts: jax.Array,
                    m: int) -> jax.Array:
    """The paper's sequential carry-out fixup as one scatter-add: place each
    span's local rows at its row_start offset (span boundaries overlap by
    <= 1 row, which the add resolves). ``partials`` is (P, R) for SpMV or
    (P, R, K) for SpMM; returns (m,) / (m, K)."""
    R = partials.shape[1]
    idx = row_starts[:-1, None] + jnp.arange(R, dtype=jnp.int32)[None]
    y = jnp.zeros((m + R,) + partials.shape[2:], jnp.float32)
    return y.at[idx].add(partials)[:m]


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


def _kernel(cols_ref, vals_ref, seg_ref, x_ref, out_ref, *, r_width: int):
    cols = cols_ref[0]                       # (D,) int32
    vals = vals_ref[0].astype(jnp.float32)   # (D,)
    seg = seg_ref[0]                         # (D,) int32
    xs = jnp.take(x_ref[...], cols, axis=0,
                  mode="clip").astype(jnp.float32)       # VMEM gather
    prod = vals * xs                                      # (D,)
    # one-hot (D, R) matmul replaces the scatter — MXU-native reduction
    onehot = (seg[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, r_width), 1)
              ).astype(jnp.float32)                       # (D, R)
    out_ref[0] = jax.lax.dot_general(
        prod, onehot, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (R,)


@functools.partial(jax.jit, static_argnames=("r_width", "interpret"))
def merge_spmv_partials(plan_cols, plan_vals, plan_seg, x_pad, *,
                        r_width: int, interpret: bool = False):
    interpret_only("merge_spmv", interpret)
    P, D = plan_cols.shape
    np_ = x_pad.shape[0]
    grid_spec = pl.GridSpec(
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, D), lambda p: (p, 0)),
            pl.BlockSpec((1, D), lambda p: (p, 0)),
            pl.BlockSpec((1, D), lambda p: (p, 0)),
            pl.BlockSpec((np_,), lambda p: (0,)),
        ],
        out_specs=pl.BlockSpec((1, r_width), lambda p: (p, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, r_width=r_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, r_width), jnp.float32),
        interpret=interpret,
    )(plan_cols, plan_vals, plan_seg, x_pad)
