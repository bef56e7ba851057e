"""Pure-jnp references: the XLA paths of the TiledSparse blocked format
(single- and multi-RHS) and of the merge-path plan, and the oracle of the
MoE grouped GEMM kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiling import TILE_C, TILE_R, TiledSparse


@jax.jit
def bsr_spmv_ref(ts: TiledSparse, x: jax.Array) -> jax.Array:
    """TiledSparse SpMV in XLA: vmap the per-tile matvec, scatter-add
    slabs."""
    m, n = ts.shape
    mp, np_ = ts.padded_shape()
    x_pad = jnp.zeros((np_,), x.dtype).at[:n].set(x)
    xs = x_pad.reshape(np_ // TILE_C, TILE_C)[ts.tile_cols]   # (T, 128)
    contrib = jnp.einsum("trc,tc->tr", ts.tiles.astype(jnp.float32),
                         xs.astype(jnp.float32))              # (T, 8)
    y = jnp.zeros((mp // TILE_R, TILE_R), jnp.float32)
    y = y.at[ts.tile_rows].add(contrib)
    return y.reshape(mp)[:m]


def moe_group_matmul_ref(tokens: jax.Array, weights: jax.Array,
                         group_sizes: jax.Array) -> jax.Array:
    """Oracle for the grouped GEMM: tokens [T, K] sorted by expert,
    group_sizes int32[E]; weights [E, K, N] -> out [T, N]."""
    T, K = tokens.shape
    E, _, N = weights.shape
    bounds = jnp.cumsum(group_sizes)
    expert_of_token = jnp.searchsorted(bounds,
                                       jnp.arange(T, dtype=group_sizes.dtype),
                                       side="right")
    w = weights[expert_of_token]                 # (T, K, N)
    return jnp.einsum("tk,tkn->tn", tokens.astype(jnp.float32),
                      w.astype(jnp.float32))


import functools


@functools.partial(jax.jit, static_argnames=("r_width", "m"))
def merge_spmv_xla(cols, vals, seg, row_starts, x_pad: jax.Array, *,
                   r_width: int, m: int) -> jax.Array:
    """XLA realization of the merge-path algorithm from a ``MergePlan``
    (vmap over spans + segment reduction + the sequential carry-out
    fixup). Used for wall-clock algorithm sweeps on CPU (Fig 6.1
    analogue)."""
    xs = x_pad[cols]                               # [P, D] gather
    prod = vals.astype(jnp.float32) * xs.astype(jnp.float32)
    partials = jax.vmap(
        lambda pr, sg: jax.ops.segment_sum(pr, sg, num_segments=r_width)
    )(prod, seg)                                   # [P, R]
    idx = row_starts[:-1, None] + jnp.arange(r_width, dtype=jnp.int32)[None]
    y = jnp.zeros((m + r_width,), jnp.float32).at[idx].add(partials)
    return y[:m]


@jax.jit
def bsr_spmm_ref(ts: TiledSparse, x: jax.Array) -> jax.Array:
    """TiledSparse SpMM (multi-RHS) in XLA, as ``bsr_spmv_ref``."""
    m, n = ts.shape
    mp, np_ = ts.padded_shape()
    R = x.shape[1]
    x_pad = jnp.zeros((np_, R), x.dtype).at[:n].set(x)
    xs = x_pad.reshape(np_ // TILE_C, TILE_C, R)[ts.tile_cols]  # (T,128,R)
    contrib = jnp.einsum("trc,tcf->trf", ts.tiles.astype(jnp.float32),
                         xs.astype(jnp.float32))                # (T,8,R)
    y = jnp.zeros((mp // TILE_R, TILE_R, R), jnp.float32)
    y = y.at[ts.tile_rows].add(contrib)
    return y.reshape(mp, R)[:m]
