"""Pallas TPU kernel: blocked SpMV over TiledSparse (8x128 mini-tiles).

Grid = batches of TB mini-tiles. Scalar-prefetched (tile_rows, tile_cols)
drive dynamic VMEM addressing; x and y are VMEM-resident (the paper's
"x/y region fits in L2" precondition, Eq. 3.1, promoted to VMEM — the
selector only routes matrices here when 4*(m+n) fits the VMEM budget).

Per mini-tile the body does a dense (8,128)@(128,) matvec and accumulates
into y at a dynamic sublane offset — no scatter, no gather, MXU/VPU only.
The tile *visit order* (row / Morton / Hilbert, per paper algorithm) is
preserved from conversion; on hardware it controls VREG/VMEM locality, and
we report it via TiledSparse.window_switches() in the benchmarks.

The grid dimension is declared "arbitrary" (sequential) because every step
accumulates into the same y buffer — the same discipline the paper needs for
false-sharing avoidance, transplanted to megacore semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import TILE_C, TILE_R, TiledSparse, interpret_only

DEFAULT_TILES_PER_STEP = 8


def _kernel(tile_rows_ref, tile_cols_ref,   # scalar prefetch (SMEM)
            tiles_ref, x_ref,               # VMEM in
            y_ref,                          # VMEM out (revisited every step)
            *, tiles_per_step: int):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(t, _):
        idx = g * tiles_per_step + t
        r = tile_rows_ref[idx]
        c = tile_cols_ref[idx]
        tile = tiles_ref[t]                                    # (8, 128)
        xs = x_ref[pl.ds(c * TILE_C, TILE_C)]                  # (128,)
        upd = jax.lax.dot_general(
            tile, xs.astype(tile.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (8,)
        cur = y_ref[pl.ds(r * TILE_R, TILE_R)]
        y_ref[pl.ds(r * TILE_R, TILE_R)] = cur + upd
        return _

    jax.lax.fori_loop(0, tiles_per_step, body, None)


@functools.partial(jax.jit, static_argnames=("tiles_per_step", "interpret"))
def bsr_spmv(ts: TiledSparse, x: jax.Array, *,
             tiles_per_step: int = DEFAULT_TILES_PER_STEP,
             interpret: bool = False) -> jax.Array:
    """y = A @ x for A in TiledSparse form. Returns f32[m]."""
    interpret_only("bsr_spmv", interpret)
    m, n = ts.shape
    mp, np_ = ts.padded_shape()
    T = ts.num_tiles
    TB = tiles_per_step
    T_pad = -(-T // TB) * TB

    tiles = ts.tiles
    tile_rows = ts.tile_rows
    tile_cols = ts.tile_cols
    if T_pad != T:
        pad = T_pad - T
        tiles = jnp.concatenate(
            [tiles, jnp.zeros((pad,) + tiles.shape[1:], tiles.dtype)])
        # padding tiles are all-zero; point them at row/col 0 harmlessly
        tile_rows = jnp.concatenate(
            [tile_rows, jnp.zeros((pad,), tile_rows.dtype)])
        tile_cols = jnp.concatenate(
            [tile_cols, jnp.zeros((pad,), tile_cols.dtype)])

    x_pad = jnp.zeros((np_,), x.dtype).at[:n].set(x)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T_pad // TB,),
        in_specs=[
            pl.BlockSpec((TB, TILE_R, TILE_C), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((np_,), lambda g, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((mp,), lambda g, *_: (0,)),
    )
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    y = pl.pallas_call(
        functools.partial(_kernel, tiles_per_step=TB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp,), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(tile_rows, tile_cols, tiles, x_pad)
    return y[:m]


def _kernel_spmm(tile_rows_ref, tile_cols_ref, tiles_ref, x_ref, y_ref, *,
                 tiles_per_step: int):
    """Multi-RHS variant: x [n_pad, R], y [m_pad, R]."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(t, _):
        idx = g * tiles_per_step + t
        r = tile_rows_ref[idx]
        c = tile_cols_ref[idx]
        tile = tiles_ref[t]                                    # (8, 128)
        xs = x_ref[pl.ds(c * TILE_C, TILE_C), :]               # (128, R)
        upd = jax.lax.dot_general(
            tile, xs.astype(tile.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (8, R)
        cur = y_ref[pl.ds(r * TILE_R, TILE_R), :]
        y_ref[pl.ds(r * TILE_R, TILE_R), :] = cur + upd
        return _

    jax.lax.fori_loop(0, tiles_per_step, body, None)


@functools.partial(jax.jit, static_argnames=("tiles_per_step", "interpret"))
def bsr_spmm(ts: TiledSparse, x: jax.Array, *,
             tiles_per_step: int = DEFAULT_TILES_PER_STEP,
             interpret: bool = False) -> jax.Array:
    """Y = A @ X for X [n, R] (multi-RHS: iterative solver blocks, GNN
    feature matrices). Same tile stream as bsr_spmv; the MXU matvec becomes
    a (8,128)@(128,R) matmul — arithmetic intensity grows R-fold, which is
    exactly why SpMM is the preferred form on TPU (DESIGN §2)."""
    interpret_only("bsr_spmm", interpret)
    m, n = ts.shape
    mp, np_ = ts.padded_shape()
    R = x.shape[1]
    T = ts.num_tiles
    TB = tiles_per_step
    T_pad = -(-T // TB) * TB

    tiles, tile_rows, tile_cols = ts.tiles, ts.tile_rows, ts.tile_cols
    if T_pad != T:
        pad = T_pad - T
        tiles = jnp.concatenate(
            [tiles, jnp.zeros((pad,) + tiles.shape[1:], tiles.dtype)])
        tile_rows = jnp.concatenate(
            [tile_rows, jnp.zeros((pad,), tile_rows.dtype)])
        tile_cols = jnp.concatenate(
            [tile_cols, jnp.zeros((pad,), tile_cols.dtype)])
    x_pad = jnp.zeros((np_, R), x.dtype).at[:n].set(x)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T_pad // TB,),
        in_specs=[
            pl.BlockSpec((TB, TILE_R, TILE_C), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((np_, R), lambda g, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((mp, R), lambda g, *_: (0, 0)),
    )
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    y = pl.pallas_call(
        functools.partial(_kernel_spmm, tiles_per_step=TB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, R), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(tile_rows, tile_cols, tiles, x_pad)
    return y[:m]
