"""Pallas TPU kernel: grouped (expert-blocked) GEMM for MoE dispatch.

The paper's load-balancing insight applied to the LM stack: the token->expert
assignment is an unstructured sparse matrix whose "row lengths" (tokens per
expert) are as skewed as a power-law graph's degrees. We sort tokens by
expert (convert step == the paper's conversion phase), pad each group to the
M-tile, and run one GEMM whose m-tiles carry a scalar-prefetched expert id
that selects the weight block — MegaBlocks-style block-sparse compute, with
the paper's uniform-work-quantum balancing (every m-tile costs the same).

grid = (m_tiles, n_tiles, k_tiles), k innermost ("arbitrary"); the output
block is revisited across k and accumulated in VMEM (f32), written once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


M_TILE, N_TILE, K_TILE = 128, 128, 128


def _kernel(tile_expert_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
            nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "out_dtype"))
def moe_group_matmul_padded(lhs: jax.Array, rhs: jax.Array,
                            tile_expert: jax.Array, *,
                            out_dtype=jnp.float32,
                            interpret: bool = False) -> jax.Array:
    """lhs f[T_pad, K] (tokens sorted by expert, group-padded to M_TILE),
    rhs f[E, K, N], tile_expert int32[T_pad // M_TILE] -> out [T_pad, N]."""
    T_pad, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2 and T_pad % M_TILE == 0
    assert K % K_TILE == 0 and N % N_TILE == 0, (K, N)
    nm, nn, nk = T_pad // M_TILE, N // N_TILE, K // K_TILE

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((M_TILE, K_TILE), lambda i, j, k, te: (i, k)),
            pl.BlockSpec((1, K_TILE, N_TILE),
                         lambda i, j, k, te: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((M_TILE, N_TILE),
                               lambda i, j, k, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((M_TILE, N_TILE), jnp.float32)],
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T_pad, N), out_dtype),
        compiler_params=params,
        interpret=interpret,
    )(tile_expert, lhs, rhs)
