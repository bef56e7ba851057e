"""Public jit'd wrapper around the MoE grouped-GEMM Pallas kernel.

``interpret=True`` executes kernel bodies in Python on CPU (how this repo
validates them); on a TPU backend pass interpret=False for Mosaic lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import moe_group_matmul as _moe

M_TILE = _moe.M_TILE


def moe_group_matmul(tokens: jax.Array, weights: jax.Array,
                     group_sizes: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """tokens f[T, K] sorted by expert; group_sizes int32[E]; weights
    [E, K, N] -> out f32[T, N].

    Handles group padding to M_TILE internally (static worst-case padded
    length T + E*M_TILE, zero-filled rows compute zeros)."""
    T, K = tokens.shape
    E, K2, N = weights.shape
    Kp = -(-K // _moe.K_TILE) * _moe.K_TILE
    Np = -(-N // _moe.N_TILE) * _moe.N_TILE
    if Kp != K:
        tokens = jnp.pad(tokens, ((0, 0), (0, Kp - K)))
        weights = jnp.pad(weights, ((0, 0), (0, Kp - K), (0, 0)))
    if Np != N:
        weights = jnp.pad(weights, ((0, 0), (0, 0), (0, Np - N)))
    T_pad = (-(-T // M_TILE) * M_TILE) + E * M_TILE

    sizes = group_sizes.astype(jnp.int32)
    ptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    padded_sizes = -(-sizes // M_TILE) * M_TILE
    padded_ptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(padded_sizes)])

    tok_idx = jnp.arange(T, dtype=jnp.int32)
    expert_of_token = (jnp.searchsorted(ptr[1:], tok_idx, side="right")
                       ).astype(jnp.int32)
    pos = padded_ptr[expert_of_token] + (tok_idx - ptr[expert_of_token])
    lhs = jnp.zeros((T_pad, Kp), tokens.dtype).at[pos].set(tokens)

    tile_idx = jnp.arange(T_pad // M_TILE, dtype=jnp.int32)
    tile_expert = (jnp.searchsorted(padded_ptr[1:], tile_idx * M_TILE,
                                    side="right")).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, E - 1)

    out_pad = _moe.moe_group_matmul_padded(lhs, weights, tile_expert,
                                           interpret=interpret)
    return out_pad[pos, :N]
