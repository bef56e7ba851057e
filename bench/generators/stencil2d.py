"""The 5-point stencil on a ``side x side`` grid, made on the device.

The same pattern and order as ``repro.data.matrices.mesh2d(side, seed)``:
for each offset in (0,0), (0,1), (0,-1), (1,0), (-1,0) the grid points
whose neighbour lies on the grid, in row-major order, concatenated; one
standard-normal value per entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators.triplets import Triplets

OFFSETS = ((0, 0), (0, 1), (0, -1), (1, 0), (-1, 0))


@functools.partial(jax.jit, static_argnames=("side",))
def _stencil(key, *, side: int):
    rows, cols = [], []
    for dr, dc in OFFSETS:
        r = jnp.arange(max(0, -dr), side - max(0, dr), dtype=jnp.int32)
        c = jnp.arange(max(0, -dc), side - max(0, dc), dtype=jnp.int32)
        rr, cc = r[:, None], c[None, :]
        rows.append((rr * side + cc).ravel())
        cols.append(((rr + dr) * side + (cc + dc)).ravel())
    rows, cols = jnp.concatenate(rows), jnp.concatenate(cols)
    vals = jax.random.normal(key, rows.shape, jnp.float32)
    return rows, cols, vals


def generate(cfg: dict, key) -> Triplets:
    side = int(cfg["side"])
    rows, cols, vals = jax.device_get(_stencil(key, side=side))
    return Triplets(np.asarray(rows), np.asarray(cols), np.asarray(vals),
                    (side * side, side * side))
