"""Graph500 Kronecker graph, as its adjacency matrix, made on the device.

The Graph500 reference generator and kernel 1
(``kronecker_generator.m``, ``kernel_1.m``): ``edges`` edges, each of whose
``scale`` bits picks a quadrant with probabilities a, b, c,
d = 1 - a - b - c; the vertex labels then shuffled by a random permutation;
self-loops removed; the graph made undirected (``A + A^T``) with duplicate
edges merged. Each undirected edge carries one weight, uniform in [0, 1),
stored at both (u, v) and (v, u); the triplets come out in row-major order.
Before the shuffle and the symmetrisation the edges follow the law of
``repro.data.matrices.rmat(scale, edge_factor, seed, a, b, c)`` with
``edges = edge_factor * 2**scale``; the random stream is JAX's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators.triplets import Triplets


@functools.partial(jax.jit, static_argnames=("scale", "edges", "a", "b",
                                             "c"))
def _graph(key, *, scale: int, edges: int, a: float, b: float, c: float):
    """Row-major (row, col, weight) of the symmetric adjacency, packed to
    the front of arrays of ``2 * edges``, and how many there are."""
    kbits, kperm, kvals = jax.random.split(key, 3)
    n = 1 << scale
    right_given_bottom = c / max(1.0 - a - b, 1e-9)

    def bit(i, rc):
        rows, cols = rc
        r, q = jax.random.uniform(jax.random.fold_in(kbits, i), (2, edges))
        down = r >= a + b
        right = jnp.where(down, q >= right_given_bottom, r >= a)
        return (rows | (down.astype(jnp.int32) << i),
                cols | (right.astype(jnp.int32) << i))

    zero = jnp.zeros((edges,), jnp.int32)
    rows, cols = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    label = jax.random.permutation(kperm, n).astype(jnp.int32)
    u, v = label[rows], label[cols]
    # each undirected edge once as (lo, hi); self-loops sort last as (n, n)
    loop = u == v
    lo = jnp.where(loop, n, jnp.minimum(u, v))
    hi = jnp.where(loop, n, jnp.maximum(u, v))
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    keep = (lo < n) & jnp.concatenate([
        jnp.ones((1,), bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    w = jax.random.uniform(kvals, (edges,), jnp.float32)
    r = jnp.concatenate([jnp.where(keep, lo, n), jnp.where(keep, hi, n)])
    cc = jnp.concatenate([hi, lo])
    r, cc, ww = jax.lax.sort((r, cc, jnp.concatenate([w, w])), num_keys=2)
    return r, cc, ww, 2 * jnp.sum(keep)


def generate(cfg: dict, key) -> Triplets:
    scale = int(cfg["scale"])
    n = 1 << scale
    rows, cols, vals, count = _graph(
        key, scale=scale, edges=int(cfg["edges"]),
        a=float(cfg["a"]), b=float(cfg["b"]), c=float(cfg["c"]))
    count = int(count)
    rows, cols, vals = jax.device_get((rows, cols, vals))
    return Triplets(np.asarray(rows[:count]), np.asarray(cols[:count]),
                    np.asarray(vals[:count]), (n, n))
