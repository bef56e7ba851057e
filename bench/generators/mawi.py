"""A MAWI-class traffic matrix: one hub vertex beside a Graph500 graph,
as a symmetric adjacency matrix made on the device.

The SuiteSparse MAWI matrices are undirected IP-to-IP graphs of one
15-minute MAWI/WIDE backbone trace, where one address talks to most of
the others. Here the non-hub part is ``kron.py``'s graph (``edges``
Kronecker edges with quadrant probabilities a, b, c, vertex labels
shuffled by a permutation drawn from the key, self-loops removed), and
one hub vertex, drawn from the key, is joined to each other vertex with
probability ``hub_p``. The union is made undirected with duplicate edges
merged; each undirected edge carries one weight, uniform in [0, 1),
stored at both (u, v) and (v, u); the triplets come out in row-major
order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators.kron import _graph as _kron_graph
from bench.generators.triplets import Triplets


@functools.partial(jax.jit, static_argnames=("scale", "edges", "hub_p",
                                             "a", "b", "c"))
def _graph(key, *, scale: int, edges: int, hub_p: float, a: float,
           b: float, c: float):
    """Row-major (row, col, weight) of the symmetric adjacency, packed to
    the front of arrays of ``2 * (2 * edges + 2**scale)``, and how many
    there are."""
    kkron, khub, kjoin, kvals = jax.random.split(key, 4)
    n = 1 << scale
    r, cc, w, _ = _kron_graph(kkron, scale=scale, edges=edges, a=a, b=b,
                              c=c)
    # kron's graph holds each undirected edge as (lo, hi) and (hi, lo),
    # its padding as rows n: keep (lo, hi) with the weight it drew
    upper = r < cc
    lo = jnp.where(upper, r, n)
    hi = jnp.where(upper, cc, n)
    hub = jax.random.randint(khub, (), 0, n, jnp.int32)
    v = jnp.arange(n, dtype=jnp.int32)
    joined = (jax.random.uniform(kjoin, (n,)) < hub_p) & (v != hub)
    lo = jnp.concatenate([lo, jnp.where(joined, jnp.minimum(v, hub), n)])
    hi = jnp.concatenate([hi, jnp.where(joined, jnp.maximum(v, hub), n)])
    w = jnp.concatenate([w, jax.random.uniform(kvals, (n,), jnp.float32)])
    # an edge both graphs hold keeps the smaller of its two weights
    lo, hi, w = jax.lax.sort((lo, hi, w), num_keys=3)
    keep = (lo < n) & jnp.concatenate([
        jnp.ones((1,), bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    r = jnp.concatenate([jnp.where(keep, lo, n), jnp.where(keep, hi, n)])
    cc = jnp.concatenate([hi, lo])
    r, cc, ww = jax.lax.sort((r, cc, jnp.concatenate([w, w])), num_keys=2)
    return r, cc, ww, 2 * jnp.sum(keep)


def generate(cfg: dict, key) -> Triplets:
    scale = int(cfg["scale"])
    n = 1 << scale
    rows, cols, vals, count = _graph(
        key, scale=scale, edges=int(cfg["edges"]),
        hub_p=float(cfg["hub_p"]), a=float(cfg["a"]), b=float(cfg["b"]),
        c=float(cfg["c"]))
    count = int(count)
    rows, cols, vals = jax.device_get((rows, cols, vals))
    return Triplets(np.asarray(rows[:count]), np.asarray(cols[:count]),
                    np.asarray(vals[:count]), (n, n))
