"""The general traffic generator: drives a ``RequestBatcher`` as a traffic
mix file says.

A mix is a JSON file under ``bench/traffic/``: ``loop`` (its kind),
``clients`` and ``max_batch``. ``"closed"`` is the one kind so far: each
client is an independent chain (a PageRank, Krylov or feature-propagation
iteration) that submits one float32 vector and sends its next as soon as
its answer is ready on the device. Whenever requests are pending the loop
flushes up to ``max_batch`` of them. Each request's vector is made on the
device from the seed, the client and the request's index, so the check
after the window can make it again.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

WINDOW, WARM_UP = 0, 1      # streams of request vectors


def _vector(key, stream, client, index, *, n: int):
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, stream), client), index)
    return jax.random.normal(k, (n,), jnp.float32)


class Vectors:
    """The clients' request vectors, made on the device from a key."""

    def __init__(self, key, n: int):
        self._key = key
        self._n = n
        self._make = jax.jit(_vector, static_argnames=("n",))

    def __call__(self, stream: int, client: int, index: int) -> jax.Array:
        return self._make(self._key, stream, client, index, n=self._n)


class Reservoir:
    """A uniform sample of fixed size over everything offered (Vitter's
    algorithm R), drawn from its own seed (anything
    ``numpy.random.default_rng`` takes)."""

    def __init__(self, size: int, seed):
        self.size = size
        self.items: List = []
        self.seen = 0
        self._rng = np.random.default_rng(seed)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = item


@dataclasses.dataclass
class Window:
    """What one pass of the loop did, on the host clock."""
    submitted: int = 0
    answered: int = 0
    flushes: int = 0
    elapsed_s: float = 0.0
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    batch_k: List[int] = dataclasses.field(default_factory=list)


def run(batcher, vectors: Vectors, mix: dict, seconds: float, stream: int,
        on_answer: Callable[[int, int, jax.Array], None] = None) -> Window:
    """Drive ``batcher`` with ``mix`` for ``seconds``. Clients stop sending
    when the window closes; the requests already sent are answered, and
    the window's length runs to the last answer."""
    if mix["loop"] != "closed":
        raise ValueError(f"traffic loop {mix['loop']!r} is not known; "
                         "the generator knows 'closed'")
    clients = int(mix["clients"])
    w = Window()
    owner = {}                       # ticket -> (client, index, submit t)
    sent = [0] * clients
    ann = jax.profiler.TraceAnnotation

    def submit(c: int) -> None:
        with ann("bench/submit"):
            rid = batcher.submit(vectors(stream, c, sent[c]))
        owner[rid] = (c, sent[c], time.perf_counter())
        sent[c] += 1
        w.submitted += 1

    t0 = time.perf_counter()
    end = t0 + seconds
    for c in range(clients):
        submit(c)
    while batcher.pending:
        with ann("bench/flush"):
            out = batcher.flush()
        with ann("bench/wait"):
            jax.block_until_ready(list(out.values()))
        t = time.perf_counter()
        w.flushes += 1
        w.batch_k.append(len(out))
        for rid, y in out.items():
            c, i, ts = owner.pop(rid)
            w.latencies_s.append(t - ts)
            w.answered += 1
            if on_answer is not None:
                on_answer(c, i, y)
            if t < end:
                submit(c)
        w.elapsed_s = t - t0
    return w
