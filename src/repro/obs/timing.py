"""The min-of-N timing protocol, factored to one place.

The paper times 550 executions and reports the minimum (§5.2): on a
memory-bound kernel the minimum is the reproducible number — everything
above it is scheduler noise, allocator stalls, and first-flush effects.
The repo used to implement this discipline twice (``benchmarks.harness``
and ``core.autotune``) while ``launch.serve`` printed single-shot
``perf_counter`` deltas for its headline speedup; now all three call
this helper, and the harness stamps the protocol parameters it ran into
every emitted record so downstream gates can tell a min-of-20 row from a
first-flush fluke.
"""
from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple

try:                                    # importable without jax
    import jax as _jax
except Exception:                       # pragma: no cover - jax is a dep
    _jax = None


class TimingResult(NamedTuple):
    """One min-of-N measurement plus the protocol that produced it."""
    best_s: float          # minimum wall seconds over the timed reps
    reps: int
    warmup: int
    last_result: Any       # fn's return value from the final rep


def _block(out):
    """Wait for ``out``'s device work. A failed execution raises here:
    a timing over work that did not run is no timing."""
    if _jax is not None:
        return _jax.block_until_ready(out)
    return out


def time_min_of_n(fn: Callable, *args, reps: int = 20, warmup: int = 3,
                  block: bool = True) -> TimingResult:
    """Min wall seconds of ``fn(*args)`` over ``reps`` timed runs after
    ``warmup`` untimed ones. ``block=True`` (default) blocks on jax
    outputs inside the timed region, so async dispatch cannot fake a
    fast row; host-only callables pass ``block=False``."""
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    out = None
    for _ in range(warmup):
        out = fn(*args)
        if block:
            _block(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        if block:
            _block(out)
        best = min(best, time.perf_counter() - t0)
    return TimingResult(best, reps, warmup, out)
