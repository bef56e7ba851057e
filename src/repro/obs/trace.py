"""Phase-level tracing: ``span("gather_x")`` wraps a code region, records
its host wall time into the installed registry's histograms, and — when
jax is importable — nests the same name into ``jax.named_scope`` (so the
region's ops carry it in compiled HLO and device traces) and
``jax.profiler.TraceAnnotation`` (so a captured profile shows it on the
host timeline).

Nesting builds slash-joined paths: a ``span("multiply")`` opened inside
``span("flush")`` records into the ``"flush/multiply"`` histogram — the
phase breakdown ``launch.serve --metrics`` prints is exactly these
histograms grouped by prefix. A name that already contains a ``/`` is
*absolute*: it records under exactly that path and neither joins nor
extends the enclosing stack — library instrumentation
(``spmm/kernel``, ``batcher/flush``) uses absolute names so its series
stay stable no matter which caller spans are open (e.g. while a jitted
body containing them is being traced).

Two honesty caveats the instrumented call sites live by:

* Host wall time of a region that is being *traced* by ``jax.jit`` /
  ``shard_map`` is trace time, not device time — still useful (it names
  the phase in the dump and the scope in the HLO) but the number is only
  real execution time on the eager path. ``launch.serve --metrics`` runs
  one eager phase-profile pass for exactly this reason.
* jax dispatch is async: a span around a dispatch-only region would time
  the enqueue. ``maybe_block`` closes a span honestly — it blocks on the
  region's outputs when (and only when) a registry is installed, and is
  a silent no-op on tracers, so the same line is safe under ``jit``.

Zero-overhead default: with no registry installed ``span()`` returns a
process-wide singleton whose ``__enter__``/``__exit__`` do nothing — no
allocation, no perf_counter call, no jax import — asserted by the
micro-benchmark in ``tests/test_obs.py``.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from . import metrics as _metrics

try:                                    # obs must import without jax
    import jax as _jax
except Exception:                       # pragma: no cover - jax is a dep
    _jax = None


class _NullSpan:
    """The disabled path: a shared, stateless, allocation-free context
    manager."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

_STACK = threading.local()


def _stack():
    s = getattr(_STACK, "names", None)
    if s is None:
        s = _STACK.names = []
    return s


class _Span:
    """An enabled span: perf_counter + named_scope + TraceAnnotation."""
    __slots__ = ("name", "registry", "labels", "path", "_t0", "_scopes",
                 "_pushed")

    def __init__(self, name, registry, labels):
        self.name = name
        self.registry = registry
        self.labels = labels
        self.path = None
        self._t0 = 0.0
        self._scopes = None
        self._pushed = False

    def __enter__(self):
        if "/" in self.name:            # absolute: stable series name
            self.path = self.name
        else:
            stack = _stack()
            stack.append(self.name)
            self._pushed = True
            self.path = "/".join(stack)
        self._scopes = []
        if _jax is not None:
            try:
                scope = _jax.named_scope(self.name)
                scope.__enter__()
                self._scopes.append(scope)
                ann = _jax.profiler.TraceAnnotation(self.path)
                ann.__enter__()
                self._scopes.append(ann)
            except Exception:           # profiler backends may be absent
                pass
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        for scope in reversed(self._scopes):
            try:
                scope.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        if self._pushed:
            stack = _stack()
            if stack and stack[-1] == self.name:
                stack.pop()
        # record even on exception: a phase that died still spent the time
        self.registry.histogram(self.path, self.labels).observe(dt)
        return False

    @property
    def elapsed_s(self) -> float:
        """Seconds since ``__enter__`` (live) — for callers that want the
        duration they just measured without re-reading the histogram."""
        return time.perf_counter() - self._t0


def span(name: str, registry=None, labels: Optional[dict] = None):
    """Context manager timing one named phase.

    With no registry installed (and none passed) this is free: the
    returned object is a module-level singleton no-op. With a registry,
    the region's wall seconds land in the histogram named by the
    slash-joined span stack, and the name rides into device traces via
    ``jax.named_scope`` / ``jax.profiler.TraceAnnotation``.
    """
    reg = registry if registry is not None else _metrics._REGISTRY
    if reg is None:
        return _NULL_SPAN
    return _Span(name, reg, labels)


def maybe_block(x):
    """Block on jax outputs iff a registry is installed, so the enclosing
    span times execution instead of async dispatch. Returns ``x``.

    Safe inside ``jit``/``shard_map`` tracing: ``jax.block_until_ready``
    leaves tracers untouched, so instrumented library code needs no
    eager-vs-traced branch. The disabled path is one global load. A
    device execution that failed raises here, inside its span."""
    if _metrics._REGISTRY is None or _jax is None:
        return x
    return _jax.block_until_ready(x)
