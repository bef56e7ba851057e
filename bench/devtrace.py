"""The reduction from a profiler trace to device time.

A traced run writes the JAX profiler's ``.xplane.pb``; ``load`` flattens
the events this reduction reads into :class:`Event` records and
``summarize`` reduces them, inside the benchmark's ``bench/window`` host
span, to:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  averaged over the chips;
* ``kernel_s``: the device time of the Mosaic custom calls, the
  operations whose HLO names ``custom_call_target="tpu_custom_call"``;
* ``glue_s``: the union of the other operations, less those of the
  programs that make the clients' request vectors (``jit__vector``);
* the operations that took most time, named ``<program>:<operation>``,
  and the longest idle gaps, each named by the innermost host span
  (``bench/*``, ``batcher/*``) open at its midpoint.

On a v5e an operation's event name is its HLO text
(``%sellcs_slots.1 = f32[...] custom-call(...), custom_call_target=...``)
and the ``XLA Modules`` line holds one event per program run
(``jit_sellcs_slots(<fingerprint>)``), which gives each operation its
program.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:"
HOST_SPAN = re.compile(r"^(bench|batcher)/")
WINDOW_SPAN = "bench/window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
CLIENT_MODULE = "jit__vector"
TOP = 10

Interval = Tuple[float, float]


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Summary(NamedTuple):
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_events: int
    glue_s: float
    top_ops: List[list]
    idle_gaps: List[list]


def options(jax):
    """Profiler options: host TraceMe spans, no Python call tracer."""
    o = jax.profiler.ProfileOptions()
    o.host_tracer_level = 2
    o.python_tracer_level = 0
    return o


def _wanted(plane: str, line: str, name: str) -> bool:
    if DEVICE_PLANE.match(plane):
        return line in (OPS_LINE, MODULES_LINE)
    return plane.startswith(HOST_PLANE) and bool(HOST_SPAN.match(name))


def load(trace_dir) -> List[Event]:
    """The events this reduction reads, from the newest trace under
    ``trace_dir``: device operations and programs, and the host spans."""
    import jax
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if _wanted(plane.name, line.name, e.name):
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns), {}))
    return events


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def is_kernel(e: Event) -> bool:
    """A Mosaic custom call: a Pallas kernel compiled for the TPU."""
    return KERNEL_MARK in e.name


def op_name(e: Event) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def program_name(e: Event) -> str:
    """``jit_sellcs_slots(1289...)`` -> ``jit_sellcs_slots``."""
    return e.name.split("(", 1)[0]


class _Programs:
    """Which program run holds a given instant, on one device."""

    def __init__(self, modules: Sequence[Event]):
        self._mods = sorted(modules, key=lambda e: e.start_ns)
        self._starts = [e.start_ns for e in self._mods]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._mods[i].end_ns:
            return program_name(self._mods[i])
        return ""


def _innermost(spans: Sequence[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and (best is None
                                           or s.dur_ns < best.dur_ns):
            best = s
    return "host:other" if best is None else best.name


def summarize(events: Sequence[Event]) -> Summary:
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    spans = [e for e in events if e.plane.startswith(HOST_PLANE)
             and HOST_SPAN.match(e.name) and e.name != WINDOW_SPAN
             and e.end_ns > lo and e.start_ns < hi]
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    busy = kernel = glue = 0.0
    kernel_events = 0
    op_time: Dict[str, float] = {}
    gaps: List[list] = []
    for plane in planes:
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        programs = _Programs([e for e in events if e.plane == plane
                              and e.line == MODULES_LINE])
        cover = union(clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy += length(cover)
        kern, rest = [], []
        for e in ops:
            prog = programs.at(e.start_ns)
            if is_kernel(e):
                kern.append((e.start_ns, e.end_ns))
            elif not prog.startswith(CLIENT_MODULE):
                rest.append((e.start_ns, e.end_ns))
            name = f"{prog or '?'}:{op_name(e)}"
            op_time[name] = op_time.get(name, 0.0) + (
                min(e.end_ns, hi) - max(e.start_ns, lo))
        kernel_events += len(kern)
        kernel += length(union(clip(kern, lo, hi)))
        glue += length(union(clip(rest, lo, hi)))
        edges = [lo] + [t for iv in cover for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append([_innermost(spans, (a + b) / 2), (b - a) * 1e-9])
    nplanes = max(len(planes), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy * 1e-9 / nplanes,
                   kernel_s=kernel * 1e-9 / nplanes,
                   kernel_events=kernel_events,
                   glue_s=glue * 1e-9 / nplanes,
                   top_ops=[[k, v * 1e-9 / nplanes] for k, v in top],
                   idle_gaps=gaps[:TOP])
