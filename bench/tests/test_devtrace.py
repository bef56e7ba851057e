"""The trace reduction, on events built by hand and on a small trace
recorded on a v5e."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import devtrace
from bench.devtrace import Event

DEV, OPS, MODS = "/device:TPU:0", devtrace.OPS_LINE, devtrace.MODULES_LINE
HOST = "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "v5e_kron14_clients32.json.gz"


def _op(name, start, dur):
    return Event(DEV, OPS, name, start, dur, {})


def _hand_made():
    kernel = ('%k.1 = f32[8,128] custom-call(f32[8,128] %a), '
              'custom_call_target="tpu_custom_call"')
    return [
        Event(HOST, "python3", "bench/window", 0, 1000, {}),
        Event(HOST, "python3", "bench/flush", 50, 450, {}),
        Event(HOST, "python3", "batcher/pad", 50, 50, {}),
        Event(HOST, "python3", "bench/submit", 700, 200, {}),
        Event(HOST, "python3", "other/span", 0, 1000, {}),
        Event(DEV, MODS, "jit_other(1)", -60, 90, {}),
        Event(DEV, MODS, "jit_sellcs_slots(2)", 90, 400, {}),
        Event(DEV, MODS, "jit__vector(3)", 590, 60, {}),
        Event(DEV, MODS, "jit_slice(4)", 650, 60, {}),
        _op("%early = f32[4] add(%a, %b)", -50, 70),
        _op(kernel, 100, 300),
        _op("%fusion = f32[8] fusion(%k.1)", 400, 50),
        _op("%draw = f32[8] fusion(%key)", 600, 50),
        _op("%slice = f32[8] slice(%y)", 650, 50),
        _op("%late = f32[4] add(%a, %b)", 1100, 10),
    ]


def test_interval_helpers():
    assert devtrace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert devtrace.clip([(0, 3), (5, 9)], 1, 6) == [(1, 3), (5, 6)]
    assert devtrace.length([(1, 3), (5, 6)]) == 3


def test_summary_of_a_hand_made_trace():
    s = devtrace.summarize(_hand_made())
    ns = 1e-9
    assert s.window_s == pytest.approx(1000 * ns)
    # busy: [0,20] + [100,450] + [600,700]
    assert s.busy_s == pytest.approx(470 * ns)
    assert s.kernel_s == pytest.approx(300 * ns) and s.kernel_events == 1
    # glue leaves out the kernel and the clients' draws:
    # [0,20] + [400,450] + [650,700]
    assert s.glue_s == pytest.approx(120 * ns)
    assert [g[0] for g in s.idle_gaps] == ["bench/submit", "host:other",
                                          "batcher/pad"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [300 * ns, 150 * ns, 80 * ns])
    assert s.top_ops[0] == ["jit_sellcs_slots:k.1", pytest.approx(300 * ns)]
    assert ["jit_other:early", pytest.approx(20 * ns)] in s.top_ops


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        devtrace.summarize(_hand_made()[1:])


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        data = json.load(f)
    return [Event(*e) for e in data["events"]]


def test_recorded_trace_busy_time_by_brute_force(recorded):
    s = devtrace.summarize(recorded)
    win = next(e for e in recorded if e.name == devtrace.WINDOW_SPAN)
    lo, hi = int(win.start_ns), int(win.end_ns)
    # a 10 ns grid over the window, marked wherever an operation runs
    grid = np.zeros((hi - lo) // 10 + 1, bool)
    for e in recorded:
        if e.plane == DEV and e.line == OPS:
            a = max(int(e.start_ns), lo) - lo
            b = min(int(e.end_ns), hi) - lo
            if b > a:
                grid[a // 10:(b + 9) // 10] = True
    assert s.busy_s == pytest.approx(grid.sum() * 10e-9, rel=2e-3)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.kernel_s + s.glue_s <= s.busy_s * (1 + 1e-9)


def test_recorded_trace_finds_one_kernel_per_flush(recorded):
    s = devtrace.summarize(recorded)
    flushes = [e for e in recorded if e.name == "bench/flush"]
    assert s.kernel_events == len(flushes) == 3
    assert s.top_ops[0][0] == "jit_sellcs_slots:sellcs_slots.1"
    assert s.kernel_s == pytest.approx(s.top_ops[0][1])
    assert 0 < s.glue_s < s.busy_s - s.kernel_s + 1e-12
    assert all(g[0].startswith(("bench/", "batcher/", "host:"))
               for g in s.idle_gaps)
    assert all(n.split(":")[0] != "?" for n, _ in s.top_ops)
