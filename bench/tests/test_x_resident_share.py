"""The reader of ``x_resident_share`` on hand-made registries."""
import types

import pytest

from bench import spec
from repro.obs import MetricRegistry


def _read(registry):
    return spec.reader("x_resident_share").read(
        types.SimpleNamespace(registry=registry))


@pytest.mark.parametrize("resident,share", [(True, 100.0), (False, 0.0)])
def test_reads_resident_lanes_over_live_lanes(resident, share):
    reg = MetricRegistry()
    for _ in range(3):                     # three flushes
        reg.counter("sellcs/lanes_live").inc(2 * 9000)
        reg.counter("sellcs/x_resident_lanes").inc(2 * 9000 if resident
                                                   else 0)
    assert _read(reg) == pytest.approx(share)


def test_reads_a_mix_of_paths():
    reg = MetricRegistry()
    reg.counter("sellcs/lanes_live").inc(3000 + 1000)
    reg.counter("sellcs/x_resident_lanes").inc(3000)
    assert _read(reg) == pytest.approx(75.0)


@pytest.mark.parametrize("case", ["no_registry", "no_counter",
                                  "nothing_walked"])
def test_nothing_to_read(case):
    """A parent without the counter gives a registry without it, though
    its ``sellcs/lanes_live`` counts; a window with no multiply counts no
    live lane."""
    reg = None if case == "no_registry" else MetricRegistry()
    if case == "no_counter":
        reg.counter("sellcs/lanes_live").inc(9000)
    if case == "nothing_walked":
        reg.counter("sellcs/lanes_live").inc(0)
        reg.counter("sellcs/x_resident_lanes").inc(0)
    assert _read(reg) is None
