"""The reader of ``split_fill`` on hand-made registries."""
import types

import pytest

from bench import spec
from repro.obs import MetricRegistry


def _read(registry):
    return spec.reader("split_fill").read(
        types.SimpleNamespace(registry=registry))


def test_reads_lanes_over_slots_of_every_pass():
    reg = MetricRegistry()
    for _ in range(3):                     # three flushes, two k-tiles each
        reg.counter("sellcs/split_slots").inc(2 * 4000)
        reg.counter("sellcs/split_lanes").inc(2 * 3900)
    assert _read(reg) == pytest.approx(97.5)


@pytest.mark.parametrize("case", ["no_registry", "no_counters",
                                  "nothing_split"])
def test_nothing_to_read(case):
    """A parent without the counters gives an empty registry; a stream
    with no split row counts zero slots."""
    reg = None if case == "no_registry" else MetricRegistry()
    if case == "nothing_split":
        reg.counter("sellcs/split_slots").inc(0)
        reg.counter("sellcs/split_lanes").inc(0)
    assert _read(reg) is None
