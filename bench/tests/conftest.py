"""Sizes at which the CPU runs the configurations that came after
``tiny.SIZES`` was written, so that ``tiny.make_root`` can cut every
configuration ``BENCHMARK.json`` names."""
from bench.tests import tiny

tiny.SIZES.setdefault("mawi22", {"scale": 10, "edges": 120})
