"""Reads ``BENCHMARK.json`` and the data files it names.

Nothing here knows a cell, a configuration or a metric by name: a later
change adds an entry to ``BENCHMARK.json`` and a file beside the others,
and the harness finds it.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration as it is run: the JSON file the entry names."""
    entry = _named(bench["configs"], name, "configuration")
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, root: Path = ROOT) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    with open(Path(root) / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metrics_for(bench: dict, cell_name: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones
    with ``--trace 0``, the per-layer ones with ``--trace 1``; a metric
    with a ``workloads`` key only in the cells it lists."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def _module(path: Path, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT) -> ModuleType:
    """The reader of one metric: ``bench/metrics/<metric>.py``, whose
    ``read(ctx)`` returns the value or ``None`` when it finds nothing to
    read."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    return _module(path, f"bench_metric_{metric.replace('.', '_')}")


def generator(name: str, root: Path = ROOT) -> ModuleType:
    """The matrix generator ``bench/generators/<name>.py``."""
    path = Path(root) / "bench" / "generators" / f"{name}.py"
    return _module(path, f"bench_generator_{name}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The peaks of ``device_kind``: the file under ``bench/peaks/`` whose
    ``device_kind`` matches exactly. An unknown device is an error."""
    for path in sorted((Path(root) / "bench" / "peaks").glob("*.json")):
        with open(path) as f:
            entry = json.load(f)
        if entry["device_kind"] == device_kind:
            return entry
    raise LookupError(f"no peaks for device kind {device_kind!r} under "
                      "bench/peaks/; measuring on it would have no "
                      "roofline to compare with")
