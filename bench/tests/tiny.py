"""A copy of the benchmark whose configurations are cut to a size the CPU
runs in seconds, and the two looks of a run that only a chip passes
skipped."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import spec

SIZES = {"kron21": {"scale": 8, "edges": 16 * 256},
         "poisson2d_2048": {"side": 24}}


def make_root(tmp: Path, extra_cells=(), extra_traffic=None) -> Path:
    """``tmp`` with ``bench/`` and ``BENCHMARK.json`` copied, each
    configuration cut to ``SIZES``, plus any extra cells and traffic."""
    shutil.copytree(spec.BENCH_DIR, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(SIZES[c["name"]])
        path.write_text(json.dumps(cfg))
    bench["workloads"].extend(extra_cells)
    for name, mix in (extra_traffic or {}).items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def off_chip(monkeypatch) -> None:
    """Let ``harness.run_cell`` run on the CPU: no look for a chip or its
    peaks, and the program's XLA path (what ``impl="auto"`` resolves to
    off the TPU) in place of the Mosaic kernel."""
    from bench import harness
    monkeypatch.setattr(harness, "check_device", lambda jax, chips, root: (
        jax.devices(), spec.peaks("TPU v5 lite", root)))
    monkeypatch.setattr(harness, "check_kernel", lambda *a: None)
