"""BENCHMARK.json keeps to its stated limits, and the harness finds what it
names by name alone."""
import json
import re

import pytest

from bench import harness, spec
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_configs_cells_and_their_files(bench):
    used = {c["config"] for c in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/")
        cfg = spec.config(bench, c["name"])
        assert cfg["source"].startswith(c["source"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        spec.generator(cfg["generator"])
        assert float(cfg["limit_normwise_err"]) > 0
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert spec.traffic(w["traffic"])["loop"] == "closed"


def test_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        spec.reader(m["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    cells = {w["name"] for w in bench["workloads"]}
    for cell in cells:
        reported = {m["name"] for m in spec.metrics_for(bench, cell, False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_for(bench, cell, True)


def test_peaks_are_found_by_device_kind_and_unknown_kinds_refused():
    p = spec.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    assert p["source"]
    with pytest.raises(LookupError):
        spec.peaks("TPU v9 imaginary")


def test_a_new_traffic_file_and_cell_need_no_code(tmp_path, monkeypatch):
    tiny.off_chip(monkeypatch)
    cell = {"name": "kron21.clients4", "config": "kron21",
            "traffic": "clients4", "chips": 1, "why": "four chains"}
    root = tiny.make_root(tmp_path, [cell], {
        "clients4": {"loop": "closed", "clients": 4, "max_batch": 4}})
    out = harness.run_cell("kron21.clients4", 17, 0.3, False, root=root)
    assert out["correct"] and out["answered"] >= 4
    assert set(out["metrics"]) == {"spmv_per_s", "convert_s", "setup_s"}
