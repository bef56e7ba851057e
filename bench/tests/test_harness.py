"""A whole run on the CPU with the look for a chip skipped: the result
line, the command's refusal off the chip, and ``correct`` coming out
false when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import harness, spec
from bench.tests import tiny
from repro.spmm import SparseOperator

CELL = "kron21.clients32"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def off_chip(monkeypatch):
    tiny.off_chip(monkeypatch)


def _run(root, cell=CELL, seed=2 ** 31 + 99):
    return harness.run_cell(cell, seed, 0.5, False, root=root)


@pytest.mark.parametrize("cell", [CELL, "poisson2d_2048.solo",
                                  "kron21.solo"])
def test_a_sound_run_is_correct_and_reports_its_metrics(root, cell):
    out = _run(root, cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["answered"] > 0
    bench = spec.load_benchmark(root)
    assert set(out["metrics"]) == {
        m["name"] for m in spec.metrics_for(bench, cell, False)}
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert list(out)[-1] == "checks"
    c = out["checks"]["normwise_err"]
    assert c["value"] <= c["limit"]
    # every flush of a closed loop with clients <= max_batch is full
    mix = spec.traffic(spec.cell(bench, cell)["traffic"], root)
    assert out["answered"] == out["flushes"] * mix["clients"]
    # every client's answers are in the sample
    assert out["compared"] == min(out["answered"], max(
        harness.SAMPLE, mix["clients"]))


def _broken(monkeypatch, fault):
    real = SparseOperator.matmul

    def matmul(self, X):
        Y = real(self, X)
        return fault(X, Y)

    monkeypatch.setattr(SparseOperator, "matmul", matmul)


def _half_batch(X, Y):
    # half of the batch left out, the mean of the rest in its place
    h = max(Y.shape[1] // 2, 1)
    mean = jnp.mean(Y[:, :h], axis=1, keepdims=True)
    return Y.at[:, h:].set(jnp.broadcast_to(mean, Y[:, h:].shape))


def _altered(X, Y):
    # every answer altered where it is produced: its largest entry off by
    # a thousandth of the answer's norm
    i = jnp.argmax(jnp.abs(Y), axis=0)
    bump = 1e-3 * jnp.linalg.norm(Y, axis=0)
    return Y.at[i, jnp.arange(Y.shape[1])].add(bump)


def _one_slot(X, Y):
    # one slot of the batch altered, the others sound
    return Y.at[:, -1].add(1e-3 * jnp.linalg.norm(Y[:, -1]))


@pytest.mark.parametrize("fault", [
    lambda X, Y: X.astype(Y.dtype),           # the state returned unchanged
    _half_batch,
    _altered,
    _one_slot,
], ids=["unchanged", "half_batch", "altered", "one_slot"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    out = _run(root)
    assert out["correct"] is False and out["failed"] > 0
    c = out["checks"]["normwise_err"]
    assert c["value"] > c["limit"]


def test_the_command_refuses_the_cpu(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    cmd = [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
           CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_the_command_fails_without_the_program(tmp_path):
    # a directory holding only BENCHMARK.json and the files under paths
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench")
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", [CELL, "poisson2d_2048.solo"])
def test_the_control_fails_the_limit_on_three_seeds(root, monkeypatch,
                                                    cell):
    from bench import control
    control.install(monkeypatch.setattr)
    for seed in (1, 2, 2 ** 33 + 5):
        out = harness.run_cell(cell, seed, 0.3, False, root=root)
        assert out["correct"] is False, (cell, seed, out["checks"])
        c = out["checks"]["normwise_err"]
        assert c["value"] > 3 * c["limit"]


def test_results_print_as_one_json_line(root, capsys, monkeypatch):
    monkeypatch.setattr(harness, "run_cell",
                        lambda *a, **k: {"correct": True, "checks": {
                            "normwise_err": {"value": 1e-8, "limit": 1e-5}}})
    assert harness.main(["--workload", CELL, "--seed", "3", "--seconds",
                         "1"]) == 0
    captured = capsys.readouterr()
    assert json.loads(captured.out.strip().splitlines()[-1])["correct"]
    assert captured.err.strip().splitlines()[-1].startswith(
        "check normwise_err")
