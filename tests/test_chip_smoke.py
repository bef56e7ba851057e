"""chip_smoke.py off the chip, and the compile-cache placement it and
``launch.serve`` share.

The smoke must refuse to report anything without a TPU v5e: under
``JAX_PLATFORMS=cpu``, and copied alone into a directory without the
program, it exits non-zero and prints no ``"ok": true`` line.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    r = _run_smoke(ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_serve_result_feeds_the_smoke_reference_check():
    """serve --mode spmv returns what it served, in request order, and
    the smoke's reference check reads it: exact answers give a zero error
    against a nonzero reference, a corrupted column does not pass."""
    import importlib.util
    from repro.launch import serve
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    run = serve.main(["--mode", "spmv", "--matrix", "kron_like",
                      "--scale", "0.02", "--algorithm", "sellcs",
                      "--impl", "pallas_interpret", "--requests", "6",
                      "--max-batch", "4", "--reps", "1"])
    assert run.plan.impl == "pallas_interpret"
    assert run.plan.label == "sellcs[pallas_interpret]"
    assert len(run.xs) == len(run.ys) == 6
    worst, ref_norm = smoke.worst_rel_err(run)
    assert worst <= smoke.MAX_REL_ERR and ref_norm > 0
    bad = run._replace(ys=[y * 1.01 for y in run.ys])
    assert smoke.worst_rel_err(bad)[0] > smoke.MAX_REL_ERR


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it applies
    and the helper sets no path."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch, tmp_path):
    """Without it, the cache goes to .jax_cache/ at the checkout root —
    a fixed path, ignored by git — or under the root a caller names; an
    installed package (no pyproject.toml above it) caches nothing."""
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.checkout_root() == ROOT
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
        assert compile_cache.enable_compile_cache(root=tmp_path) == \
            str(tmp_path / ".jax_cache")
        monkeypatch.setattr(compile_cache, "checkout_root", lambda: None)
        jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_serve_library_call_leaves_the_cache_alone(monkeypatch):
    """serve.main turns the persistent cache on only as the command line;
    called with an argument list (as tests and chip_smoke.py do) it
    leaves the caller's setting as it is."""
    from repro.launch import serve
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    serve.main(["--mode", "spmv", "--matrix", "hhh_like", "--scale",
                "0.005", "--algorithm", "sellcs", "--impl", "ref",
                "--requests", "2", "--max-batch", "2", "--reps", "1"])
    assert jax.config.jax_compilation_cache_dir == before
