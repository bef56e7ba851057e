"""Where JAX keeps its persistent compilation cache.

A compile cache only helps if the next process looks in the same place:
the directory is part of what the cache is keyed on. So the path is either
the one the environment names (``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself) or one fixed directory inside the checkout — never a temp
name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def checkout_root() -> Optional[Path]:
    """The source checkout this package runs from: the directory holding
    ``pyproject.toml`` above ``src/repro``. ``None`` for an installed
    package, which has no checkout to keep a cache in."""
    root = Path(__file__).resolve().parents[3]
    return root if (root / "pyproject.toml").is_file() else None


def enable_compile_cache(root: Optional[Path] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own handling of it applies
    and nothing is changed here; otherwise the cache goes to ``.jax_cache/``
    (ignored by git) under ``root``, by default the checkout this package
    runs from. Without either, nothing is cached and ``None`` is returned.
    Call it before the first compile of the process."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    root = root if root is not None else checkout_root()
    if root is None:
        return None
    import jax
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
