"""Where JAX keeps its persistent compilation cache.

Every entry point (``fl_sim``, the benchmarks, ``chip_smoke.py``, the test
suite) calls :func:`configure_compile_cache` before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins, and nothing
  else is set — JAX reads the variable itself;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The path is fixed because it is part of the cache key:
  a directory that moves between runs never hits.

Outside a source checkout (an installed package) no cache is configured.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def _checkout_root() -> Optional[str]:
    """The source checkout this package runs from, or None when installed."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    return root if os.path.exists(os.path.join(root, "pyproject.toml")) \
        else None


def configure_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its one place; returns
    the directory in use (None when no cache is configured)."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    root = _checkout_root()
    if root is None:
        return None
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
