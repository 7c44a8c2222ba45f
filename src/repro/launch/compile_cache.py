"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called by the entry points (``chip_smoke.py``,
``launch/train.py``, ``launch/serve_molopt.py``, ``benchmarks/run.py``)
before their first compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing; otherwise the cache goes to
``<repo>/.cache/jax_compile``.  The path is fixed because it is part of
the cache key: a directory that moves never hits.  The tests never call
this, so they compile without a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".cache" / "jax_compile"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
