"""Persistent XLA compilation cache for the entry points.

``enable()`` is called by ``launch/serve.py``'s main, ``chip_smoke.py``
and the benchmark scripts, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it; otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, listed in ``.gitignore``). The path is
part of what a cache hit needs, so it never depends on a temp dir, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
