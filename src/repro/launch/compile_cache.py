"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``python -m repro.launch.train``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` once at
start-up; nothing calls it at import, so importing the library never
touches JAX's configuration.

The cache key includes the directory, so a directory that moves between
runs never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
setting and stands as it is; otherwise the cache goes to ``.jax_cache`` at
the root of the checkout — a fixed path, never a temporary one.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the in-checkout default: ``<repo>/.jax_cache`` (listed in .gitignore)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
