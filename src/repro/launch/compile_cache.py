"""Where JAX's persistent compilation cache lives.

One helper for every entry point that compiles the serving steps
(``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/serve_bench.py``).
Library modules never call it on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the helper
  sets no other directory.
* Not set: the cache goes to ``CACHE_DIR``, one fixed directory inside the
  checkout (listed in ``.gitignore``).  The path is part of the cache key,
  so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (``<repo>/.jax_cache``)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
