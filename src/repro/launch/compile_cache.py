"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`configure_compile_cache` once, before they compile anything.  No
library module sets a cache when it is imported.

* ``JAX_COMPILATION_CACHE_DIR`` set: nothing is changed, and JAX keeps the
  cache in that directory.
* Otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path that
  git ignores.  The directory is part of each entry's key, so it never
  depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
