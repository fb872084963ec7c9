"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`configure` once, before their first compile; importing a module
of this package never does.  The rule:

- ``JAX_COMPILATION_CACHE_DIR``, when set, decides where the cache goes
  (JAX reads it itself; no directory is set in code);
- otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path,
  because the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  Every compiled program is kept, however fast it
    compiled, so a repeat run compiles nothing new."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
