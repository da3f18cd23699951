"""Where JAX keeps its persistent compilation cache.

Call ``enable_compile_cache()`` once, before the first compile, from every
entry point that compiles a real program (``repro.launch.train``,
``repro.launch.serve``, ``chip_smoke.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  configured, so the cache lives there and nowhere else.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).  The
  path is fixed so that the next run looks where this one wrote; a
  directory named after a temporary name, a process id or the time would
  never be found again.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
