"""Where JAX's persistent compilation cache lives.

Entry points (``launch.serve``, ``launch.train``, ``benchmarks.run``,
``chip_smoke.py``) call ``configure_compile_cache()`` first thing; importing
this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: the environment placed the cache, JAX
  reads the variable itself, and nothing is set here.
* otherwise: ``<checkout>/.jax_cache``.  The path is part of each entry's
  key, so it is fixed -- never derived from a temp name, a pid or the time
  -- and a second identical run finds the first run's programs.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
