"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``examples/quickstart.py``,
``examples/serve_actor.py``, ``launch/train.py``, ``benchmarks/run.py``)
call ``enable()`` once before their first compile.  Importing this
module changes nothing.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    caches there; this module sets no other directory.
  * unset: the cache lives at ``<checkout>/.jax_cache``, found from this
    file's location.  The path is part of every cache key, so it is
    fixed — never built from a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
