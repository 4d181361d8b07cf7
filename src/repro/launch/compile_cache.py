"""Persistent XLA compilation cache for the entry points.

Called by ``chip_smoke.py`` and ``repro.launch.serve`` once, before the
first compile — never at import and never from tests.  The default is
a fixed directory in the checkout, so a later run of the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os

#: the checkout root (``src/repro/launch`` -> three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
