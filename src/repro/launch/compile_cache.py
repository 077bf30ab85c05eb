"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and the ``mpk-serve`` / ``mpk-trace`` /
``mpk-bench`` mains — never at import, so a library user's own cache
setting is left alone.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``<repo>/src/repro/launch/compile_cache.py``)
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX;
    otherwise the cache is ``<repo>/.jax_cache``, a path that stays the
    same from run to run so later runs find what earlier ones compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
