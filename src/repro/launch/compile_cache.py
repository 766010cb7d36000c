"""Where the entry points keep JAX's persistent compilation cache.

Compiling the layer and serving programs dominates a cold run on a chip,
so every entry point (``chip_smoke.py``, ``train_dssfn``, ``serve_dssfn``)
calls :func:`enable_compile_cache` from its ``main`` — never at import.
``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
otherwise the cache goes to ``.jax_cache`` at the root of the checkout,
a fixed path, so a later run of the same checkout finds what an earlier
one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The in-checkout default (listed in ``.gitignore``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir`; returns
    it.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is changed here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return compile_cache_dir()
