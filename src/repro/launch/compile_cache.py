"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.cli``, ``repro.launch.serve``, ``repro.launch.dryrun``
and ``chip_smoke.py``) call ``configure_compile_cache()`` once, before their
first compile; importing a module never does. ``$JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself, so nothing is overridden).
Otherwise the cache lives in ``<checkout>/.xla_cache``, found from this
file's own path: a fixed directory, so a later run from the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".xla_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
