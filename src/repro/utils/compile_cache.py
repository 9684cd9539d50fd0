"""JAX's persistent compilation cache, placed by the entry points.

A cache only hits when its directory stays put from one run to the next,
so the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself), otherwise ``<checkout>/.jax_cache``.
Entry points (``chip_smoke.py``, ``repro.launch.train.main``,
``repro.fed.runtime.main``) call :func:`use_compile_cache` before their
first compile; importing the package sets nothing, so tests run without a
cache.

The key includes each program's metadata.  JAX leaves it out by default,
and then an executable cached from a build without the round's named
scopes (``repro.core.algorithm``) is reused for one with them: its HLO,
which a profiler trace reads each op's ``op_name`` from, names no layer.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Point JAX's persistent cache at :data:`CACHE_DIR`, unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it, and key it on the
    programs' metadata too."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
