"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``examples/amg_solve.py``,
``benchmarks/run.py``, ``repro.launch.serve``, ``repro.launch.train``)
call :func:`configure_compile_cache` before their first compile.  Library
modules never call it, so importing them leaves JAX's configuration as it
was.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache``: one fixed directory, so a second run from the
#: same checkout finds what the first one compiled.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
