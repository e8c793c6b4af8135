"""One home for JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``examples/million_nodes.py``,
``examples/serve_batched.py``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` from its ``main`` — never at import — so the
chunk programs of the sharded engine are compiled once per machine, not
once per process. A later process finds the programs only at the same
path, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads it itself and nothing else is configured), otherwise
``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
