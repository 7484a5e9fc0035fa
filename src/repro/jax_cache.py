"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) call
:func:`enable_persistent_cache` once before their first compile, so a
second run of the same program loads its executables instead of compiling
them again.  Nothing calls it at import time, and the test suite does not.
"""
from __future__ import annotations

import os

import jax

#: Fixed cache location inside the checkout (listed in ``.gitignore``).
#: The path is part of every cache key, so it must not move between runs.
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def enable_persistent_cache() -> str:
    """Turn on the persistent cache; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands: nothing is changed.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
