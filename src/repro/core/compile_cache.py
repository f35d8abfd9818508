"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first compile; library
modules never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it. Otherwise the cache goes to a fixed
directory inside the checkout, ``<root>/.jax_cache`` (git-ignored): a
cache keyed by a path that moves between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root: os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<root>/.jax_cache``."""
    import jax

    env = os.environ.get(ENV)
    if env:
        return env
    path = str(pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
