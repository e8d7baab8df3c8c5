"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`use_compile_cache` before they compile anything.  A
``JAX_COMPILATION_CACHE_DIR`` set in the environment wins — JAX reads it
itself and nothing here sets another directory.  Otherwise the cache lives
at one fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored):
never a temp name, a pid or a time, because the path is part of what makes
a later run find the entries again.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory compiled programs go to: the environment's, else the
    fixed in-checkout default."""
    return os.environ.get(ENV) or str(REPO_CACHE)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return it.  Sets JAX's option only when
    the environment does not already name a directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
