"""Where JAX keeps its persistent compilation cache.

The cache is keyed by, among other things, its own path, so it only pays
off at a path that stays put between runs: a directory that moves (a temp
name, a process id, a time stamp) never hits.  :func:`use_compile_cache` is
called first by every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``), before anything compiles.
"""

from __future__ import annotations

import os

import jax

#: the source checkout this package lives in (``<repo>/src/repro/utils``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to the fixed path
    ``<repo>/.jax_cache`` (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
