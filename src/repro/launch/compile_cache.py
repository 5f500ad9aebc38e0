"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(listed in ``.gitignore``): the directory is part of the cache key, so it
must not move between runs.  Entry points call ``enable_compile_cache`` at
the top of ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ=os.environ) -> tuple[str, bool]:
    """(directory, whether this program must set it): the environment's
    directory when it names one, else the fixed in-checkout one."""
    env = environ.get(ENV_VAR)
    if env:
        return env, False
    return str(CHECKOUT_CACHE), True


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path, must_set = compile_cache_dir()
    if must_set:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
