"""JAX's persistent compilation cache, switched on by entry points.

Scripts, examples and benchmark mains call :func:`enable_compile_cache`
once, before their first compile, so a second run of the same program
loads its executables instead of compiling them again.  The library never
calls it on import, and tests never call it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this helper
  sets no other directory.
* Not set: the cache lives at ``<repo>/.jax_cache``, a fixed path found
  from this file's location (the path is part of the cache key, so a
  directory that moves never hits).  ``.gitignore`` lists it.
"""

from __future__ import annotations

import os
import pathlib

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process and
    return the directory it uses.  Every executable is cached, however
    short its compile: a cold run on a chip pays for each one."""

    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
