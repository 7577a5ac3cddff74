"""Where JAX keeps compiled programs between processes.

A cold compile of a full-width step takes tens of seconds to minutes, so
entry points turn JAX's persistent compile cache on. Nothing here runs
at import time; tests leave the cache off.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: The fallback cache: a fixed path (the path is part of what makes a
#: later process find the entries again), never a temp name or a pid.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other path; otherwise the cache is ``.jax_cache/`` at
    the root of the checkout.
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
