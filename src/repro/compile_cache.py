"""JAX's persistent compilation cache, kept at one fixed place per checkout.

A TPU compile of a fleetsim scan or a training step takes seconds to
minutes; the persistent cache lets every later process on the same machine
reuse it.  A later run finds an entry only where an earlier one wrote it, so
the directory never depends on the process, the time or a temporary path:
`JAX_COMPILATION_CACHE_DIR` when the environment sets it, else
`<checkout>/.jax_cache` (listed in .gitignore).
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  Where `JAX_COMPILATION_CACHE_DIR` is
    set, JAX reads it itself and this changes nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
