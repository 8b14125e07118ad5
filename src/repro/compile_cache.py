"""JAX's persistent compilation cache, kept at one fixed place per checkout.

A TPU compile of a fleetsim scan or a training step takes seconds to
minutes; the persistent cache lets every later process on the same machine
reuse it.  A later run finds an entry only where an earlier one wrote it, so
the directory never depends on the process, the time or a temporary path:
`JAX_COMPILATION_CACHE_DIR` when the environment sets it, else
`<checkout>/.jax_cache` (listed in .gitignore).

An entry's key covers the program's metadata too (each op's `op_name`,
with the `fleetsim.<stage>` scopes, and its source line): JAX leaves it out
by default, and an executable read back from the cache then names its ops
as the source that first compiled it did, so a profile of this source
would show another revision's stages.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  Where `JAX_COMPILATION_CACHE_DIR` is
    set, JAX reads it itself and this changes only the key.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
