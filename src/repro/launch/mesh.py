"""Production meshes.  Functions, not module constants — importing this module
never touches jax device state (the dry-run sets XLA_FLAGS first)."""
from __future__ import annotations

import math

import jax

from repro.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model single pod; (2,16,16) pod x data x model multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(jax.devices())} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(dryrun.py sets this automatically)")
    return make_mesh(shape, axes)
