"""Logical-axis sharding: map model-code axis names onto whatever mesh is active.

Model code annotates activations/params with *logical* axes ("batch", "tensor",
"fsdp", "expert", "vocab", ...).  The rules below resolve those onto the mesh
axis names of the active mesh ("pod", "data", "model").  Axes absent from the
mesh resolve to None (replicated), so the same model code runs on a single
device, a (data, model) pod, or a (pod, data, model) multi-pod mesh.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> tuple of mesh axes (joined) in priority order.  A mesh axis is
# used only if present in the active mesh.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),   # data parallel over pods x in-pod data axis
    "fsdp": ("data",),          # parameter/optimizer-state sharding (ZeRO/FSDP)
    "fsdp_pod": ("pod", "data"),  # cross-pod ZeRO-3 (opt-in per config)
    "tensor": ("model",),       # megatron tensor parallel
    "expert": ("model",),       # expert parallel (MoE) -- in-pod by design (see DESIGN.md)
    "vocab": ("model",),        # vocab/embedding sharding
    "seq": (),                  # sequence parallel (off by default; hillclimb knob)
    "kv_batch": ("pod", "data"),  # KV-cache batch dim
    "seq_kv": (),               # KV-cache sequence dim (long_500k remaps -> data)
    "none": (),
}


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of `shape` over `devices` (default: the first prod(shape) local
    devices) in their given order, with every axis `AxisType.Auto`.

    `jax.make_mesh` defaults its axes to Explicit, under which plain
    gathers and reshapes of sharded operands raise `ShardingTypeError`
    (the code here relies on GSPMD propagation and manual `shard_map`
    regions, which Auto axes give), and it refuses a subset of a TPU
    host's chips — such as three DCs on a four-chip host.
    """
    n = math.prod(shape)
    devices = jax.devices()[:n] if devices is None else list(devices)
    if len(devices) != n:
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"got {len(devices)}")
    grid = np.empty(n, dtype=object)
    grid[:] = devices
    return Mesh(grid.reshape(tuple(shape)), tuple(axes),
                axis_types=(AxisType.Auto,) * len(axes))


class _State(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES)


_STATE = _State()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Activate `mesh` (+ optional rule overrides) for logical sharding."""
    prev_mesh, prev_rules = _STATE.mesh, _STATE.rules
    _STATE.mesh = mesh
    if rules:
        merged = dict(DEFAULT_RULES)
        merged.update(rules)
        _STATE.rules = merged
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _STATE.mesh, _STATE.rules = prev_mesh, prev_rules


@contextlib.contextmanager
def use_rules(overrides: dict):
    """Trace-time rule overrides (e.g. inside a pod-manual shard_map the
    'batch' logical axis must stop referencing the manual 'pod' axis)."""
    prev = _STATE.rules
    merged = dict(prev)
    merged.update(overrides)
    _STATE.rules = merged
    try:
        yield
    finally:
        _STATE.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def resolve(*logical_axes: Optional[str], shape: Optional[Sequence[int]] = None) -> P:
    """Resolve logical axis names to a PartitionSpec for the active mesh.

    If `shape` is given, mesh axes that do not evenly divide the corresponding
    dim are dropped (from the right) — e.g. 9 heads on a 16-way `model` axis,
    or batch=1 cells — so every resulting sharding is XLA-legal.
    """
    mesh = _STATE.mesh
    if mesh is None:
        return P()
    mesh_axes = set(mesh.axis_names)
    used: set[str] = set()
    out = []
    for i, ax in enumerate(logical_axes):
        if ax is None:
            out.append(None)
            continue
        cands = _STATE.rules.get(ax, ())
        picked = [a for a in cands if a in mesh_axes and a not in used]
        if shape is not None:
            dim = shape[i]
            while picked:
                total = 1
                for a in picked:
                    total *= _axis_size(mesh, a)
                if dim % total == 0:
                    break
                picked.pop()
        used.update(picked)
        if len(picked) == 0:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    return P(*out)


def profile_rules(cfg) -> dict:
    """Logical-rule overrides for a config's sharding profile.

    'dp': tiny models (e.g. 135M on 256 chips) waste the mesh on 2D
    sharding — indivisible head/ff dims leave weights half-replicated while
    activations thrash through reshards.  Replicate the weights outright and
    give the batch every mesh axis (§Perf HC2)."""
    if getattr(cfg, "sharding_profile", "2d") == "dp":
        every = ("pod", "data", "model")
        return {"batch": every, "kv_batch": every, "fsdp": (),
                "fsdp_pod": (), "tensor": (), "vocab": (), "expert": ()}
    return {}


def batch_group_count(n: int) -> int:
    """How many shards the logical 'batch' axis maps to on the active mesh
    (and that divide n).  Used by MoE dispatch to keep token sort/scatter
    LOCAL per batch shard — a global scatter forces XLA to merge a
    replicated (E*cap, d) buffer with per-layer all-reduces."""
    mesh = _STATE.mesh
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    g = 1
    for a in _STATE.rules.get("batch", ()):
        if a in sizes:
            g *= sizes[a]
    while g > 1 and n % g:
        g //= 2
    return g


def shard(x, *logical_axes: Optional[str]):
    """with_sharding_constraint under the active mesh (no-op without a mesh)."""
    mesh = _STATE.mesh
    if mesh is None:
        return x
    spec = resolve(*logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*logical_axes: Optional[str],
                   shape: Optional[Sequence[int]] = None) -> Optional[NamedSharding]:
    mesh = _STATE.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical_axes, shape=shape))


def spec_tree_to_shardings(mesh: Mesh, spec_tree):
    """Map a pytree of PartitionSpec -> pytree of NamedSharding for `mesh`."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )
