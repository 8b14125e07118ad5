"""Uno applied to cross-pod training: chunked, quantized, RS-protected
gradient exchange on the `pod` (DCI/WAN) mesh axis.

The paper's Fig 13 C workload — data-parallel training across two DCs with
an Allreduce per iteration — is exactly this module's job, adapted to TPU:

  intra-pod  : gradients reduce over the `data` axis on ICI (fast, reliable)
               — left to GSPMD (psum), as the paper leaves intra-DC to the
               fabric's fast control loop;
  cross-pod  : the latency-bound DCI hop gets the UnoRC treatment —
               * the payload is int8 block-quantized (2x fewer DCI bytes,
                 scales travel in f32),
               * framed into x data rows + y RS parity rows (default (8,2),
                 the paper's scheme) via the Pallas GF(2^8) kernels,
               * split into `uno_chunks` chunks sent as independent
                 collective-permute streams ("subflows": XLA schedules them
                 as separate channels it can overlap with compute),
               * the receiver runs a real RS decode on the wire bytes: rows
                 {0..y-1} are reconstructed from the survivor rows and used
                 in place of the transferred copies — the decode sits on the
                 critical path with its true cost, and equals the transfer
                 when nothing is lost (asserted by tests).

Packet loss cannot happen inside an XLA collective (reliable ICI/DCI
runtime), so the *benefit* of EC is evaluated in repro.netsim (as the paper
itself evaluates it, in simulation); the *cost* of EC is carried end-to-end
here and shows up in the dry-run roofline (EXPERIMENTS.md §Perf).

Ring generalization: >2 pods run reduce-scatter / all-gather rings over
`pod` built from the same protected chunk exchange.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.kernels import ops, ref

F32 = jnp.float32


def _use_pallas() -> bool:
    env = os.environ.get("REPRO_UNO_KERNELS")
    if env:
        return env == "pallas"
    # ref-jnp on CPU dry-runs (512 fake devices x interpret-mode python would
    # dominate compile time); pallas kernels on real TPU
    return jax.default_backend() != "cpu"


def _quant(v):
    if _use_pallas():
        return ops.quant_int8(v)
    pad = (-v.shape[0]) % ops.QUANT_BLOCK
    vp = jnp.pad(v, (0, pad))
    q, s = ref.quant_int8_ref(vp, ops.QUANT_BLOCK)
    return q, s, v.shape[0]


def _dequant(q, s, n0):
    if _use_pallas():
        return ops.dequant_int8(q, s, n0)
    return ref.dequant_int8_ref(q, s, ops.QUANT_BLOCK)[:n0]


def _rs_encode(rows, r):
    if _use_pallas():
        return ops.rs_encode(rows, r)
    return ref.rs_encode_ref(rows, r)


def _rs_decode(survivors, k, r, missing, parity_avail):
    if _use_pallas():
        return ops.rs_decode(survivors, k, r, missing, parity_avail)
    return ref.rs_decode_ref(survivors, k, r, missing, parity_avail)


# --------------------------------------------------------------- wire format

def _protect(chunk, run: RunConfig):
    """chunk f32 (C,) -> (q_rows uint8 (x, C/x), scales f32, parity (y, .))."""
    x, y = run.uno_ec_data, run.uno_ec_parity
    q, scales, n0 = _quant(chunk)
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8)
    rows = qb.reshape(x, -1)                       # C % (x*block) == 0 by pad
    parity = _rs_encode(rows, y)
    return rows, scales, parity, n0


def _unprotect(rows, scales, parity, n0, run: RunConfig, dtype=F32):
    """Receiver: RS-decode rows {0..y-1} from the survivors and use the
    reconstruction (equals the wire copy when nothing was lost)."""
    x, y = run.uno_ec_data, run.uno_ec_parity
    missing = tuple(range(y))                      # designated decode rows
    survivors = jnp.concatenate([rows[y:], parity], axis=0)
    rebuilt = _rs_decode(survivors, x, y, missing, tuple(range(y)))
    full = jnp.concatenate([rebuilt, rows[y:]], axis=0)
    q = jax.lax.bitcast_convert_type(full.reshape(-1), jnp.int8)
    return _dequant(q, scales, n0).astype(dtype)


# ------------------------------------------------------------- pod exchange

def _pod_ring_psum(v, run: RunConfig, n_pods: int, axis: str = "pod"):
    """Mean over `axis` of a flat f32 vector, via `uno_chunks` independent
    protected chunk streams (ring reduce-scatter + all-gather for p > 2,
    single pairwise exchange for p = 2)."""
    n_chunks = max(1, run.uno_chunks)
    pad = (-v.shape[0]) % (n_chunks * run.uno_ec_data * ops.QUANT_BLOCK)
    vp = jnp.pad(v, (0, pad))
    chunks = jnp.split(vp, n_chunks)

    fwd = [(i, (i + 1) % n_pods) for i in range(n_pods)]
    rev = [(i, (i - 1) % n_pods) for i in range(n_pods)]

    def send(chunk, perm):
        rows, scales, parity, n0 = _protect(chunk, run)
        rows_p = jax.lax.ppermute(rows, axis, perm)
        scales_p = jax.lax.ppermute(scales, axis, perm)
        parity_p = jax.lax.ppermute(parity, axis, perm)
        return _unprotect(rows_p, scales_p, parity_p, n0, run)

    if n_pods == 2:
        out = [(c + send(c, fwd)) * 0.5 for c in chunks]
        return jnp.concatenate(out)[: v.shape[0]]

    # ring reduce-scatter + all-gather over `pod`, every hop protected
    idx = jax.lax.axis_index(axis)
    out_chunks = []
    for c in chunks:
        cpad = (-c.shape[0]) % n_pods
        cp = jnp.pad(c, (0, cpad))
        parts = jnp.stack(jnp.split(cp, n_pods))       # (p, L)
        L = parts.shape[1]

        def take(ps, i):
            return jax.lax.dynamic_index_in_dim(ps, i % n_pods, 0,
                                                keepdims=False)

        def put(ps, i, val):
            return jax.lax.dynamic_update_index_in_dim(ps, val, i % n_pods, 0)

        # RS phase: step s moves the running sum of ring-index (idx - s)
        for s in range(n_pods - 1):
            blk = take(parts, idx - s)
            recv = send(blk, fwd)                      # from pod idx-1
            tgt = idx - s - 1
            parts = put(parts, tgt, take(parts, tgt) + recv)
        # pod idx now owns the full sum of part (idx + 1) % p
        # AG phase: circulate the owned parts around the ring
        for s in range(n_pods - 1):
            blk = take(parts, idx + 1 - s)
            recv = send(blk, fwd)
            parts = put(parts, idx - s, recv)
        out_chunks.append(parts.reshape(-1)[: c.shape[0]] / n_pods)
    return jnp.concatenate(out_chunks)[: v.shape[0]]


# ----------------------------------------------------------------- flattening

def _flatten(grads):
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [math.prod(l.shape) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(F32) for l in leaves])
    return flat, (treedef, [l.shape for l in leaves],
                  [l.dtype for l in leaves], sizes)


def _unflatten(flat, meta):
    treedef, shapes, dtypes, sizes = meta
    out, off = [], 0
    for shp, dt, n in zip(shapes, dtypes, sizes):
        out.append(flat[off:off + n].reshape(shp).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ public

def make_uno_grad_sync(mesh: Mesh, cfg: ModelConfig, run: RunConfig
                       ) -> Callable:
    """Returns uno_sync(stacked_grads): per-pod grad copies (leading axis =
    `pod`, produced by the Uno train step's vmap over the pod batch split)
    -> pod-mean grads without the leading axis.

    Implementation note: the model's forward/backward stays in plain GSPMD
    (partial-manual shard_map around large in-pod meshes trips an XLA SPMD
    partitioner CHECK at >=128 in-pod devices — recorded in DESIGN.md).  The
    protected exchange itself runs in a FULLY-manual shard_map over all mesh
    axes: its body contains only local reshape/bitcast/kernel ops plus pod
    ppermutes, which partition trivially.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pods = axis_sizes.get("pod", 1)
    n_shards = axis_sizes.get("data", 1) * axis_sizes.get("model", 1)
    inpod_axes = tuple(a for a in ("data", "model") if a in axis_sizes)
    all_axes = (("pod",) if "pod" in axis_sizes else ()) + inpod_axes

    def _exchange_flat(flat):
        """'flat' impl: one pod-stacked (p, N) vector constrained to
        P('pod', (data, model)).  Baseline for §Perf HC3: the constraint
        fights every leaf's natural layout -> XLA inserts a full
        reshard (involuntary-remat all-gathers)."""
        unit = n_shards * run.uno_chunks * run.uno_ec_data * ops.QUANT_BLOCK
        pad = (-flat.shape[1]) % unit
        flat_p = jnp.pad(flat, ((0, 0), (0, pad)))
        flat_p = jax.lax.with_sharding_constraint(
            flat_p, jax.NamedSharding(mesh, P("pod", inpod_axes)))

        def exchange_local(vloc):                  # (1, N_local) on-device
            return _pod_ring_psum(vloc[0], run, n_pods)

        exchange = jax.shard_map(
            exchange_local, mesh=mesh,
            in_specs=P("pod", inpod_axes), out_specs=P(inpod_axes),
            axis_names=set(all_axes), check_vma=False)
        return exchange(flat_p)[: flat.shape[1]]

    def uno_sync_flat(stacked):
        leaves, treedef = jax.tree.flatten(stacked)
        sizes = [math.prod(l.shape[1:]) for l in leaves]
        shapes = [l.shape[1:] for l in leaves]
        dtypes = [l.dtype for l in leaves]
        flat = jnp.concatenate(
            [l.reshape(n_pods, -1).astype(F32) for l in leaves], axis=1)
        out = _exchange_flat(flat)
        res, off = [], 0
        for shp, dt, n in zip(shapes, dtypes, sizes):
            res.append(out[off:off + n].reshape(shp).astype(dt))
            off += n
        return jax.tree.unflatten(treedef, res)

    def uno_sync_leaf_local(stacked):
        """'leaf_local' impl (§Perf HC3): enter ONE shard_map with every
        grad leaf in its NATURAL sharding (P('pod', *param_spec)) — zero
        resharding; flatten/pad/quant/RS/ppermute all happen on the local
        shards."""
        from repro import models, sharding as shlib
        pspecs = models.param_pspecs(cfg)
        # grads mirror params with a leading pod dim
        in_specs = jax.tree.map(lambda s: P("pod", *s), pspecs,
                                is_leaf=lambda s: isinstance(s, P))
        out_specs = pspecs
        leaves, treedef = jax.tree.flatten(stacked)
        spec_leaves = jax.tree.leaves(in_specs,
                                      is_leaf=lambda s: isinstance(s, P))
        for s in spec_leaves:                      # pod-sharded params can't
            assert "pod" not in jax.tree.leaves(s)  # use this path (fsdp_pod)

        def local_fn(tree_loc):
            # each local leaf is (1, *local_shape): pod dim sharded away
            lvs = jax.tree.leaves(tree_loc)
            shapes = [l.shape[1:] for l in lvs]
            sizes = [math.prod(s) for s in shapes]
            dts = [l.dtype for l in lvs]
            flat = jnp.concatenate([l.reshape(-1).astype(F32) for l in lvs])
            out = _pod_ring_psum(flat, run, n_pods)
            res, off = [], 0
            for shp, dt, n in zip(shapes, dts, sizes):
                res.append(out[off:off + n].reshape(shp).astype(dt))
                off += n
            return jax.tree.unflatten(jax.tree.structure(tree_loc), res)

        exchange = jax.shard_map(
            local_fn, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
            axis_names=set(all_axes), check_vma=False)
        return exchange(stacked)

    def uno_sync(stacked):
        if n_pods == 1:
            return jax.tree.map(lambda g: g[0], stacked)
        if run.uno_impl == "flat":
            return uno_sync_flat(stacked)
        return uno_sync_leaf_local(stacked)

    return uno_sync
