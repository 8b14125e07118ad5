"""Locality-sharded flow axis: private/boundary link split + halo exchange.

The fleet step is embarrassingly parallel in the flow dimension except for
one reduction: the per-link offered load.  PR 3 sharded the flow axis with
`shard_map` and psummed the ENTIRE (n_links + 1,) load buffer every epoch —
correct, but on real topologies the flow->link incidence is overwhelmingly
local (a dumbbell uplink is touched by exactly one flow, a downlink by the
~64 flows hashed onto it), so almost every element of that collective was
traffic one shard alone produced and one shard alone would read.  It also
rebuilt the shard_map closure and re-jitted it on every call, so repeated
runs paid multi-second retrace+recompile each time — the benchmark's
"sharded is 100x slower" was mostly that.

This module instead runs the flow axis under a compile-time `ShardPlan`
(repro.scenarios.plan_shards):

  * flows are permuted into per-shard rows so each shard's flows touch a
    CONTIGUOUS range of link ids that shard owns privately;
  * link ids are relabeled so every boundary link — one touched by flows
    of 2+ shards — sits at the TAIL of the id space;
  * per-shard RouteLayouts are compiled over the permuted routes and
    stacked, so each shard steps on its own CSR view.

Per epoch each shard reduces its private links entirely locally with the
normal `links` backends and exchanges only the trailing boundary slice
(`links.halo_exchange`, one contiguous psum of `plan.n_boundary` values
instead of `n_links + 1`).  On the standard 100k-flow dumbbell the
boundary is 2 links (the WAN pipe + the one downlink straddling the shard
cut) out of 51,563 — a ~25,000x smaller collective payload, boundary
fraction 0.0039% (`benchmarks/fleetsim_sweep.py` records it per run).
Queue state on links outside a shard's reach goes stale, but no local
flow reads it; the final state's link arrays are reassembled from each
link's owning shard before returning.

`unroll=K` fuses K epochs per scan step (the boundary collectives and
loop bookkeeping batch per step instead of paying per-epoch dispatch),
the padded initial state is donated to the compiled executable, and
compiled executables are cached per (mesh, scheme, epochs, backend,
halo, ...) so repeated calls — sweeps, benchmark reps — reuse them
(capacity via FLEETSIM_EXEC_CACHE / `set_executable_cache_size`;
hit/miss counters via `cache_stats`).
Measured on the 2-core dev container the fusion is neutral-to-negative
(XLA CPU loop overhead is tiny and the boundary psum is already
payload-free; compile time grows with K), so it defaults to 1 — it is
the knob to raise where per-step launch/collective dispatch dominates
(real device fleets).

Flow counts that do not divide the shard count are padded per shard with
*inert* flows (every hop -1: zero split, zero load, zero goodput).  Churn
IS supported under sharding now: every shard draws the same global
uniform vector from the replicated PRNG key and gathers its rows by
ORIGINAL flow id (`cc.make_step(churn_map=...)`), so the sharded run
flips exactly the flows the single-device run flips.  Sharded and
single-device runs agree to float-sum tolerance (reduction order
changes), which tests/test_fleet_scale.py pins across single-path,
multipath, lb, and churn scenarios.

Meshes come from `flow_mesh` (Auto axis types over explicit devices).  On
a TPU host one process drives every chip; on CPU the same code path is
exercised with `XLA_FLAGS=--xla_force_host_platform_device_count=N`
(device count must be set before jax initializes, so CPU tests and the
benchmark spawn a fresh interpreter).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.fleetsim import links as L
from repro.fleetsim.cc import steady_state_core
from repro.fleetsim.faults import FaultCarry, FaultSchedule
from repro.fleetsim.reliability import _LADDER_SHARED, RelParams, RelState
from repro.fleetsim.state import (ChurnParams, FleetParams, FleetState,
                                  LbParams, init_state)
from repro.sharding import make_mesh

AXIS = "flows"
# FleetState fields replicated across flow shards (cc._NON_FLOW_FIELDS
# additionally lists `active`, which IS per-flow — it is excluded there
# only because the churn merge sets it explicitly)
_REPLICATED = ("q_phys", "q_phantom", "key")


def flow_mesh(n_devices: Optional[int] = None, devices=None):
    """1-D Auto-axis mesh over `devices`, else the first `n_devices`
    (default: all) local devices."""
    if devices is None:
        devices = jax.devices()[:n_devices or len(jax.devices())]
    return make_mesh((len(devices),), (AXIS,), devices=devices)


def _contiguous_plan(n_real: int, n_links: int, n_shards: int):
    """The PR-3 layout as a ShardPlan: contiguous flow blocks, no link
    relabeling, every link boundary (full-buffer exchange)."""
    from repro.scenarios.compile_fleetsim import ShardPlan
    rows = -(-n_real // n_shards)
    ids = np.arange(n_shards * rows, dtype=np.int32)
    gather = np.where(ids < n_real, ids, n_real).reshape(n_shards, rows)
    eye = np.arange(n_links, dtype=np.int32)
    return ShardPlan(n_shards=n_shards, n_real=n_real, n_links=n_links,
                     n_boundary=n_links, gather=gather, new2old=eye,
                     old2new=eye,
                     owner_ptr=np.zeros(n_shards + 1, np.int32))


class ShardedFleet(NamedTuple):
    """A scenario compiled against one ShardPlan + mesh: flow axis
    permuted into per-shard rows, link ids relabeled boundary-last,
    per-shard RouteLayouts stacked along a leading shard axis.  Build
    once with `shard_scenario`, reuse across runs/backends — everything
    here is call-invariant."""
    plan: object                  # ShardPlan (host-side, never traced)
    mesh: object
    net: L.FluidNet               # permuted links+routes, layout=None
    layouts: L.RouteLayout        # stacked per-shard layouts (leading S axis)
    params: FleetParams           # flow axis permuted + padded
    is_inter: jnp.ndarray
    lb: Optional[LbParams]
    churn: Optional[ChurnParams]
    churn_map: Optional[jnp.ndarray]  # (S, rows) original flow id per row
    own: jnp.ndarray              # (S, n_links) link-ownership masks
    rel: Optional[RelParams] = None   # flow axis permuted + padded
    fault: Optional[FaultSchedule] = None  # link ids relabeled via old2new
    nbr: Optional[jnp.ndarray] = None  # (S, 2, P) neighbor-exchange index
    # table (links.halo_exchange nbr mode); None -> boundary psum fallback


def neighbor_halo(plan) -> Optional[np.ndarray]:
    """(S, 2, P) ppermute halo-exchange index table, or None when illegal.

    Legal iff every boundary link is touched by exactly one RING-ADJACENT
    shard pair {p, (p+1) % S} (ShardPlan.boundary_pairs) — the DC-major
    guarantee on ring/full multi-DC meshes, and trivially true on any
    2-shard mesh.  Pair group p (shared by shards p and p+1) is one global
    link list; shard p's row 0 is group p (its RIGHT group), row 1 group
    p-1 (LEFT), both padded to the widest group with `n_links` (the
    scratch slot).  Links with 3+ touchers (a hub fanning to many
    spokes) or a non-adjacent toucher pair (a ring DC pinned to both
    its neighbors d-1 / d+1 at S >= 4) make this return None — the psum
    path is the documented fallback there (see the mesh-by-mesh legality
    notes in repro.scenarios.multi_dc).
    """
    bp = getattr(plan, "boundary_pairs", None)
    S = plan.n_shards
    if bp is None or S < 2 or plan.n_boundary == 0:
        return None
    a = bp[:, 0].astype(np.int64)
    b = bp[:, 1].astype(np.int64)
    if np.any(a < 0):
        return None                       # 3+ touchers somewhere
    g = np.where((b - a) % S == 1, a,
                 np.where((a - b) % S == 1, b, -1))
    if np.any(g < 0):
        return None                       # non-adjacent pair
    base = plan.n_links - plan.n_boundary
    groups = [base + np.flatnonzero(g == gg) for gg in range(S)]
    width = max(gr.shape[0] for gr in groups)
    nbr = np.full((S, 2, width), plan.n_links, np.int32)
    for p in range(S):
        r, l = groups[p], groups[(p - 1) % S]
        nbr[p, 0, :r.shape[0]] = r
        nbr[p, 1, :l.shape[0]] = l
    return nbr


def _take_links(net: L.FluidNet, new2old: jnp.ndarray) -> L.FluidNet:
    """Permute every (n_links,) field of the net into the relabeled order."""
    return net._replace(
        cap=net.cap[new2old], qcap=net.qcap[new2old],
        ecn_lo=net.ecn_lo[new2old], ecn_hi=net.ecn_hi[new2old],
        drain=net.drain[new2old], vcap=net.vcap[new2old],
        use_phantom=net.use_phantom[new2old],
        p_loss=None if net.p_loss is None else net.p_loss[new2old])


def shard_scenario(net: L.FluidNet, params: FleetParams, *,
                   is_inter: Optional[jnp.ndarray] = None,
                   lb: Optional[LbParams] = None,
                   churn: Optional[ChurnParams] = None,
                   rel: Optional[RelParams] = None,
                   fault: Optional[FaultSchedule] = None,
                   mesh=None, locality: bool = True,
                   plan=None, link_tier=None, link_dc=None,
                   sender_private: Optional[bool] = None,
                   exchange: str = "auto", seed: int = 0,
                   path_table="auto") -> ShardedFleet:
    """Compile (net, params, ...) against a locality ShardPlan.

    `locality=False` reproduces the PR-3 contiguous-block sharding (full
    link buffer exchanged every epoch) — kept for A/B benchmarking.  An
    explicit `plan` overrides both.  `link_tier` (a (n_links,) locality
    array, e.g. FleetScenario.link_tier) feeds the planner's tier score
    on multi-tier topologies like the fat tree; `link_dc` (e.g.
    FleetScenario.link_dc) its DC-major shard order, and
    `sender_private` the first-hop rehoming pass (default: on exactly
    when `link_dc` is given).  `seed` fixes the planner's deal/split
    draws.  `rel` (RelParams) is
    permuted like the other flow-axis parameter families; padding rows
    are force-disabled so the reliability machine stays inert on them.

    `exchange` picks the boundary collective: "auto" uses the ppermute
    NEIGHBOR exchange whenever the plan proves every boundary link
    adjacent-pair-only (`neighbor_halo`) and falls back to the psum tail
    otherwise; "psum" forces the fallback; "nbr" demands the neighbor
    exchange and raises when the plan cannot support it.  In neighbor
    mode each boundary link's final queue state is reassembled from its
    FIRST toucher shard (both touchers hold the full two-shard sum).

    `path_table` controls the per-shard compressed PathTables: "auto"
    attaches them only when EVERY shard clears links.PT_MIN_COMPRESS
    (shard_map stacks the tables into one operand, so mixed flat/
    compressed shards cannot share an executable), True forces them,
    False keeps the flat layouts.  Shards whose unique-segment count
    falls short of the widest shard's are rebuilt padded to the common U
    so the stacked operand is rectangular.
    """
    from repro.scenarios.compile_fleetsim import plan_shards
    if exchange not in ("auto", "psum", "nbr"):
        raise ValueError(f"unknown boundary exchange {exchange!r}")
    mesh = mesh if mesh is not None else flow_mesh()
    n_dev = mesh.devices.size
    n_real = params.bdp.shape[0]
    routes3 = np.asarray(net.routes if net.routes.ndim == 3
                         else net.routes[:, None, :])
    if sender_private is None:
        sender_private = link_dc is not None
    if plan is None:
        plan = (plan_shards(routes3, net.n_links, n_dev,
                            link_tier=link_tier, seed=seed,
                            link_dc=link_dc,
                            sender_private=sender_private) if locality
                else _contiguous_plan(n_real, net.n_links, n_dev))
    if plan.n_shards != n_dev or plan.n_real != n_real:
        raise ValueError(
            f"plan is for {plan.n_shards} shards x {plan.n_real} flows, "
            f"mesh/params give {n_dev} x {n_real}")

    gflat = plan.flat_gather
    real = gflat < n_real
    gc_np = np.where(real, gflat, 0)
    gc = jnp.asarray(gc_np)
    realj = jnp.asarray(real)

    # routes: relabel link ids, permute flows, force inert padding rows
    relabeled = np.where(routes3 >= 0,
                         plan.old2new[np.clip(routes3, 0, None)], -1)
    routes_p = np.where(real[:, None, None], relabeled[gc_np], -1)
    routes_p = jnp.asarray(routes_p, jnp.int32)

    net_p = _take_links(net, jnp.asarray(plan.new2old))._replace(
        routes=routes_p, layout=None)
    rows = plan.rows
    shard_routes = [routes_p[s * rows:(s + 1) * rows]
                    for s in range(plan.n_shards)]
    lays = [L.compute_layout(r, net.n_links, path_table=False)
            for r in shard_routes]
    if path_table:
        min_c = L.PT_MIN_COMPRESS if path_table == "auto" else None
        pts = [L.compute_path_table(r, net.n_links, min_compress=min_c)
               for r in shard_routes]
        if all(pt is not None for pt in pts):
            # pad every shard's table to the widest (U, E1) so the stack
            # below sees one shape per field
            u_max = max(pt.n_segments for pt in pts)
            e1_max = max(pt.seg_gather.size for pt in pts)
            pts = [pt if pt.n_segments == u_max and
                   pt.seg_gather.size == e1_max else
                   L.compute_path_table(r, net.n_links,
                                        pad_segments_to=u_max,
                                        pad_entries_to=e1_max)
                   for r, pt in zip(shard_routes, pts)]
            lays = [lay._replace(path_table=pt)
                    for lay, pt in zip(lays, pts)]
    layouts = jax.tree.map(lambda *xs: jnp.stack(xs), *lays)

    params_p = jax.tree.map(lambda a: a[gc], params)
    if is_inter is None:
        is_inter = jnp.zeros(n_real, bool)
    ii_p = is_inter[gc] & realj
    lb_p = None if lb is None else jax.tree.map(lambda a: a[gc], lb)
    rel_p = None
    if rel is not None:
        # ladder arrays are RUNG-indexed (shared across flows): they ride
        # along unpermuted — gathering them by flow id would corrupt them
        rel_p = RelParams(**{
            f: (v if f in _LADDER_SHARED or v is None else v[gc])
            for f, v in zip(RelParams._fields, rel)})
        rel_p = rel_p._replace(enabled=rel.enabled[gc] & realj)
        if rel_p.adapt_on is not None:
            rel_p = rel_p._replace(adapt_on=rel.adapt_on[gc] & realj)
    fault_p = None
    if fault is not None:
        # schedule link ids live in the original link id space — relabel
        # them through the plan exactly like the route tensor
        o2n = jnp.asarray(plan.old2new)
        fault_p = fault._replace(link=o2n[fault.link],
                                 ge_link=o2n[fault.ge_link])
    churn_p = cmap = None
    if churn is not None:
        churn_p = ChurnParams(churned=churn.churned[gc] & realj,
                              mean_on=churn.mean_on[gc],
                              mean_off=churn.mean_off[gc])
        cmap = gc.reshape(plan.n_shards, rows).astype(jnp.int32)

    nbr = None
    if exchange != "psum":
        nbr = neighbor_halo(plan)
        if nbr is None and exchange == "nbr":
            raise ValueError(
                "exchange='nbr' but the plan's boundary links are not all "
                "ring-adjacent shard pairs (neighbor_halo); hub-spoke "
                "relays and straddled multi-shard hubs need the psum path")

    # link-ownership masks: shard s owns its private range plus (on shard
    # 0) any untouched links (identically zero everywhere).  The boundary
    # tail: under the psum exchange it is identical on every shard, so
    # shard 0 claims it wholesale; under the neighbor exchange only a
    # link's two touchers hold the full sum, so each boundary link is
    # credited to its FIRST toucher.
    iota = np.arange(plan.n_links)
    own = (iota >= plan.owner_ptr[:-1, None]) & \
        (iota < plan.owner_ptr[1:, None])
    base = plan.n_links - plan.n_boundary
    if nbr is None:
        own[0] |= iota >= base
    else:
        own[plan.boundary_pairs[:, 0],
            base + np.arange(plan.n_boundary)] = True
    return ShardedFleet(plan=plan, mesh=mesh, net=net_p, layouts=layouts,
                        params=params_p, is_inter=ii_p, lb=lb_p,
                        churn=churn_p, churn_map=cmap,
                        own=jnp.asarray(own), rel=rel_p, fault=fault_p,
                        nbr=None if nbr is None else jnp.asarray(nbr))


def _net_spec(has_ploss: bool = False) -> L.FluidNet:
    """PartitionSpec tree for FluidNet: routes sharded, links replicated."""
    return L.FluidNet(cap=P(), qcap=P(), ecn_lo=P(), ecn_hi=P(), drain=P(),
                      vcap=P(), use_phantom=P(), routes=P(AXIS), dt=P(),
                      layout=None, p_loss=P() if has_ploss else None)


def _state_spec(has_rel: bool = False, has_fault: bool = False) -> FleetState:
    """PartitionSpec tree for FleetState: link state + PRNG key replicated.
    The nested RelState (when present) is per-flow, so fully sharded; the
    FaultCarry (when present) is fully replicated — every shard advances
    an identical copy (same epoch counter, same chain PRNG)."""
    specs = {f: P() if f in _REPLICATED else P(AXIS)
             for f in FleetState._fields if f not in ("rel", "fault")}
    specs["rel"] = RelState(**{f: P(AXIS) for f in RelState._fields}) \
        if has_rel else None
    specs["fault"] = FaultCarry(epoch=P(), ge_bad=P(), key=P()) \
        if has_fault else None
    return FleetState(**specs)


# executable-cache capacity: FLEETSIM_EXEC_CACHE overrides the default
# (a long-lived sweep service juggling many shapes may want more; a
# memory-tight worker less).  Resize at runtime with
# `set_executable_cache_size`; inspect with `cache_stats`.
_EXEC_CACHE_DEFAULT = 64


def _exec_cache_size() -> int:
    return int(os.environ.get("FLEETSIM_EXEC_CACHE", _EXEC_CACHE_DEFAULT))


def _compiled_impl(mesh, scheme, n_warm, n_meas, backend, halo, unroll,
                   churn_n, has_lb, has_churn, has_rel, has_ploss=False,
                   has_pt=False, has_fault=False, has_ladder=False,
                   has_nbr=False):
    """Build the jitted shard_map'd steady-state executable (cached via
    `_compiled`).

    PR 3 rebuilt this closure (and its jit wrapper) inside every call, so
    every benchmark rep re-traced and re-compiled the whole scan — THE
    dominant cost of the old sharded path.  Everything value-like is a
    traced argument here; only genuinely static config is in the key.
    """
    pt_spec = None if not has_pt else L.PathTable(
        **{f: P(AXIS) for f in L.PathTable._fields})
    lay_spec = L.RouteLayout(
        **{f: P(AXIS) for f in L.RouteLayout._fields
           if f != "path_table"}, path_table=pt_spec)
    param_spec = FleetParams(**{f: P(AXIS) for f in FleetParams._fields})
    lb_spec = None if not has_lb else LbParams(
        **{f: P(AXIS) for f in LbParams._fields})
    rel_spec = None
    if has_rel:
        # per-flow fields shard; the rung-indexed ladder tables replicate
        rd = {f: P(AXIS) for f in RelParams._fields}
        for f in _LADDER_SHARED:
            rd[f] = P() if has_ladder else None
        rd["adapt_on"] = P(AXIS) if has_ladder else None
        rel_spec = RelParams(**rd)
    fault_spec = None if not has_fault else FaultSchedule(
        **{f: P() for f in FaultSchedule._fields})
    churn_spec = cmap_spec = None
    if has_churn:
        churn_spec = ChurnParams(
            **{f: P(AXIS) for f in ChurnParams._fields})
        cmap_spec = P(AXIS)

    def local(net_l, lay_l, params_l, state0_l, ii_l, lb_l, churn_l,
              cmap_l, own_l, rel_l, fault_l, nbr_l):
        net_l = net_l._replace(layout=jax.tree.map(lambda a: a[0], lay_l))
        final, rates = steady_state_core(
            net_l, params_l, state0_l, ii_l, scheme=scheme, n_warm=n_warm,
            n_meas=n_meas, lb=lb_l, churn=churn_l, backend=backend,
            axis_name=AXIS, halo=halo,
            churn_map=None if cmap_l is None else cmap_l[0],
            churn_n=churn_n, unroll=unroll, rel=rel_l, fault=fault_l,
            nbr=nbr_l[0] if has_nbr else None,
            n_shards=mesh.devices.size if has_nbr else None)
        # reassemble globally-correct link state from each link's owner
        own = own_l[0]
        return final._replace(
            q_phys=jax.lax.psum(
                jnp.where(own, final.q_phys, 0.0), AXIS),
            q_phantom=jax.lax.psum(
                jnp.where(own, final.q_phantom, 0.0), AXIS)), rates

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(_net_spec(has_ploss), lay_spec, param_spec,
                                _state_spec(has_rel, has_fault), P(AXIS),
                                lb_spec, churn_spec, cmap_spec, P(AXIS),
                                rel_spec, fault_spec,
                                P(AXIS) if has_nbr else None),
                      out_specs=(_state_spec(has_rel, has_fault), P(AXIS)),
                      check_vma=False)
    return jax.jit(f, donate_argnums=(3,))


_compiled = functools.lru_cache(maxsize=_exec_cache_size())(_compiled_impl)


def set_executable_cache_size(maxsize: int) -> None:
    """Rebuild the compiled-executable cache with a new capacity.

    Drops every cached executable (the next call per config re-traces),
    so resize at service startup, not mid-sweep.  The initial capacity
    comes from the FLEETSIM_EXEC_CACHE env var (default 64)."""
    global _compiled
    _compiled = functools.lru_cache(maxsize=int(maxsize))(_compiled_impl)


def cache_stats() -> dict:
    """Hit/miss counters of the compiled-executable cache.

    A healthy warm service shows hits >> misses; misses == distinct
    (mesh, scheme, epochs, backend, halo, ...) configs seen.  `evictions`
    > 0 means the working set exceeds the capacity — raise
    FLEETSIM_EXEC_CACHE (or call `set_executable_cache_size`) before
    trusting warm-latency numbers."""
    info = _compiled.cache_info()
    return {"hits": info.hits, "misses": info.misses,
            "maxsize": info.maxsize, "currsize": info.currsize,
            "evictions": max(info.misses - info.currsize, 0)}


def _permute_state(state: FleetState, flow_idx: jnp.ndarray,
                   link_idx: jnp.ndarray) -> FleetState:
    """Reindex a FleetState: per-flow fields by `flow_idx`, link-shaped
    replicated fields by `link_idx`, the PRNG key untouched.  One place
    decides the classification (keyed on _REPLICATED, same as
    _state_spec) for both the permute-in and permute-out directions."""
    out = {}
    for f in FleetState._fields:
        v = getattr(state, f)
        if f == "key" or v is None:
            out[f] = v
        elif f == "fault":   # replicated carry: nothing flow/link-indexed
            out[f] = v
        elif f in _REPLICATED:
            out[f] = v[link_idx]
        elif hasattr(v, "_fields"):  # nested per-flow pytree (RelState)
            out[f] = jax.tree.map(lambda a: a[flow_idx], v)
        else:
            out[f] = v[flow_idx]
    return FleetState(**out)


def _unalias(state: FleetState) -> FleetState:
    """Fresh buffer per leaf.  init_state reuses one zeros array across
    many fields (and cc_countdown aliases params.cc_period); donating an
    aliased pytree trips XLA's double-donation check, so the one state we
    donate per run is copied leaf-by-leaf first — the copy is what
    donation then saves on every fused scan step."""
    return jax.tree.map(lambda x: jnp.array(x, copy=True), state)


def steady_state_prepared(sf: ShardedFleet, *, n_warm: int, n_meas: int,
                          scheme: str = "uno", backend: str = "auto",
                          unroll: int = 1,
                          state0: Optional[FleetState] = None,
                          seed: int = 0):
    """`cc.steady_state` over an already-compiled ShardedFleet.

    Returns (final_state, mean goodput) in the ORIGINAL flow and link
    order with padding stripped.  `state0`, when given, must match the
    unpadded flow count and original ordering — it is permuted in (its
    buffers are never donated; the permuted copy is).
    """
    plan, net = sf.plan, sf.net
    if state0 is None:
        state0 = init_state(sf.params, net.n_links, n_paths=net.n_paths,
                            split0=L.uniform_split(net), seed=seed,
                            rel=sf.rel, fault=sf.fault)
    else:
        if state0.cwnd.shape[0] != plan.n_real:
            raise ValueError("state0 flow count does not match the plan")
        if (state0.rel is None) != (sf.rel is None):
            raise ValueError("state0 rel state does not match the "
                             "scenario's RelParams presence")
        if (state0.fault is None) != (sf.fault is None):
            raise ValueError("state0 fault carry does not match the "
                             "scenario's FaultSchedule presence")
        gflat = plan.flat_gather
        real = gflat < plan.n_real
        gc = jnp.asarray(np.where(real, gflat, 0))
        realj = jnp.asarray(real)
        state0 = _permute_state(state0, gc, jnp.asarray(plan.new2old))
        # inert padding must carry zero split weight, not a real flow's copy
        state0 = state0._replace(
            split=jnp.where(realj[:, None], state0.split, 0.0))

    run = _compiled(sf.mesh, scheme, n_warm, n_meas, backend,
                    plan.n_boundary, unroll,
                    None if sf.churn is None else plan.n_real,
                    sf.lb is not None, sf.churn is not None,
                    sf.rel is not None, net.p_loss is not None,
                    sf.layouts.path_table is not None,
                    sf.fault is not None,
                    sf.rel is not None and sf.rel.ladder_k is not None,
                    sf.nbr is not None)
    final, rates = run(net, sf.layouts, sf.params, _unalias(state0),
                       sf.is_inter, sf.lb, sf.churn, sf.churn_map, sf.own,
                       sf.rel, sf.fault, sf.nbr)

    inv = jnp.asarray(plan.inverse_flow)
    return (_permute_state(final, inv, jnp.asarray(plan.old2new)),
            rates[inv])


def steady_state_sharded(net: L.FluidNet, params: FleetParams, *,
                         n_warm: int, n_meas: int, scheme: str = "uno",
                         is_inter: Optional[jnp.ndarray] = None,
                         lb: Optional[LbParams] = None,
                         churn: Optional[ChurnParams] = None,
                         rel: Optional[RelParams] = None,
                         fault: Optional[FaultSchedule] = None,
                         state0: Optional[FleetState] = None,
                         mesh=None, backend: str = "auto",
                         locality: bool = True, plan=None,
                         link_tier=None, link_dc=None,
                         sender_private: Optional[bool] = None,
                         exchange: str = "auto", path_table="auto",
                         unroll: int = 1, seed: int = 0):
    """`cc.steady_state` with the flow axis sharded over `mesh` (default:
    all local devices) under a locality ShardPlan — one-shot convenience
    over `shard_scenario` + `steady_state_prepared`.  Repeated runs over
    the same scenario should build the ShardedFleet once and call
    `steady_state_prepared` directly (the scenario compile — plan,
    permutation, per-shard layouts — is the only per-call host work; the
    executable itself is cached either way)."""
    sf = shard_scenario(net, params, is_inter=is_inter, lb=lb, churn=churn,
                        rel=rel, fault=fault, mesh=mesh, locality=locality,
                        plan=plan, link_tier=link_tier, link_dc=link_dc,
                        sender_private=sender_private, exchange=exchange,
                        seed=seed, path_table=path_table)
    return steady_state_prepared(sf, n_warm=n_warm, n_meas=n_meas,
                                 scheme=scheme, backend=backend,
                                 unroll=unroll, state0=state0, seed=seed)
