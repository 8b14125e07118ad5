"""Scenario sweeps: `vmap` whole fluid simulations across parameter grids.

A "scenario" is (FluidNet, FleetParams, is_inter[, LbParams[, ChurnParams
[, RelParams]]]) — pure pytrees of arrays; `repro.scenarios.FleetScenario`
instances are accepted directly.  Scenarios that share shapes (same
n_flows / n_paths / n_links / max_hops) stack along a leading axis and one
jitted vmapped call (`_grid_core`, cached at module level so same-shape
grids trace/compile once per process — `grid_traces()` counts) sweeps the
whole grid: RTT ratios x phantom drain fractions, flow-count mixes, load
levels, churn duty cycles, loss-recovery configs — heatmaps the
per-packet simulator cannot reach (its wall-clock per cell is minutes; a
fluid cell is milliseconds).  `run_grid_streamed` evaluates the same grid
in fixed-size chunks and yields completed cells as a generator (the
sweep service's partial-results path).

Numeric knobs (RTT, drain, caps, even route link-ids) may vary freely across
the grid; only array *shapes* must match, and the LB / churn / reliability
axes must be present on all scenarios or none.  Flow-count mixes therefore
keep the total flow count fixed and flip flows between intra and inter
profiles.

`run_grid(mesh=...)` additionally shards the FLOW axis of every grid cell
under one locality ShardPlan (repro.fleetsim.shard) while the grid axis
vmaps inside each shard — vmapped sweeps at 100k+ flows then pay the same
boundary-only halo exchange as single-scenario sharded runs, with
`link_tier` (or the cells' FleetScenario.link_tier) feeding the planner's
tier score.  The plan is shared, so every cell must route identically
(the concrete sweeps here vary caps/params/rel, never routes); grids with
differing routes fall back to the single-device vmap path with a warning.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleetsim import links as fl
from repro.fleetsim.cc import steady_state_core
from repro.fleetsim.state import init_state, make_params

US = fl.US
_SUM_CHUNK = 1024


def fleet_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Compensated float32 sum along `axis`, accurate at 10^6+ flows.

    A naive float32 accumulation of n ~ 1e5-1e6 per-flow rates carries
    O(n * eps) rounding — enough to visibly bias Jain / utilization
    numbers whose interesting differences are in the third decimal.
    Chunked Neumaier summation (pairwise inside `_SUM_CHUNK`-sized chunks,
    a compensated carry across them) keeps the error near 1 ulp of the
    true sum without needing the x64 mode this repo leaves off.
    """
    x = jnp.moveaxis(jnp.asarray(x, jnp.float32), axis, -1)
    n = x.shape[-1]
    pad = (-n) % _SUM_CHUNK
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    chunks = jnp.moveaxis(
        x.reshape(x.shape[:-1] + (-1, _SUM_CHUNK)), -2, 0)

    def body(carry, c):
        s, comp = carry
        y = jnp.sum(c, axis=-1)
        t = s + y
        comp = comp + jnp.where(jnp.abs(s) >= jnp.abs(y),
                                (s - t) + y, (y - t) + s)
        return (t, comp), None

    zero = jnp.zeros(x.shape[:-1], x.dtype)
    (s, comp), _ = jax.lax.scan(body, (zero, zero), chunks)
    return s + comp


def jain(rates: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Jain fairness index along `axis` (1.0 = perfectly fair).

    Both reductions (sum of rates, sum of squares) run through the
    compensated `fleet_sum` so the index stays meaningful at 100k+ flows.
    """
    s = fleet_sum(rates, axis=axis)
    s2 = fleet_sum(rates * rates, axis=axis)
    n = rates.shape[axis]
    return s * s / jnp.maximum(n * s2, 1e-12)


def _norm_scenario(sc):
    """Scenario -> (net, params, is_inter, lb, churn, rel, fault).

    Accepts a FleetScenario instance (any NamedTuple with these field
    names) or a bare (net, params, is_inter[, lb[, churn[, rel[,
    fault]]]]) tuple; absent trailing axes pad with None.
    """
    if hasattr(sc, "net") and hasattr(sc, "params"):
        return (sc.net, sc.params, sc.is_inter, getattr(sc, "lb", None),
                getattr(sc, "churn", None), getattr(sc, "rel", None),
                getattr(sc, "fault", None))
    sc = tuple(sc)
    if not 3 <= len(sc) <= 7:
        raise ValueError(f"scenario tuple of length {len(sc)}")
    net, params, ii = sc[:3]
    lb = sc[3] if len(sc) > 3 else None
    churn = sc[4] if len(sc) > 4 else None
    rel = sc[5] if len(sc) > 5 else None
    fault = sc[6] if len(sc) > 6 else None
    return net, params, ii, lb, churn, rel, fault


def _strip_unstackable_path_tables(nets):
    """Drop per-cell PathTables that cannot stack into one grid operand.

    Cells with different route tensors dedupe to different unique-segment
    counts (load_mix_sweep rebuilds routes per cell), so their tables'
    shapes disagree and jnp.stack would fail; a mix of flat and compressed
    layouts is just as unstackable.  Every cell keeps its flat layout
    fields, so the sweep silently falls back to the CSR backend — correct,
    just uncompressed.
    """
    pts = [None if n.layout is None else n.layout.path_table for n in nets]
    if all(pt is None for pt in pts):
        return nets
    sigs = {None if pt is None else
            tuple(jnp.shape(leaf) for leaf in pt) for pt in pts}
    if len(sigs) == 1:
        return nets
    warnings.warn("stack_scenarios: per-cell PathTables have mismatched "
                  "shapes; stripping them (cells fall back to the flat "
                  "CSR backend)")
    return tuple(
        n if n.layout is None or n.layout.path_table is None
        else n._replace(layout=n.layout._replace(path_table=None))
        for n in nets)


def stack_scenarios(scenarios: Sequence[tuple]):
    """Stack same-shape scenario pytrees on a leading axis.

    Returns (nets, params, is_inter, lb, churn, rel, fault); the LB /
    churn / reliability / fault slots are None when absent (each must be
    present on all scenarios or none — a fault grid pads inactive cells
    with inert events, see `fault_sweep`).  Per-cell PathTables survive
    the stack only when every cell carries one of identical shape (see
    `_strip_unstackable_path_tables`).
    """
    nets, params, inters, lbs, churns, rels, faults = zip(
        *(_norm_scenario(s) for s in scenarios))
    for tag, xs in (("lb", lbs), ("churn", churns), ("rel", rels),
                    ("fault", faults)):
        if any(x is None for x in xs) != all(x is None for x in xs):
            raise ValueError(f"{tag} must be set on all scenarios or none")
    nets = _strip_unstackable_path_tables(nets)
    stk = lambda *xs: jnp.stack(xs)
    return (jax.tree.map(stk, *nets), jax.tree.map(stk, *params),
            jnp.stack(inters),
            None if lbs[0] is None else jax.tree.map(stk, *lbs),
            None if churns[0] is None else jax.tree.map(stk, *churns),
            None if rels[0] is None else jax.tree.map(stk, *rels),
            None if faults[0] is None else jax.tree.map(stk, *faults))


_GRID_TRACES = [0]        # bumped at TRACE time inside _grid_core


def grid_traces() -> int:
    """How many times the grid executable has (re)traced this process.

    `_grid_core` is a module-level jitted function, so jax's own jit cache
    keys it on the stacked operands' shapes/dtypes/treedefs plus the
    static config — repeat grids of the same shape signature reuse the
    compiled executable and leave this counter unchanged.  The sweep
    service reads it to prove warm batches really did skip the trace.
    """
    return _GRID_TRACES[0]


@functools.partial(jax.jit, static_argnames=("scheme", "n_warm", "n_meas",
                                             "backend"))
def _grid_core(nets, params, inters, lb, churn, rel, seeds, fault=None, *,
               scheme, n_warm, n_meas, backend):
    """The one grid executable: vmapped init + steady state over stacked
    scenario pytrees.

    Module-level on purpose — the old `jax.jit(jax.vmap(one))` closure was
    rebuilt inside every `run_grid` call, so every grid invocation paid a
    fresh trace + XLA compile even for identical shapes.  Here the trace
    cache persists for the process lifetime: N same-shape grid calls cost
    one trace (see `grid_traces`).  The initial-state construction is
    traced INTO the executable (one fused init, no host loop); the
    optional lb / churn / rel axes vmap as empty pytrees when absent.
    """
    _GRID_TRACES[0] += 1
    n_links = nets.cap.shape[1]
    n_paths = nets.routes.shape[2] if nets.routes.ndim == 4 else 1
    splits = jax.vmap(fl.uniform_split)(nets)
    state0 = jax.vmap(
        lambda p, s0, sd, r, fa: init_state(p, n_links, n_paths=n_paths,
                                            split0=s0, seed=sd, rel=r,
                                            fault=fa)
    )(params, splits, seeds, rel, fault)

    def one(net, p, s0, ii, lb_i, churn_i, rel_i, fault_i):
        return steady_state_core(net, p, s0, ii, scheme, n_warm, n_meas,
                                 lb_i, churn_i, backend, rel=rel_i,
                                 fault=fault_i)

    return jax.vmap(one)(nets, params, state0, inters, lb, churn, rel,
                         fault)


def _grid_seeds(n: int, seed: int, seeds) -> jnp.ndarray:
    if seeds is None:
        return seed + jnp.arange(n, dtype=jnp.int32)
    seeds = jnp.asarray(seeds, jnp.int32)
    if seeds.shape != (n,):
        raise ValueError(f"seeds shape {seeds.shape} != ({n},)")
    return seeds


def run_grid(scenarios: Sequence[tuple], *, scheme: str = "uno",
             n_warm: int = 50_000, n_meas: int = 10_000, seed: int = 0,
             seeds=None, mesh=None, link_tier=None, unroll: int = 1,
             backend: str = "auto"):
    """Sweep all scenarios in one vmapped call.

    Returns (final_states, rates): each leaf carries a leading scenario
    axis; `rates` is (n_scenarios, n_flows) mean steady goodput in bytes/ns.
    Churn PRNGs are derived from `seed` + the scenario index (or an
    explicit per-cell `seeds` array — the sweep service uses it so a
    cell's result never depends on which batch it rode in), so a grid is
    reproducible end to end.  The vmapped executable is cached at module
    level (`_grid_core`): repeat grids with the same shape signature and
    static config skip the trace + compile entirely.

    `mesh` shards the flow axis of every cell over the mesh devices under
    ONE locality ShardPlan (the grid axis vmaps inside each shard);
    `link_tier` feeds the planner — when omitted it is taken from the
    first FleetScenario cell that carries one.  The shared plan requires
    identical routes across cells; grids that vary routes fall back to the
    single-device vmap path with a warning.
    """
    if mesh is not None:
        out = _run_grid_sharded(scenarios, scheme, n_warm, n_meas, seed,
                                mesh, link_tier, unroll, backend)
        if out is not None:
            return out
    with jax.profiler.TraceAnnotation("fleetsim.stack"):
        nets, params, inters, lb, churn, rel, fault = \
            stack_scenarios(scenarios)
        sd = _grid_seeds(len(scenarios), seed, seeds)
    with jax.profiler.TraceAnnotation("fleetsim.dispatch"):
        return _grid_core(nets, params, inters, lb, churn, rel, sd, fault,
                          scheme=scheme, n_warm=n_warm, n_meas=n_meas,
                          backend=backend)


def run_grid_streamed(scenarios: Sequence[tuple], *, chunk: int = 8,
                      scheme: str = "uno", n_warm: int = 50_000,
                      n_meas: int = 10_000, seed: int = 0, seeds=None,
                      backend: str = "auto"):
    """Generator variant of `run_grid`: evaluate in fixed-size chunks,
    yielding `(index, final_state_cell, rates_cell)` per completed cell in
    submission order — a 100-cell grid shows first results after one
    chunk instead of after the whole grid.

    Results are identical to `run_grid` over the same list (cell i keeps
    churn seed `seed + i` regardless of chunking); only latency-to-first-
    cell changes.  The tail chunk is padded by replicating its last cell,
    so every chunk presents the same stacked shapes and the whole stream
    reuses ONE `_grid_core` executable — the first chunk pays the trace,
    the rest are pure scan time.
    """
    n = len(scenarios)
    if n == 0:
        return
    chunk = max(1, chunk)
    sd = np.asarray(_grid_seeds(n, seed, seeds))
    for lo in range(0, n, chunk):
        cells = list(scenarios[lo:lo + chunk])
        live = len(cells)
        csd = sd[lo:lo + chunk]
        if live < chunk:
            cells += [cells[-1]] * (chunk - live)
            csd = np.concatenate(
                [csd, np.repeat(csd[-1], chunk - live)])
        final, rates = run_grid(cells, scheme=scheme, n_warm=n_warm,
                                n_meas=n_meas, seeds=csd, backend=backend)
        jax.block_until_ready(rates)
        for i in range(live):
            yield (lo + i, jax.tree.map(lambda a, j=i: a[j], final),
                   rates[i])


def _run_grid_sharded(scenarios, scheme, n_warm, n_meas, seed, mesh,
                      link_tier, unroll, backend):
    """Flow-sharded grid sweep: one ShardPlan, grid vmapped inside shards.

    Returns None (after warning) when the cells' routes differ — the
    caller then takes the single-device vmap path.  Results come back in
    the ORIGINAL flow/link order with padding stripped, same contract as
    the vmap path.
    """
    from jax.sharding import PartitionSpec as P
    from repro.fleetsim import shard as sh

    norm = [_norm_scenario(s) for s in scenarios]
    for tag, i in (("lb", 3), ("churn", 4), ("rel", 5), ("fault", 6)):
        xs = [nm[i] for nm in norm]
        if any(x is None for x in xs) != all(x is None for x in xs):
            raise ValueError(f"{tag} must be set on all scenarios or none")
    r0 = np.asarray(norm[0][0].routes)
    if any(not np.array_equal(r0, np.asarray(nm[0].routes))
           for nm in norm[1:]):
        warnings.warn(
            "run_grid(mesh=...) needs identical routes across grid cells "
            "to share one ShardPlan; falling back to the single-device "
            "vmap path", RuntimeWarning, stacklevel=3)
        return None
    if link_tier is None:
        for s in scenarios:
            link_tier = getattr(s, "link_tier", None)
            if link_tier is not None:
                break

    # compile the shared plan + permuted routes + per-shard layouts ONCE
    # (cell 0), then permute each cell's value arrays against it
    net0, params0, ii0, lb0, churn0, rel0, fault0 = norm[0]
    sf0 = sh.shard_scenario(net0, params0, is_inter=ii0, lb=lb0,
                            churn=churn0, rel=rel0, fault=fault0,
                            mesh=mesh, link_tier=link_tier)
    plan = sf0.plan
    gflat = plan.flat_gather
    real = gflat < plan.n_real
    gc = jnp.asarray(np.where(real, gflat, 0))
    realj = jnp.asarray(real)
    new2old = jnp.asarray(plan.new2old)
    old2new = jnp.asarray(plan.old2new)

    from repro.fleetsim.reliability import _LADDER_SHARED, RelParams

    def permute_cell(nm):
        net, params, ii, lb, churn, rel, fault = nm
        net_p = sh._take_links(net, new2old)._replace(
            routes=sf0.net.routes, layout=None)
        params_p = jax.tree.map(lambda a: a[gc], params)
        ii_p = ii[gc] & realj
        lb_p = None if lb is None else jax.tree.map(lambda a: a[gc], lb)
        rel_p = None
        if rel is not None:
            # rung-indexed ladder tables are shared, never flow-gathered
            rel_p = RelParams(**{
                f: (v if f in _LADDER_SHARED or v is None else v[gc])
                for f, v in zip(RelParams._fields, rel)})
            rel_p = rel_p._replace(enabled=rel.enabled[gc] & realj)
            if rel_p.adapt_on is not None:
                rel_p = rel_p._replace(adapt_on=rel.adapt_on[gc] & realj)
        churn_p = None
        if churn is not None:
            churn_p = churn._replace(churned=churn.churned[gc] & realj,
                                     mean_on=churn.mean_on[gc],
                                     mean_off=churn.mean_off[gc])
        fault_p = None if fault is None else fault._replace(
            link=old2new[fault.link], ge_link=old2new[fault.ge_link])
        return net_p, params_p, ii_p, lb_p, churn_p, rel_p, fault_p

    cells = [permute_cell(nm) for nm in norm]
    stk = lambda *xs: jnp.stack(xs)
    nets = jax.tree.map(stk, *(c[0] for c in cells))
    params = jax.tree.map(stk, *(c[1] for c in cells))
    inters = jnp.stack([c[2] for c in cells])
    lb = None if cells[0][3] is None else \
        jax.tree.map(stk, *(c[3] for c in cells))
    churn = None if cells[0][4] is None else \
        jax.tree.map(stk, *(c[4] for c in cells))
    rel = None if cells[0][5] is None else \
        jax.tree.map(stk, *(c[5] for c in cells))
    fault = None if cells[0][6] is None else \
        jax.tree.map(stk, *(c[6] for c in cells))

    n_links = plan.n_links
    n_paths = nets.routes.shape[2] if nets.routes.ndim == 4 else 1
    seeds = seed + jnp.arange(len(scenarios), dtype=jnp.int32)
    splits = jax.vmap(fl.uniform_split)(nets)  # zero on inert padding rows

    def init_cell(p, s0, sd, r, fa):
        return init_state(p, n_links, n_paths=n_paths, split0=s0, seed=sd,
                          rel=r, fault=fa)

    state0 = jax.vmap(init_cell)(params, splits, seeds, rel, fault)

    churn_n = None if churn is None else plan.n_real
    has = lambda x: x is not None
    g = lambda spec: jax.tree.map(lambda s: P(None, *s), spec)

    def local(nets_l, lay_l, params_l, state0_l, ii_l, lb_l, churn_l,
              cmap_l, own_l, rel_l, fault_l):
        lay = jax.tree.map(lambda a: a[0], lay_l)
        own = own_l[0]
        cmap = None if cmap_l is None else cmap_l[0]

        def one(net_c, p_c, s0_c, ii_c, lb_c, churn_c, rel_c, fault_c):
            net_c = net_c._replace(layout=lay)
            final, rates = steady_state_core(
                net_c, p_c, s0_c, ii_c, scheme=scheme, n_warm=n_warm,
                n_meas=n_meas, lb=lb_c, churn=churn_c, backend=backend,
                axis_name=sh.AXIS, halo=plan.n_boundary, churn_map=cmap,
                churn_n=churn_n, unroll=unroll, rel=rel_c, fault=fault_c)
            return final._replace(
                q_phys=jax.lax.psum(
                    jnp.where(own, final.q_phys, 0.0), sh.AXIS),
                q_phantom=jax.lax.psum(
                    jnp.where(own, final.q_phantom, 0.0), sh.AXIS)), rates

        axes = (0, 0, 0, 0, 0 if has(lb_l) else None,
                0 if has(churn_l) else None, 0 if has(rel_l) else None,
                0 if has(fault_l) else None)
        return jax.vmap(one, in_axes=axes)(
            nets_l, params_l, state0_l, ii_l, lb_l, churn_l, rel_l,
            fault_l)

    from repro.fleetsim.faults import FaultSchedule
    from repro.fleetsim.state import ChurnParams, FleetParams, LbParams
    AXIS = sh.AXIS
    # one spec per layout leaf — the optional nested PathTable subtree
    # (present on deep-multipath shards) must get specs too
    lay_spec = jax.tree.map(lambda _: P(AXIS), sf0.layouts)
    param_spec = g(FleetParams(
        **{f: P(AXIS) for f in FleetParams._fields}))
    lb_spec = None if lb is None else g(LbParams(
        **{f: P(AXIS) for f in LbParams._fields}))
    rel_spec = None
    if rel is not None:
        rd = {f: P(AXIS) for f in RelParams._fields}
        for fname in _LADDER_SHARED:
            rd[fname] = P() if rel.ladder_k is not None else None
        rd["adapt_on"] = P(AXIS) if rel.ladder_k is not None else None
        rel_spec = g(RelParams(**rd))
    fault_spec = None if fault is None else g(FaultSchedule(
        **{f: P() for f in FaultSchedule._fields}))
    churn_spec = cmap_spec = None
    if churn is not None:
        churn_spec = g(ChurnParams(
            **{f: P(AXIS) for f in ChurnParams._fields}))
        cmap_spec = P(AXIS)
    state_spec = g(sh._state_spec(rel is not None, fault is not None))

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(g(sh._net_spec(nets.p_loss is not None)),
                                lay_spec, param_spec,
                                state_spec, g(P(AXIS)), lb_spec, churn_spec,
                                cmap_spec, P(AXIS), rel_spec, fault_spec),
                      out_specs=(state_spec, g(P(AXIS))),
                      check_vma=False)
    final, rates = jax.jit(f)(nets, sf0.layouts, params, state0, inters,
                              lb, churn, sf0.churn_map, sf0.own, rel,
                              fault)

    inv = jnp.asarray(plan.inverse_flow)
    old2new = jnp.asarray(plan.old2new)
    final = jax.vmap(lambda s: sh._permute_state(s, inv, old2new))(final)
    return final, rates[:, inv]


# ------------------------------------------------------------ concrete sweeps

def fairness_sweep(rtt_ratios: Sequence[float],
                   drain_fracs: Sequence[float], *,
                   n_intra: int = 4, n_inter: int = 4,
                   rate: float = fl.RATE_100G, intra_rtt: float = 14 * US,
                   scheme: str = "uno", multipath: bool = False,
                   n_wan: int = 8, n_warm: int = 50_000,
                   n_meas: int = 10_000) -> dict:
    """Inter/intra fairness heatmap over (RTT ratio x phantom drain frac).

    The paper's Fig 11 question at grid scale: does fairness survive as the
    inter-DC RTT grows and as the phantom drain (the utilization target)
    moves?  `multipath=True` gives inter flows UnoLB-style adaptive subflow
    splits over `n_wan` separate border links instead of the aggregated
    pipe.  Returns 2D (len(rtt_ratios), len(drain_fracs)) arrays:
    'jain', 'class_ratio' (mean inter / mean intra rate), 'util'.
    """
    from repro.scenarios import dumbbell_scenario, to_fleetsim
    scen, shape = [], (len(rtt_ratios), len(drain_fracs))
    for ratio in rtt_ratios:
        for drain in drain_fracs:
            fs = to_fleetsim(dumbbell_scenario(
                n_intra, n_inter, rate=rate, intra_rtt=intra_rtt,
                inter_rtt=ratio * intra_rtt, drain_frac=drain,
                multipath=multipath, n_wan=n_wan))
            scen.append(fs)
    _, rates = run_grid(scen, scheme=scheme, n_warm=n_warm, n_meas=n_meas)
    ii = jnp.arange(n_intra + n_inter) >= n_intra
    mean_inter = jnp.mean(rates[:, ii], axis=1) if n_inter else \
        jnp.zeros(rates.shape[0])
    mean_intra = jnp.mean(rates[:, ~ii], axis=1) if n_intra else \
        jnp.ones(rates.shape[0])
    return {
        "rtt_ratios": jnp.asarray(rtt_ratios),
        "drain_fracs": jnp.asarray(drain_fracs),
        "rates": rates.reshape(shape + (n_intra + n_inter,)),
        "jain": jain(rates).reshape(shape),
        "class_ratio": (mean_inter / jnp.maximum(mean_intra, 1e-9))
        .reshape(shape),
        "util": (fleet_sum(rates, axis=1) / rate).reshape(shape),
    }


def load_mix_sweep(inter_counts: Sequence[int],
                   loads: Sequence[float], *, n_total: int = 16,
                   rate: float = fl.RATE_100G, intra_rtt: float = 14 * US,
                   inter_rtt: float = 2 * fl.MS, scheme: str = "uno",
                   n_warm: int = 50_000, n_meas: int = 10_000) -> dict:
    """Heatmap over (flow-count mix x bottleneck load).

    `loads` scales the bottleneck capacity relative to the flows' access
    rate (load 1.0 = the incast exactly fills the receiver link; >1
    oversubscribed).  Total flow count stays `n_total` so shapes match;
    scenario (m, l) runs m inter + (n_total - m) intra flows into a
    bottleneck of capacity rate / load.
    """
    scen, shape = [], (len(inter_counts), len(loads))
    # ONE base dumbbell (fixed link layout: n_total uplinks + wan +
    # bottleneck, so all grid cells stack); each cell then varies only the
    # per-cell arrays — routes + flow profile once per mix m (the m inter
    # flows repoint hop 0 at the WAN pipe, recompiling the RouteLayout),
    # cap/drain once per load level — instead of rebuilding and recompiling
    # the whole scenario spec per cell.
    base, bdp0, rtt0 = fl.dumbbell(n_total, 0, rate=rate,
                                   intra_rtt=intra_rtt, inter_rtt=inter_rtt)
    wan, down = n_total, base.cap.shape[0] - 1
    for m in inter_counts:
        if not 0 <= m <= n_total:
            raise ValueError(f"inter count {m} not in [0, {n_total}]")
        ii = jnp.arange(n_total) >= (n_total - m)
        routes = jnp.where(ii[:, None, None] & (jnp.arange(2) == 0),
                           wan, base.routes).astype(jnp.int32)
        net_m = fl.with_layout(base._replace(routes=routes))
        p = make_params(jnp.where(ii, rate * inter_rtt, bdp0),
                        jnp.where(ii, inter_rtt, rtt0),
                        rate * intra_rtt, intra_rtt)
        for load in loads:
            net = net_m._replace(
                cap=net_m.cap.at[down].mul(1.0 / load),
                drain=net_m.drain.at[down].mul(1.0 / load))
            scen.append((net, p, ii))
    _, rates = run_grid(scen, scheme=scheme, n_warm=n_warm, n_meas=n_meas)
    return {
        "inter_counts": jnp.asarray(inter_counts),
        "loads": jnp.asarray(loads),
        "rates": rates.reshape(shape + (n_total,)),
        "jain": jain(rates).reshape(shape),
        "util": (fleet_sum(rates, axis=1) / rate).reshape(shape),
    }


def churn_sweep(duty_fracs: Sequence[float],
                mean_on_rtts: Sequence[float], *, n_flows: int = 16,
                rate: float = fl.RATE_100G, intra_rtt: float = 14 * US,
                scheme: str = "uno", n_warm: int = 20_000,
                n_meas: int = 30_000, seed: int = 0) -> dict:
    """Open-loop churn heatmap over (ON duty cycle x ON-period length).

    Every flow is an on/off source: ON for ~`mean_on_rtts` intra-RTTs at a
    time, ON a fraction `duty` of the time overall.  Sweeps how utilization
    and fairness degrade as senders become app-limited (short, sparse
    bursts) — the regime the backlogged fluid model could not previously
    express.  `duty == 1.0` is the exact backlogged baseline (mean_on =
    inf: flows never blink off, no restart resets).  Returns 2D arrays
    'util' (mean goodput / line rate), 'jain' (across flows' time-averaged
    goodput), and 'expected_on' (mean number of concurrently ON flows).
    """
    from repro.scenarios import ChurnSpec, dumbbell_scenario, to_fleetsim
    scen, shape = [], (len(duty_fracs), len(mean_on_rtts))
    for duty in duty_fracs:
        if not 0.0 < duty <= 1.0:
            raise ValueError(f"duty {duty} not in (0, 1]")
        for on_rtts in mean_on_rtts:
            if duty >= 1.0:
                churn = ChurnSpec(mean_on=float("inf"), mean_off=1.0)
            else:
                mean_on = on_rtts * intra_rtt
                churn = ChurnSpec(
                    mean_on=mean_on,
                    mean_off=mean_on * (1.0 - duty) / duty)
            fs = to_fleetsim(dumbbell_scenario(
                n_flows, 0, rate=rate, intra_rtt=intra_rtt,
                intra_churn=churn, seed=seed))
            scen.append(fs)
    _, rates = run_grid(scen, scheme=scheme, n_warm=n_warm, n_meas=n_meas,
                        seed=seed)
    return {
        "duty_fracs": jnp.asarray(duty_fracs),
        "mean_on_rtts": jnp.asarray(mean_on_rtts),
        "rates": rates.reshape(shape + (n_flows,)),
        "jain": jain(rates).reshape(shape),
        "util": (fleet_sum(rates, axis=1) / rate).reshape(shape),
        "expected_on": jnp.full(
            shape, n_flows) * jnp.asarray(duty_fracs)[:, None],
    }


def recovery_sweep(overloads: Sequence[float],
                   ec_configs: Sequence[tuple],
                   debounce_rtts: Sequence[float], *, n_inter: int = 64,
                   rate: float = fl.RATE_100G, intra_rtt: float = 14 * US,
                   inter_rtt: float = 2 * fl.MS, qcap: float = 64 * 1024,
                   scheme: str = "uno", n_warm: int = 20_000,
                   n_meas: int = 10_000, seed: int = 0, mesh=None,
                   link_tier=None, unroll: int = 1) -> dict:
    """Loss-recovery heatmap over (overload x EC geometry x NACK debounce).

    Every cell is the same lossy inter-DC dumbbell — physical RED drops
    (no phantom), a small `qcap`, and drop thresholds pushed to the tail
    (`red_lo/hi = 0.85/0.98`) so the queue actually overflows — with the
    downlink capacity scaled to `rate / overload`; only the bottleneck
    pressure and the RelParams vary, so routes are identical and the grid
    shards under one plan when `mesh` is given (satisfying run_grid's
    sharded-path contract at 100k+ flows).

    `ec_configs` are (k, r) pairs; `debounce_rtts` is the NACK holdoff in
    units of the inter RTT (0.0 = fire every batch tick).  The NACK batch
    period is pinned at a quarter RTT, matching netsim's default receiver
    timeout, so fluid cells stay comparable to the packet oracle.

    Returns (len(overloads), len(ec_configs), len(debounce_rtts)) arrays:
    'util' (goodput / scaled bottleneck capacity), 'jain', 'retx_ratio'
    (retransmitted / offered wire bytes), 'rec_ratio' (bytes recovered by
    EC parity alone), 'loss_ratio', 'nacks' (total NACK batches fired),
    'nack_lat' (mean per-flow recovery-latency EWMA, ns); plus
    'rel_config', the resolved reliability knobs (EC geometries, debounce,
    batch period, NACK quantum, loss MD) — benchmark entries persist it so
    the compare tool can refuse to diff runs whose recovery configuration
    changed (the numbers mean different machines then, not a regression).
    """
    from repro.fleetsim.reliability import make_rel_params
    from repro.scenarios import dumbbell_scenario, to_fleetsim
    base = to_fleetsim(dumbbell_scenario(
        0, n_inter, rate=rate, intra_rtt=intra_rtt, inter_rtt=inter_rtt,
        qcap=qcap, phantom=False, red_lo_frac=0.85, red_hi_frac=0.98,
        seed=seed))
    dt = float(base.net.dt)
    down = base.net.cap.shape[0] - 1
    period = max(int(round(0.25 * inter_rtt / dt)), 1)
    shape = (len(overloads), len(ec_configs), len(debounce_rtts))
    rels = {}
    for ec in ec_configs:
        for deb in debounce_rtts:
            rels[(tuple(ec), float(deb))] = make_rel_params(
                n_inter, ec=tuple(ec), nack_period=period,
                nack_hold=int(round(deb * inter_rtt / dt)))
    scen = []
    for load in overloads:
        if load <= 0:
            raise ValueError(f"overload {load} must be positive")
        net = base.net._replace(
            cap=base.net.cap.at[down].mul(1.0 / load),
            drain=base.net.drain.at[down].mul(1.0 / load))
        for ec in ec_configs:
            for deb in debounce_rtts:
                scen.append((net, base.params, base.is_inter, base.lb,
                             base.churn, rels[(tuple(ec), float(deb))]))
    final, rates = run_grid(scen, scheme=scheme, n_warm=n_warm,
                            n_meas=n_meas, seed=seed, mesh=mesh,
                            link_tier=link_tier, unroll=unroll)
    rs = final.rel
    wire = jnp.maximum(fleet_sum(rs.wire_bytes, axis=1), 1.0)
    loads = jnp.repeat(jnp.asarray(overloads, jnp.float32),
                       len(ec_configs) * len(debounce_rtts))
    return {
        "overloads": jnp.asarray(overloads),
        "ec_configs": tuple(tuple(ec) for ec in ec_configs),
        "debounce_rtts": jnp.asarray(debounce_rtts),
        "rates": rates.reshape(shape + (n_inter,)),
        "jain": jain(rates).reshape(shape),
        "util": (fleet_sum(rates, axis=1) * loads / rate).reshape(shape),
        "retx_ratio": (fleet_sum(rs.rtx_bytes, axis=1) / wire)
        .reshape(shape),
        "rec_ratio": (fleet_sum(rs.rec_bytes, axis=1) / wire)
        .reshape(shape),
        "loss_ratio": (fleet_sum(rs.lost_bytes, axis=1) / wire)
        .reshape(shape),
        "nacks": fleet_sum(rs.nacks, axis=1).reshape(shape),
        "nack_lat": jnp.mean(rs.lat_ewma, axis=1).reshape(shape),
        "rel_config": {
            "ec_configs": [list(map(int, ec)) for ec in ec_configs],
            "debounce_rtts": [float(d) for d in debounce_rtts],
            "nack_period_epochs": period,
            "nack_quantum": float(next(iter(rels.values()))
                                  .nack_quantum[0]),
            "loss_md": float(next(iter(rels.values())).loss_md[0]),
        },
    }


_FAULT_KINDS = ("down", "brownout", "flap", "burst")


def fault_sweep(fail_times: Sequence[float],
                fault_kinds: Sequence[str],
                ec_policies: Sequence[tuple], *, n_inter: int = 64,
                rate: float = fl.RATE_100G, intra_rtt: float = 14 * US,
                inter_rtt: float = 2 * fl.MS, qcap: float = 64 * 1024,
                fault_rtts: float = 50.0, brownout_frac: float = 0.4,
                flap_period_rtts: float = 2.0, flap_duty: float = 0.5,
                burst_loss: float = 2e-2, burst_corr: float = 0.3,
                mean_burst_len: float = 3.0, scheme: str = "uno",
                n_warm: int = 20_000, n_meas: int = 10_000, seed: int = 0,
                mesh=None, link_tier=None, unroll: int = 1) -> dict:
    """Fault-response grid over (fail time x fault kind x EC policy).

    Every cell is the recovery_sweep dumbbell (physical RED, small qcap,
    tail drop thresholds) with ONE scheduled fault on the bottleneck
    downlink: a `fault_rtts`-RTT window starting at `fail_times[i]` (ns)
    whose kind is drawn from `_FAULT_KINDS` — hard 'down', 'brownout' to
    `brownout_frac` capacity, 'flap' (period `flap_period_rtts` RTTs, ON
    fraction `flap_duty`), or a Gilbert-Elliott loss 'burst'
    (`burst_loss` mean loss, `burst_corr` in-burst drop prob,
    `mean_burst_len` expected burst length in chain ticks).  Kinds use
    inert schedule rows (a zero-length window) on the axis they don't
    exercise, so every cell carries the same E=1 / G=1 schedule shapes and
    the whole grid stacks into one vmapped executable — sharding under one
    plan when `mesh` is given.

    `ec_policies` are EC-strength ladders: tuples of (k, r) rungs for the
    adaptive controller, a 1-rung tuple meaning static EC.  Shorter
    ladders are padded by repeating their last rung so all cells share one
    rung-table length (padding rungs are idempotent — stepping onto a
    repeated rung changes nothing).

    Returns (len(fail_times), len(fault_kinds), len(ec_policies)) arrays:
    the recovery_sweep metrics plus 'rung_mean' (mean final ladder rung —
    how hard the adaptive controller escalated) and 'fault_config' (the
    resolved fault knobs, persisted by benchmark entries like
    'rel_config').
    """
    from repro.fleetsim.faults import make_schedule
    from repro.fleetsim.reliability import make_rel_params
    from repro.scenarios import dumbbell_scenario, to_fleetsim
    for kind in fault_kinds:
        if kind not in _FAULT_KINDS:
            raise ValueError(f"fault kind {kind!r} not in {_FAULT_KINDS}")
    base = to_fleetsim(dumbbell_scenario(
        0, n_inter, rate=rate, intra_rtt=intra_rtt, inter_rtt=inter_rtt,
        qcap=qcap, phantom=False, red_lo_frac=0.85, red_hi_frac=0.98,
        seed=seed))
    dt = float(base.net.dt)
    down = base.net.cap.shape[0] - 1
    period = max(int(round(0.25 * inter_rtt / dt)), 1)
    flap_ep = max(int(round(flap_period_rtts * inter_rtt / dt)), 1)
    dur_ep = max(int(round(fault_rtts * inter_rtt / dt)), 1)
    p_bg = 1.0 / max(float(mean_burst_len), 1.0)
    p_gb = min(burst_loss / max(burst_corr * mean_burst_len, 1e-12), 1.0)
    L = max(len(pol) for pol in ec_policies)
    rels = []
    for pol in ec_policies:
        rungs = [tuple(map(int, kr)) for kr in pol]
        rungs += [rungs[-1]] * (L - len(rungs))
        rels.append(make_rel_params(n_inter, ladder=tuple(rungs),
                                    nack_period=period))
    inert_cap = (down, 0, 0, 1.0, 0, 0.0)       # t1 == t0: never active
    inert_ge = (down, 0, 0, 0.0, 0.0, 0.0, 1.0)
    scen = []
    for t in fail_times:
        e0 = max(int(round(float(t) / dt)), 0)
        e1 = e0 + dur_ep
        for kind in fault_kinds:
            cap_ev, ge_ev = inert_cap, inert_ge
            if kind == "down":
                cap_ev = (down, e0, e1, 0.0, 0, 0.0)
            elif kind == "brownout":
                cap_ev = (down, e0, e1, float(brownout_frac), 0, 0.0)
            elif kind == "flap":
                cap_ev = (down, e0, e1, 0.0, flap_ep, float(flap_duty))
            else:                                # burst
                ge_ev = (down, e0, e1, 0.0, float(burst_corr), p_gb, p_bg)
            fault = make_schedule(cap_events=[cap_ev], ge_events=[ge_ev])
            for rel in rels:
                scen.append((base.net, base.params, base.is_inter,
                             base.lb, base.churn, rel, fault))
    shape = (len(fail_times), len(fault_kinds), len(ec_policies))
    final, rates = run_grid(scen, scheme=scheme, n_warm=n_warm,
                            n_meas=n_meas, seed=seed, mesh=mesh,
                            link_tier=link_tier, unroll=unroll)
    rs = final.rel
    wire = jnp.maximum(fleet_sum(rs.wire_bytes, axis=1), 1.0)
    return {
        "fail_times": jnp.asarray(fail_times),
        "fault_kinds": tuple(fault_kinds),
        "ec_policies": tuple(tuple(tuple(map(int, kr)) for kr in pol)
                             for pol in ec_policies),
        "rates": rates.reshape(shape + (n_inter,)),
        "jain": jain(rates).reshape(shape),
        "util": (fleet_sum(rates, axis=1) / rate).reshape(shape),
        "retx_ratio": (fleet_sum(rs.rtx_bytes, axis=1) / wire)
        .reshape(shape),
        "rec_ratio": (fleet_sum(rs.rec_bytes, axis=1) / wire)
        .reshape(shape),
        "loss_ratio": (fleet_sum(rs.lost_bytes, axis=1) / wire)
        .reshape(shape),
        "nacks": fleet_sum(rs.nacks, axis=1).reshape(shape),
        "nack_lat": jnp.mean(rs.lat_ewma, axis=1).reshape(shape),
        "rung_mean": jnp.mean(rs.rung.astype(jnp.float32), axis=1)
        .reshape(shape),
        "fault_config": {
            "fail_times": [float(t) for t in fail_times],
            "fault_kinds": list(fault_kinds),
            "ec_policies": [[list(map(int, kr)) for kr in pol]
                            for pol in ec_policies],
            "fault_rtts": float(fault_rtts),
            "brownout_frac": float(brownout_frac),
            "flap_period_rtts": float(flap_period_rtts),
            "flap_duty": float(flap_duty),
            "burst_loss": float(burst_loss),
            "burst_corr": float(burst_corr),
            "mean_burst_len": float(mean_burst_len),
            "nack_period_epochs": period,
        },
    }
