"""fleetsim throughput benchmark + the sweep heatmaps for the figure set.

Acceptance targets:
  * ISSUE 1: >= 1,000 flows x 10,000 epochs simulated in under 30 s on CPU
    — the scale gap the fluid model exists to close (the packet simulator
    needs minutes for a few dozen flows).
  * ISSUE 2: >= 1M flow-epochs/s with n_paths = 4 multipath (adaptive
    UnoLB-style splits) on one CPU core.
  * ISSUE 3: the million-flow scaling curve (`--scaling` / `--smoke`):
    flow-epochs/s at n_flows in {1k, 10k, 100k, 1M} for the compiled
    RouteLayout path, the original `.at[].add` scatter path, and the
    shard_map'd flow axis (in-process over the host's devices; on a CPU
    a child with --xla_force_host_platform_device_count, since the device
    count must be fixed before jax initializes).
  * ISSUE 4: the sharded flow axis runs under the locality ShardPlan
    ("sharded2-local": private links reduced on-shard, only the boundary
    tail psummed) next to the PR-3 full-buffer exchange ("sharded2");
    each locality point records its boundary payload and the run FAILS if
    the psum payload is not >= 10x smaller than the full link buffer on
    the standard dumbbell (the CI smoke guard).  Sharded points below
    MIN_SHARD_FLOWS flows per shard are skipped AND recorded as skipped —
    collective overhead dominates there and used to pollute the curve.
    Compiled scenarios are cached across backend variants (and shipped to
    the sharded subprocess as an .npz) so the curve builds each route
    tensor once.  BENCH_fleetsim.json is a TRAJECTORY now: each run
    appends an entry keyed by git SHA + date (the PR-3 single-run file is
    absorbed as the first entry) and `benchmarks/compare.py` prints
    deltas vs the previous entry.
  * ISSUE 5: a fat-tree point — the paper's actual two-DC k-ary fat-tree
    (scenarios.fat_tree_spec) at k=8 / 100k flows (k=4 in smoke),
    single-device layout path + the locality-sharded flow axis under the
    pod-grouping tiered ShardPlan.  The psum payload-shrink guard is
    parameterized per scenario kind (MIN_PSUM_SHRINK): 10x on the
    dumbbell's 2-link boundary, 1.5x on the fat-tree's agg/core/WAN cut.
  * ISSUE 6: a loss-recovery point — one jitted recovery_sweep grid
    (dynamic EC + NACK state machine, overload x debounce) whose entry
    records the reliability config (EC geometry, debounce, NACK quantum,
    loss MD) so compare.py refuses to diff runs with different recovery
    knobs; plus the smoke-mode fast-path guard asserting the
    reliability-DISABLED 10k layout point holds its throughput vs the
    last comparable trajectory entry (rel=None compiles the machine out
    — the guard keeps that claim honest).
  * ISSUE 7: the fat-tree layout point runs the PathTable-compressed
    backend ("auto" selects it — the scenario compiler attaches the
    unique-path-segment table on deep-multipath routes) and its entry
    splits timing into spec_build_s / compile_s / warm_s and records
    n_unique_paths next to n_flows so the dedupe ratio is visible in the
    trajectory.  `--check-equivalence` pins the pt backends to
    the reference scatter on the smoke fat tree (CI runs it under a
    2-forced-device mesh so the sharded/halo variant is covered too);
    `--block` overrides the Pallas flow-block size (default: picked from
    n_flows).  The smoke fast-path guard also covers the k=4 fat-tree
    layout point so the compressed backend cannot silently regress.
  * ISSUE 10: a multi-DC point — the 3-DC k=4 ring
    (scenarios.multi_dc_spec) sharded DC-major onto 3 forced host devices
    so shard == datacenter, with the ppermute neighbor halo exchange.
    Its entry records the topology knobs (k, n_dc, mesh, oversub — keys
    compare.py requires to MATCH before printing a ratio), the boundary
    size and BOTH payload-shrink factors; the boundary guard
    (MIN_PSUM_SHRINK["multi_dc"]) and the neighbor-exchange shrink guard
    are fatal in smoke mode.

Reports: jitted single-scenario rate (compile time separated out), the same
1k-flow scenario's steady utilization/fairness as a sanity check, the
multipath rate, and the vmapped heatmap grids (fairness x drain, churn duty
x burst length) whose full arrays land in results/paper/fleetsim_sweep.json
for the figure registry (benchmarks.run).
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks import common
from repro.fleetsim import dumbbell, links as fl, make_params, simulate
from repro.fleetsim.links import RATE_100G, US
from repro.fleetsim.sweeps import churn_sweep, fairness_sweep, jain
from repro.scenarios import dumbbell_scenario, fat_tree_spec, to_fleetsim

BENCH_PATH = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_fleetsim.json"


def _timed_sim(n_flows: int, n_epochs: int) -> dict:
    net, bdp, rtt = dumbbell(n_flows // 2, n_flows - n_flows // 2,
                             n_bottleneck=max(1, n_flows // 64))
    params = make_params(bdp, rtt, RATE_100G * 14 * US, 14 * US)

    t0 = time.time()
    final, _ = simulate(net, params, n_epochs=n_epochs)
    jax.block_until_ready(final.cwnd)
    cold_s = time.time() - t0          # includes jit compile

    t0 = time.time()
    final, _ = simulate(net, params, n_epochs=n_epochs)
    jax.block_until_ready(final.cwnd)
    warm_s = time.time() - t0

    rate = np.asarray(final.cwnd / params.rtt)
    return {
        "n_flows": n_flows, "n_epochs": n_epochs,
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 3),
        "flow_epochs_per_s": round(n_flows * n_epochs / warm_s),
        "under_30s": cold_s < 30.0,
        "final_jain": round(float(jain(rate)), 4),
    }


def _timed_multipath(n_flows: int, n_epochs: int, n_paths: int = 4) -> dict:
    """Multipath acceptance: adaptive-split fluid LB at n_paths paths."""
    fs = to_fleetsim(dumbbell_scenario(
        n_flows // 2, n_flows - n_flows // 2, multipath=True,
        n_wan=n_paths, n_bottleneck=max(1, n_flows // 64)))

    def run_once():
        t0 = time.time()
        final, _ = simulate(fs.net, fs.params, n_epochs=n_epochs,
                            is_inter=fs.is_inter, lb=fs.lb)
        jax.block_until_ready(final.cwnd)
        return time.time() - t0, final

    cold_s, _ = run_once()
    warm_s, final = run_once()
    split = np.asarray(final.split)
    return {
        "n_flows": n_flows, "n_epochs": n_epochs, "n_paths": n_paths,
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 3),
        "flow_epochs_per_s": round(n_flows * n_epochs / warm_s),
        "over_1m_per_s": n_flows * n_epochs / warm_s >= 1e6,
        "split_rows_sum_to_1": bool(
            np.allclose(split.sum(axis=1), 1.0, atol=1e-5)),
    }


def _grid_payload(grid: dict, keys=("jain", "class_ratio", "util")) -> dict:
    """Full heatmap arrays (figure data) + compact summary stats."""
    out = {}
    for k, v in grid.items():
        a = np.asarray(v)
        if k == "rates":
            continue                   # per-flow detail; too big for JSON
        out[k] = np.round(a, 5).tolist()
    for k in keys:
        if k in grid:
            a = np.asarray(grid[k])
            out[f"{k}_min"] = round(float(a.min()), 4)
            out[f"{k}_max"] = round(float(a.max()), 4)
    return out


def run(quick: bool = True) -> dict:
    out = {"acceptance": _timed_sim(1000, 10_000),
           "acceptance_multipath": _timed_multipath(1000, 10_000)}
    if not quick:
        out["10k_flows"] = _timed_sim(10_000, 10_000)
        out["100k_epochs"] = _timed_sim(1000, 100_000)

    n_warm = 50_000 if not quick else 20_000
    n_meas = 10_000 if not quick else 5_000
    with common.Timer() as t:
        grid = fairness_sweep([2, 10, 50, 140], [0.8, 0.9, 0.95],
                              n_warm=n_warm, n_meas=n_meas)
    out["fairness_grid"] = dict(_grid_payload(grid), wall_s=t.wall_s,
                                cells=int(grid["jain"].size))

    with common.Timer() as t:
        mp = fairness_sweep([2, 10, 50, 140], [0.8, 0.9, 0.95],
                            multipath=True, n_wan=4,
                            n_warm=n_warm, n_meas=n_meas)
    out["fairness_grid_multipath"] = dict(_grid_payload(mp), wall_s=t.wall_s,
                                          cells=int(mp["jain"].size))

    with common.Timer() as t:
        ch = churn_sweep([0.1, 0.3, 0.6, 1.0], [50.0, 200.0, 1000.0],
                         n_flows=16, n_warm=10_000,
                         n_meas=40_000 if not quick else 20_000)
    out["churn_grid"] = dict(_grid_payload(ch, keys=("jain", "util")),
                             wall_s=t.wall_s, cells=int(ch["util"].size))

    common.save("fleetsim_sweep", out)
    return out


# --------------------------------------------- million-flow scaling curve

# sharded points need at least this many flows per shard to clear the
# collective/dispatch overhead; below it the point is recorded as skipped
MIN_SHARD_FLOWS = 5_000

# boundary-psum payload-shrink guard, per scenario kind: the dumbbell's
# boundary is 2-3 links (>= 10x shrink), while a fat-tree's boundary is
# structurally the agg/core/WAN cut plus the straddling sender uplinks —
# a ~2x shrink at k=8 (the tiered plan still beats the untiered ~1.26x).
# The multi-DC DC-major plan's boundary is the DCI attach tier only (12
# links on the 3-DC k=4 ring, independent of flow count), so it warrants
# a much tighter floor.
MIN_PSUM_SHRINK = {"dumbbell": 10.0, "fat_tree": 1.5, "multi_dc": 5.0}

FAT_TREE_PATHS = 8            # ECMP path-set cap for the fat-tree points

# compiled scenarios are expensive at 1M flows (route tensor + layout);
# build each (kind, n_flows, multipath) once and reuse across backend
# variants.  Entries are (net, params, is_inter, lb, link_tier).
_SCENARIO_CACHE: dict = {}


def _scenario(n_flows: int, multipath: bool, kind: str = "dumbbell",
              k: int = 8):
    key = (kind, n_flows, multipath, k)
    if key in _SCENARIO_CACHE:
        return _SCENARIO_CACHE[key]
    if kind == "fat_tree":
        fs = to_fleetsim(fat_tree_spec(k=k, n_wan=k, n_flows=n_flows,
                                       n_paths=FAT_TREE_PATHS, seed=1))
        out = fs.net, fs.params, fs.is_inter, fs.lb, fs.link_tier
    elif multipath:
        fs = to_fleetsim(dumbbell_scenario(
            n_flows // 2, n_flows - n_flows // 2, multipath=True, n_wan=4,
            n_bottleneck=max(1, n_flows // 64)))
        out = fs.net, fs.params, fs.is_inter, fs.lb, None
    else:
        net, bdp, rtt = dumbbell(n_flows // 2, n_flows - n_flows // 2,
                                 n_bottleneck=max(1, n_flows // 64))
        params = make_params(bdp, rtt, RATE_100G * 14 * US, 14 * US)
        out = net, params, None, None, None
    _SCENARIO_CACHE[key] = out
    return out


def _dump_scenario(n_flows: int, kind: str = "dumbbell",
                   k: int = 8) -> pathlib.Path:
    """Publish the compiled scenario to the content-addressed bundle cache
    so the sharded run (in this process or a forced-device child) can
    load it — it must not rebuild the same route tensor already compiled
    (at 1M flows that is most of the wall time).  Dumbbell points ship
    the single-path scenario; fat-tree points ship the full multipath one
    plus its locality tiers (and LbParams when present) so the sharded
    run reproduces the pod-locality plan.  The bundle is keyed by the bench build request,
    so repeat runs on one host dedupe to a single write (atomic rename —
    concurrent runs race safely) and later processes skip the build."""
    from repro.fleetsim import service
    from repro.scenarios import FleetScenario, fingerprint
    key = fingerprint({"bench_scenario": "fleetsim_sweep", "kind": kind,
                       "n_flows": n_flows, "k": k,
                       "multipath": kind == "fat_tree"},
                      service.CACHE_VERSION)
    path = service.bundle_path(key)
    if path.exists():
        return path
    net, params, is_inter, lb, tier = _scenario(
        n_flows, kind == "fat_tree", kind, k)
    fs = FleetScenario(net=net, params=params, is_inter=is_inter, lb=lb,
                       churn=None, seed=0, link_tier=tier)
    return service.publish_scenario(fs, key)


def _time_simulate(net, params, n_epochs, *, is_inter=None, lb=None,
                   backend="auto", block=None, reps=3):
    """(cold_s, best warm_s) for one jitted n_epochs run."""
    t0 = time.time()
    final, _ = simulate(net, params, n_epochs=n_epochs, is_inter=is_inter,
                        lb=lb, backend=backend, block=block)
    jax.block_until_ready(final.cwnd)
    cold = time.time() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        final, _ = simulate(net, params, n_epochs=n_epochs,
                            is_inter=is_inter, lb=lb, backend=backend,
                            block=block)
        jax.block_until_ready(final.cwnd)
        best = min(best, time.time() - t0)
    return cold, best


def _point(n_flows, n_epochs, *, variant, path, warm_s, cold_s=None,
           **extra):
    rec = {"n_flows": n_flows, "n_epochs": n_epochs, "variant": variant,
           "path": path, "warm_s": round(warm_s, 3),
           "flow_epochs_per_s": round(n_flows * n_epochs / warm_s)}
    if cold_s is not None:
        rec["cold_s"] = round(cold_s, 2)
    rec.update(extra)
    print("  ", json.dumps(rec))
    return rec


def _fat_tree_layout_point(ft_k: int, ft_n: int, ft_ne: int, *,
                           backend: str = "auto", block=None) -> dict:
    """Time the fat-tree layout point with its phases split out.

    spec_build_s: scenario compile + trimmed-layout rebuild, including
    the PathTable dedupe (0.0x when the cached scenario is reused);
    compile_s: jit trace + compile, reported as cold_s - warm_s (it used
    to hide inside cold_s); warm_s: the best warm scan.  The entry also
    records n_unique_paths — the table's unique-segment count (null when
    the scenario compiled flat) — next to n_flow_paths, so the dedupe
    ratio is visible in the trajectory.
    """
    t0 = time.time()
    net, params, ii, lb, _ = _scenario(ft_n, True, "fat_tree", ft_k)
    fast_net = fl.with_layout(net, trim=True)
    spec_build = time.time() - t0
    cold, warm = _time_simulate(fast_net, params, ft_ne, is_inter=ii,
                                lb=lb, backend=backend, block=block)
    pt = fast_net.layout.path_table
    return _point(
        ft_n, ft_ne, variant=f"fat_tree_k{ft_k}", path="layout",
        warm_s=warm, cold_s=cold,
        spec_build_s=round(spec_build, 2),
        compile_s=round(max(cold - warm, 0.0), 2),
        backend=backend,
        n_unique_paths=None if pt is None else int(pt.n_segments),
        n_flow_paths=int(np.prod(fast_net.routes.shape[:2])))


def _sharded_run(bundle: str, n_devices: int, n_epochs: int, *,
                 locality: bool = True, dc: bool = False) -> dict:
    """Time the sharded steady state of a published scenario bundle over
    the first `n_devices` local devices.  Returns warm_s plus the plan's
    boundary stats.  The dense RouteLayout rides in the bundle but is
    stripped before sharding — each shard compiles its own local view.
    `dc=True` shards DC-major (shard == datacenter) with the neighbor
    halo exchange where the plan allows it."""
    from repro.fleetsim import service
    from repro.fleetsim.shard import (flow_mesh, shard_scenario,
                                      steady_state_prepared)
    fs = service.load_bundle(bundle)
    if fs is None:
        raise RuntimeError(f"scenario bundle missing or corrupt: {bundle}")
    dc_kw = (dict(link_dc=fs.link_dc, exchange="auto", seed=fs.seed)
             if dc else {})
    sf = shard_scenario(fs.net._replace(layout=None), fs.params,
                        is_inter=fs.is_inter, lb=fs.lb, locality=locality,
                        link_tier=fs.link_tier, mesh=flow_mesh(n_devices),
                        **dc_kw)
    kw = dict(n_warm=n_epochs - 10, n_meas=10)
    _, r = steady_state_prepared(sf, **kw)
    jax.block_until_ready(r)
    best = float("inf")
    for _ in range(2):
        t0 = time.time()
        _, r = steady_state_prepared(sf, **kw)
        jax.block_until_ready(r)
        best = min(best, time.time() - t0)
    return {"warm_s": best, "n_links": int(sf.plan.n_links),
            "n_boundary": int(sf.plan.n_boundary),
            "nbr_width": None if sf.nbr is None else int(sf.nbr.shape[2])}


def _shard_skip_reason(n_devices: int):
    """Why a sharded point cannot run here, or None.  A CPU run forks a
    child with forced host devices; an accelerator host shards over its
    own devices in this process (a chip belongs to the process that
    touched it first), so it needs `n_devices` of them."""
    if jax.device_count() >= n_devices or jax.default_backend() == "cpu":
        return None
    return (f"needs {n_devices} devices, host has {jax.device_count()} "
            f"{jax.default_backend()}")


def _sharded(bundle, n_devices: int, n_epochs: int, **kw) -> dict:
    """`_sharded_run` in this process when it sees `n_devices` devices,
    else (CPU only) in a child with that many forced host devices."""
    if jax.device_count() >= n_devices:
        return _sharded_run(str(bundle), n_devices, n_epochs, **kw)
    code = f"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={n_devices} "
    + os.environ.get("XLA_FLAGS", ""))
import json
from benchmarks.fleetsim_sweep import _sharded_run
print(json.dumps(_sharded_run({str(bundle)!r}, {n_devices}, {n_epochs},
                              **{kw!r})))
"""
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root), env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


# 3-DC smoke point: topology knobs ride along in the entry so compare.py
# can refuse cross-topology ratios (absent or changed keys -> incomparable)
_MULTI_DC = {"k": 4, "n_dc": 3, "mesh": "ring", "oversub": 1.0}


def _multi_dc_point(mode: str, points: list) -> None:
    """The N-DC point: a 3-DC k=4 ring sharded DC-major onto 3 devices
    (shard == datacenter), ppermute neighbor halo exchange where the plan
    proves it legal.  Records the boundary payload plus BOTH shrink
    factors — full-buffer/psum-tail and psum-tail/ppermute-payload — and
    fails the run when either falls under its floor
    (MIN_PSUM_SHRINK["multi_dc"] for the boundary cut; the neighbor
    exchange must strictly shrink the tail or the DC-major plan has
    stopped matching the topology), or when the point itself fails."""
    from repro.fleetsim import service
    from repro.scenarios import fingerprint, multi_dc_spec, to_fleetsim
    n = 15_000 if mode == "smoke" else 60_000
    ne = 300 if mode == "smoke" else 200
    variant = f"multi_dc_k{_MULTI_DC['k']}"
    skip = _shard_skip_reason(_MULTI_DC["n_dc"])
    if skip:
        rec = {"n_flows": n, "n_epochs": ne, "variant": variant,
               "path": "sharded3-nbr", "skipped": True, "reason": skip}
        points.append(rec)
        print("  ", json.dumps(rec))
        return
    key = fingerprint({"bench_scenario": "fleetsim_sweep",
                       "kind": "multi_dc", "n_flows": n, **_MULTI_DC},
                      service.CACHE_VERSION)
    path = service.bundle_path(key)
    if not path.exists():
        t0 = time.time()
        fs = to_fleetsim(multi_dc_spec(n_flows=n, n_paths=4, seed=1,
                                       **_MULTI_DC))
        path = service.publish_scenario(fs, key)
        print(f"   multi_dc spec build {time.time() - t0:.1f}s")
    try:
        res = _sharded(path, _MULTI_DC["n_dc"], ne, dc=True)
    except (RuntimeError, subprocess.TimeoutExpired, OSError,
            json.JSONDecodeError, KeyError, IndexError) as e:
        raise SystemExit("multi_dc point failed: " + str(e)[:500])
    full_payload = res["n_links"] + 1
    psum_shrink = full_payload / max(res["n_boundary"], 1)
    width = res["nbr_width"]
    nbr_shrink = (res["n_boundary"] / (2 * width)) if width else None
    rec = _point(n, ne, variant=variant,
                 path="sharded3-nbr", warm_s=res["warm_s"],
                 topology=dict(_MULTI_DC),
                 n_links=res["n_links"], n_boundary=res["n_boundary"],
                 exchange="nbr" if width else "psum",
                 psum_payload_shrink=round(psum_shrink, 1),
                 ppermute_payload_shrink=(None if nbr_shrink is None
                                          else round(nbr_shrink, 2)))
    points.append(rec)
    if psum_shrink < MIN_PSUM_SHRINK["multi_dc"]:
        raise SystemExit(
            f"multi_dc boundary payload guard failed: {res['n_boundary']} "
            f"boundary links vs {full_payload} full buffer "
            f"(shrink {psum_shrink:.1f}x < "
            f"{MIN_PSUM_SHRINK['multi_dc']}x)")
    if nbr_shrink is None or nbr_shrink <= 1.0:
        raise SystemExit(
            "multi_dc neighbor-exchange guard failed: the DC-major plan "
            f"no longer yields a legal shrinking ppermute exchange "
            f"(width={width}, boundary={res['n_boundary']})")


# layout-path epoch counts per size (reference runs use ~1/4 of these so
# the slow scatter path doesn't dominate benchmark wall-clock)
_CURVE_EPOCHS = {1_000: 20_000, 10_000: 2_000, 100_000: 200, 1_000_000: 40}

# recovery-sweep grid for the trajectory point (ISSUE 6): one EC geometry
# x two overloads x two debounce settings — small enough for the CI smoke
# step, wide enough that a broken NACK/retransmit path shows up as a
# zeroed retx/rec ratio rather than only as a crash
_RECOVERY_GRID = {"overloads": (1.5, 3.0), "ec_configs": ((8, 2),),
                  "debounce_rtts": (0.0, 1.0)}

# smoke-mode fast-path guard: the 10k-flow layout point (reliability
# DISABLED — the pre-existing hot path) must not lose more than this
# fraction of throughput vs the last comparable trajectory entry (same
# mode + cpu_count; cross-machine entries are not comparable).  Looser
# than the 10% local acceptance bar because shared CI runners are noisy.
_SMOKE_GUARD_RATIO = float(os.environ.get("FLEETSIM_SMOKE_GUARD", "0.7"))


def _recovery_point(mode: str) -> dict:
    """Time one jitted recovery_sweep grid and record its reliability
    config alongside the throughput — entries with different (k, r) /
    debounce / quantum knobs are flagged incomparable by compare.py."""
    from repro.fleetsim.sweeps import recovery_sweep
    n_inter = 2_000 if mode == "smoke" else 20_000
    n_warm = 4_000 if mode == "smoke" else 20_000
    n_meas = 1_000 if mode == "smoke" else 10_000
    kw = dict(_RECOVERY_GRID, n_inter=n_inter, n_warm=n_warm,
              n_meas=n_meas)
    t0 = time.time()
    res = recovery_sweep(**kw)
    jax.block_until_ready(res["rates"])
    cold = time.time() - t0
    t0 = time.time()
    res = recovery_sweep(**kw)
    jax.block_until_ready(res["rates"])
    warm = time.time() - t0
    cells = int(res["util"].size)
    rec = _point(n_inter, cells * (n_warm + n_meas), variant="recovery",
                 path="grid", warm_s=warm, cold_s=cold)
    rec["cells"] = cells
    rec["rel"] = res["rel_config"]
    rec["util_range"] = [round(float(np.min(res["util"])), 4),
                         round(float(np.max(res["util"])), 4)]
    rec["retx_ratio_max"] = round(float(np.max(res["retx_ratio"])), 5)
    rec["rec_ratio_max"] = round(float(np.max(res["rec_ratio"])), 5)
    if not np.isfinite(np.asarray(res["util"])).all():
        raise SystemExit("recovery sweep produced non-finite utilization")
    return rec


# fault-injection grid for the trajectory point: fail time x fault kind
# x EC policy — a hard down and a correlated loss burst, each against a
# static-EC policy and the adaptive three-rung ladder.  Full mode runs
# the 2x2x2 grid at 100k flows under one jitted vmap (the acceptance
# scale); smoke shrinks the flow axis only, so the grid shape CI
# exercises is the one the headline number ships with.
_FAULT_GRID = {"fault_kinds": ("down", "burst"),
               "ec_policies": (((8, 2),), ((8, 1), (8, 2), (8, 4)))}


def _fault_point(mode: str) -> dict:
    """Time one jitted fault_sweep grid and record its fault config
    alongside the throughput — entries with different fault windows or
    EC policies are flagged incomparable by compare.py."""
    from repro.fleetsim.sweeps import fault_sweep
    n_inter = 2_000 if mode == "smoke" else 100_000
    n_warm = 2_000 if mode == "smoke" else 4_000
    n_meas = 500 if mode == "smoke" else 1_000
    # the dumbbell's epoch is its intra RTT (14 us); place the two fail
    # times at 20% / 50% of the run so the late fault's recovery window
    # is still inside the measured tail
    span = (n_warm + n_meas) * 14_000.0
    kw = dict(_FAULT_GRID, fail_times=(0.2 * span, 0.5 * span),
              fault_rtts=5.0, n_inter=n_inter, n_warm=n_warm,
              n_meas=n_meas)
    t0 = time.time()
    res = fault_sweep(**kw)
    jax.block_until_ready(res["rates"])
    cold = time.time() - t0
    t0 = time.time()
    res = fault_sweep(**kw)
    jax.block_until_ready(res["rates"])
    warm = time.time() - t0
    cells = int(res["util"].size)
    rec = _point(n_inter, cells * (n_warm + n_meas), variant="fault",
                 path="grid", warm_s=warm, cold_s=cold)
    rec["cells"] = cells
    rec["fault"] = res["fault_config"]
    rec["util_range"] = [round(float(np.min(res["util"])), 4),
                         round(float(np.max(res["util"])), 4)]
    rec["rung_mean_max"] = round(float(np.max(res["rung_mean"])), 3)
    rec["loss_ratio_max"] = round(float(np.max(res["loss_ratio"])), 5)
    for key in ("util", "jain", "loss_ratio", "rung_mean", "rates"):
        if not np.isfinite(np.asarray(res[key])).all():
            raise SystemExit(f"fault sweep produced non-finite {key}")
    return rec


def _fault_smoke() -> dict:
    """CI fault-injection smoke: a small multipath dumbbell whose wan0
    dies mid-run.  Asserts every carry leaf stays finite (win_delay_min
    is +inf by design) and the aggregate re-converges after the failure,
    then writes the evidence to results/fault_smoke.json."""
    from repro.scenarios import (FaultSpec, LbSpec, dumbbell_scenario,
                                 to_fleetsim)
    spec = dumbbell_scenario(
        0, 8, multipath=True, n_wan=4,
        inter_lb=LbSpec(kind="unolb", n_subflows=4),
        faults=(FaultSpec(link="wan0", kind="down", t_start=2 * fl.MS),),
        seed=1)
    fs = to_fleetsim(spec)
    dt = float(fs.net.dt)
    n = int(round(30 * fl.MS / dt))
    t0 = time.time()
    final, traj = simulate(fs.net, fs.params, n_epochs=n, scheme="uno",
                           is_inter=fs.is_inter, lb=fs.lb,
                           fault=fs.fault, seed=fs.seed, record=True)
    jax.block_until_ready(final.cwnd)
    wall = time.time() - t0
    traj = np.asarray(traj)
    agg = traj.sum(axis=1)
    e_fail = int(np.asarray(fs.fault.t0)[0])
    pre = float(agg[max(e_fail - 10, 0)])
    post = float(agg[-200:].mean())

    bad = []
    if not np.isfinite(traj).all():
        bad.append("goodput trajectory has non-finite entries")
    for name, leaf in zip(final._fields, final):
        if leaf is None or name == "win_delay_min":
            continue
        leaves = leaf if hasattr(leaf, "_fields") else (leaf,)
        for i, a in enumerate(leaves):
            if a is not None and not np.isfinite(
                    np.asarray(a, np.float64)).all():
                bad.append(f"carry field {name}[{i}] has non-finite "
                           "entries after the link death")
    if not post > 0.5 * pre:
        bad.append(f"aggregate did not recover: pre-failure {pre:.2f} "
                   f"-> tail mean {post:.2f} bytes/ns")

    rec = {
        "n_flows": int(traj.shape[1]), "n_epochs": n,
        "fail_epoch": e_fail, "wall_s": round(wall, 2),
        "agg_pre_fail": round(pre, 3), "agg_tail_mean": round(post, 3),
        "recovered": not bad, "failures": bad,
    }
    print(json.dumps(rec, indent=1))
    common.RESULTS.parent.mkdir(parents=True, exist_ok=True)
    out_path = common.RESULTS.parent / "fault_smoke.json"
    out_path.write_text(json.dumps(rec, indent=1))
    print(f"fault smoke written to {out_path}")
    if bad:
        raise SystemExit("fault smoke failed:\n  " + "\n  ".join(bad))
    return rec


# smoke points the fast-path guard watches: the 10k dumbbell layout point
# (the pre-existing hot path) and the k=4 fat-tree layout point (the
# PathTable-compressed backend, ISSUE 7) — a broken table build would
# otherwise only show as a silent throughput cliff
_GUARD_KEYS = ((10_000, "single", "layout"),
               (12_000, "fat_tree_k4", "layout"))


def _guard_fast_path(entry: dict, hist: list) -> None:
    """Smoke-mode regression guard for the reliability-DISABLED hot path:
    compare each guarded layout point against the most recent prior
    entry measured on a comparable host.  The reliability machinery is
    compiled out entirely when rel is None — this guard is what keeps
    that claim honest run over run."""
    meta = entry["meta"]
    cur_pts = {(p["n_flows"], p.get("variant", "single"), p["path"]): p
               for p in entry["points"]}
    for key in _GUARD_KEYS:
        cur = cur_pts.get(key)
        if cur is None or cur.get("skipped"):
            continue
        for prev in reversed(hist):
            pm = prev.get("meta", {})
            if pm.get("mode") != meta["mode"] or \
                    pm.get("cpu_count") != meta["cpu_count"]:
                continue
            old = {(p["n_flows"], p.get("variant", "single"), p["path"]): p
                   for p in prev.get("points", [])}.get(key)
            if old is None or old.get("skipped"):
                continue
            ratio = cur["flow_epochs_per_s"] / \
                max(old["flow_epochs_per_s"], 1)
            print(f"  fast-path guard {key[1]}: "
                  f"{old['flow_epochs_per_s']} -> "
                  f"{cur['flow_epochs_per_s']} fe/s ({ratio:.2f}x, floor "
                  f"{_SMOKE_GUARD_RATIO}x vs {pm.get('git_sha', '?')})")
            if ratio < _SMOKE_GUARD_RATIO:
                raise SystemExit(
                    f"layout fast-path regression ({key[1]}): "
                    f"{ratio:.2f}x < {_SMOKE_GUARD_RATIO}x vs entry "
                    f"{pm.get('git_sha', '?')}")
            break
        else:
            print(f"  fast-path guard {key[1]}: no comparable prior "
                  "entry (mode/cpu) — skipped")


def _git_sha() -> str:
    """Short HEAD sha, suffixed "-dirty" when the tree has uncommitted
    changes — a trajectory entry must say which code produced it."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=BENCH_PATH.parent, timeout=10)
        sha = out.stdout.strip() or "unknown"
        st = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, cwd=BENCH_PATH.parent, timeout=10)
        return sha + "-dirty" if st.stdout.strip() else sha
    except OSError:
        return "unknown"


def load_history() -> list:
    """BENCH_fleetsim.json as a list of run entries, oldest first.  The
    PR-3 file was one bare run dict; it becomes the first entry."""
    if not BENCH_PATH.exists():
        return []
    data = json.loads(BENCH_PATH.read_text())
    return data["history"] if "history" in data else [data]


def _append_history(entry: dict) -> None:
    hist = load_history()
    hist.append(entry)
    BENCH_PATH.write_text(json.dumps(
        {"schema": "trajectory-v1", "history": hist}, indent=1))


def _sharded_points(n: int, ne: int, points: list,
                    speedups: dict, kind: str = "dumbbell", k: int = 8,
                    variant: str = "single",
                    paths=(("sharded2-local", True),
                           ("sharded2", False))) -> None:
    """Both sharded variants at one size: locality halo exchange vs the
    PR-3 full-buffer psum.  Too-small points are recorded as skipped (not
    silently omitted) — below MIN_SHARD_FLOWS per shard the collective
    overhead dominates and the curve stops measuring aggregation.  The
    locality point's boundary-psum payload shrink is guarded per scenario
    kind (MIN_PSUM_SHRINK) — the dumbbell's 2-link boundary warrants 10x,
    a fat-tree's agg/core/WAN cut ~1.5x.  A FAILED point is fatal in
    every mode: the payload guard must not pass vacuously because the
    sharded run crashed."""
    n_devices = 2
    sh_ne = min(ne, 300)
    per_shard = n // n_devices
    min_shrink = MIN_PSUM_SHRINK[kind]
    skip = _shard_skip_reason(n_devices)
    if per_shard < MIN_SHARD_FLOWS:
        skip = f"flows_per_shard {per_shard} < {MIN_SHARD_FLOWS}"
    rates = {}
    for path_name, locality in paths:
        if skip:
            rec = {"n_flows": n, "n_epochs": sh_ne, "variant": variant,
                   "path": path_name, "skipped": True, "reason": skip}
            points.append(rec)
            print("  ", json.dumps(rec))
            continue
        try:
            res = _sharded(_dump_scenario(n, kind, k), n_devices, sh_ne,
                           locality=locality)
        except (RuntimeError, subprocess.TimeoutExpired, OSError,
                json.JSONDecodeError, KeyError, IndexError) as e:
            raise SystemExit(f"{path_name} point failed at n={n}: "
                             + str(e)[:500])
        rec = _point(n, sh_ne, variant=variant, path=path_name,
                     warm_s=res["warm_s"])
        rates[path_name] = rec["flow_epochs_per_s"]
        if locality:
            full_payload = res["n_links"] + 1
            shrink = full_payload / max(res["n_boundary"], 1)
            rec["n_links"] = res["n_links"]
            rec["n_boundary"] = res["n_boundary"]
            rec["psum_payload_shrink"] = round(shrink, 1)
            if shrink < min_shrink:
                raise SystemExit(
                    f"boundary psum payload guard failed at n={n} "
                    f"({kind}): {res['n_boundary']} boundary links vs "
                    f"{full_payload} full buffer (shrink {shrink:.1f}x "
                    f"< {min_shrink}x)")
        points.append(rec)
    if len(rates) == 2:
        speedups[f"sharded_locality_vs_full:{variant}:{n}"] = round(
            rates["sharded2-local"] / rates["sharded2"], 2)


def scaling_curve(mode: str = "full", *, backend: str = "auto",
                  block=None) -> dict:
    """Grow the n_flows scaling curve and append it to the
    BENCH_fleetsim.json trajectory.

    mode: "smoke" (CI: 10k flows only, short scan), "quick" (up to 100k),
    "full" (up to 1M + the completed 1M-flow x 1k-epoch run).
    backend/block override the load backend and Pallas flow-block size on
    the single-device layout points (default: "auto" picks the PathTable
    backend where a table is attached, and the block is sized from
    n_flows).
    """
    sizes = {"smoke": [10_000], "quick": [1_000, 10_000, 100_000],
             "full": [1_000, 10_000, 100_000, 1_000_000]}[mode]
    points, speedups = [], {}
    for n in sizes:
        ne = _CURVE_EPOCHS[n] if mode != "smoke" else 300
        for variant in ("single", "multipath"):
            multipath = variant == "multipath"
            if multipath and n < 100_000 and mode != "smoke":
                continue            # headline contrast configs only
            if multipath and mode == "smoke":
                continue
            net, params, ii, lb, _ = _scenario(n, multipath)
            fast_net = fl.with_layout(net, trim=True) if multipath else net
            cold, warm = _time_simulate(fast_net, params, ne,
                                        is_inter=ii, lb=lb,
                                        backend=backend, block=block)
            points.append(_point(n, ne, variant=variant, path="layout",
                                 warm_s=warm, cold_s=cold))
            ref_ne = max(5, ne // 4)
            _, ref_warm = _time_simulate(net._replace(layout=None), params,
                                         ref_ne, is_inter=ii, lb=lb,
                                         backend="reference", reps=2)
            points.append(_point(n, ref_ne, variant=variant,
                                 path="reference", warm_s=ref_warm))
            speedups[f"{variant}:{n}"] = round(
                (n * ne / warm) / (n * ref_ne / ref_warm), 2)
        # sharded flow axis (2 CPU shards; single-path scenario)
        _sharded_points(n, ne, points, speedups)

    # fat-tree points (the paper's actual topology — PAPER §5.1): the
    # pod-structured permutation/inter mix at FAT_TREE_PATHS ECMP paths,
    # single-device layout path (PathTable-compressed backend via "auto")
    # + the locality-sharded flow axis whose plan groups flows by
    # destination pod (boundary = agg/core/WAN cut).  Smoke runs k=4
    # small; quick/full run the k=8 / 100k-flow headline.
    ft_k, ft_n = (4, 12_000) if mode == "smoke" else (8, 100_000)
    ft_ne = 300 if mode == "smoke" else 200
    variant = f"fat_tree_k{ft_k}"
    points.append(_fat_tree_layout_point(ft_k, ft_n, ft_ne, backend=backend,
                                         block=block))
    ft_paths = ((("sharded2-local", True),) if mode == "smoke" else
                (("sharded2-local", True), ("sharded2", False)))
    _sharded_points(ft_n, ft_ne, points, speedups, kind="fat_tree",
                    k=ft_k, variant=variant, paths=ft_paths)

    # multi-DC point (the N-datacenter topology layer): 3-DC k=4 ring,
    # one shard per datacenter under the DC-major plan, ppermute neighbor
    # halo exchange — both payload-shrink guards are fatal in smoke
    _multi_dc_point(mode, points)

    # loss-recovery grid (ISSUE 6): dynamic EC + NACK state machine under
    # vmap — its reliability config rides along in the entry so config
    # changes are never misread as perf deltas
    points.append(_recovery_point(mode))

    # fault-injection grid: fail time x fault kind x EC policy under one
    # jitted vmap (100k flows in full mode) — the fault config rides
    # along so changed fault knobs are never misread as perf deltas
    points.append(_fault_point(mode))

    entry = {
        "meta": {
            "generated": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "git_sha": _git_sha(),
            "mode": mode,
            "cpu_count": os.cpu_count(),
            "jax": jax.__version__,
            "scenario": "scenarios.dumbbell_scenario, "
                        "n_bottleneck=n_flows/64, multipath=n_wan=4; "
                        "scenarios.fat_tree_spec permutation mix, "
                        f"n_paths={FAT_TREE_PATHS}",
        },
        "points": points,
        "speedup_layout_vs_reference": speedups,
    }

    if mode == "full":
        # acceptance: a completed 1M-flow x 1k-epoch run on the fast path
        n, ne = 1_000_000, 1_000
        net, params, _, _, _ = _scenario(n, False)
        t0 = time.time()
        final, _ = simulate(net, params, n_epochs=ne)
        jax.block_until_ready(final.cwnd)
        wall = time.time() - t0
        rates = final.cwnd / params.rtt
        entry["run_1m"] = {
            "n_flows": n, "n_epochs": ne, "wall_s": round(wall, 1),
            "flow_epochs_per_s": round(n * ne / wall),
            "final_jain": round(float(jain(rates)), 4),
        }
        print("  run_1m:", json.dumps(entry["run_1m"]))

    hist = load_history()
    if mode == "smoke":
        _guard_fast_path(entry, hist)
    _append_history(entry)
    print(f"appended entry {entry['meta']['git_sha']} to {BENCH_PATH}")
    return entry


def check_equivalence(ft_k: int = 4, ft_n: int = 12_000) -> None:
    """CI equivalence gate for the PathTable-compressed backends.

    Builds the smoke fat-tree scenario, asserts the scenario compiler
    attached a table (a silent fall-back to the flat CSR would make the
    benchmark numbers lie), and pins the pt / pt_pallas offered loads to
    the reference `.at[].add` scatter at <= 1e-6 normalized error plus
    the full with_loss link_epoch (scale/mark/delay/loss gathers) to the
    reference backend.  When >= 2 devices are visible (CI forces
    --xla_force_host_platform_device_count=2 on this step) the pt-sharded
    halo path is compared against the flat-sharded one too.  Any
    violation is a SystemExit — this runs as a CI gate, not a report.
    """
    import jax.numpy as jnp
    from repro.fleetsim.shard import shard_scenario, steady_state_prepared
    from repro.kernels import ref as kref

    net, params, ii, lb, tier = _scenario(ft_n, True, "fat_tree", ft_k)
    fast_net = fl.with_layout(net, trim=True)
    pt = fast_net.layout.path_table
    if pt is None:
        raise SystemExit(
            "equivalence check: fat-tree scenario compiled WITHOUT a "
            "PathTable — the auto-attach policy regressed")
    n, p = fast_net.routes.shape[:2]
    print(f"  fat_tree_k{ft_k} n={ft_n}: n_unique_paths="
          f"{pt.n_segments} vs {n * p} flow-paths")

    rng = np.random.default_rng(0)
    rates = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    split = fl.normalize_split(
        jnp.asarray(rng.uniform(0.0, 1.0, (n, p)), jnp.float32),
        fl.path_mask(fast_net))
    # ground truth in float64: at ~100k route entries the float32
    # reference scatter itself drifts ~2e-6 normalized from the true sums
    # (accumulated rounding), so gating the compressed backends against
    # it at 1e-6 would fail on the REFERENCE's error — the f64 numpy
    # scatter is the arbiter instead (pt measures ~2e-7 against it)
    routes64 = np.asarray(fast_net.routes)
    sub64 = (np.asarray(rates, np.float64)[:, None]
             * np.asarray(split, np.float64))
    n_l = int(fast_net.n_links)
    true = np.zeros(n_l + 1)
    np.add.at(true, np.where(routes64 >= 0, routes64, n_l).ravel(),
              np.repeat(sub64.ravel(), routes64.shape[2]))
    true = true[:n_l]
    scale = max(1.0, float(np.abs(true).max()))
    ref = np.asarray(kref.fleet_offered_load_ref(
        fast_net.routes, rates, split, n_l)[:n_l])
    print(f"  offered_load[reference f32] vs f64 truth: "
          f"{float(np.abs(ref - true).max()) / scale:.2e} normalized")
    for be in ("pt", "pt_pallas"):
        got = np.asarray(fl.offered_load(fast_net, rates, split,
                                         backend=be))
        err = float(np.abs(got - true).max()) / scale
        print(f"  offered_load[{be}] vs f64 truth: {err:.2e} normalized")
        if err > 1e-6:
            raise SystemExit(f"offered_load[{be}] off by {err:.2e} "
                             "normalized (> 1e-6) vs f64 reference "
                             "scatter")

    # full epoch: compressed gathers (scale/mark/delay + loss thinning)
    # vs the flat reference composition
    qp = jnp.asarray(rng.uniform(0.0, 1.0, fast_net.n_links),
                     jnp.float32) * fast_net.qcap
    qv = jnp.asarray(rng.uniform(0.0, 1.0, fast_net.n_links),
                     jnp.float32) * fast_net.vcap
    ep_pt = fl.link_epoch(fast_net, rates, split, qp, qv, backend="pt",
                          with_loss=True)
    ep_ref = fl.link_epoch(fast_net, rates, split, qp, qv,
                           backend="reference", with_loss=True)
    for f in ep_pt._fields:
        a, b = getattr(ep_pt, f), getattr(ep_ref, f)
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        s = max(1.0, float(np.abs(b).max()))
        err = float(np.abs(a - b).max()) / s
        if err > 1e-5:
            raise SystemExit(f"link_epoch.{f} off by {err:.2e} "
                             "normalized (> 1e-5) pt vs reference")
    print("  link_epoch[pt] vs reference: all fields <= 1e-5 normalized")

    if jax.device_count() < 2:
        raise SystemExit(
            "equivalence check needs >= 2 devices for the sharded/halo "
            "variant — set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=2 before jax initializes")
    kw = dict(n_warm=190, n_meas=10)
    sf_pt = shard_scenario(net, params, is_inter=ii, lb=lb,
                           link_tier=tier, path_table=True)
    if sf_pt.layouts.path_table is None:
        raise SystemExit("equivalence check: sharded fat tree compiled "
                         "without per-shard PathTables")
    _, r_pt = steady_state_prepared(sf_pt, **kw)
    sf_flat = shard_scenario(net, params, is_inter=ii, lb=lb,
                             link_tier=tier, path_table=False)
    _, r_flat = steady_state_prepared(sf_flat, **kw)
    r_pt, r_flat = np.asarray(r_pt), np.asarray(r_flat)
    s = max(1.0, float(np.abs(r_flat).max()))
    err = float(np.abs(r_pt - r_flat).max()) / s
    print(f"  sharded steady state pt vs flat ({jax.device_count()} "
          f"devices): {err:.2e} normalized")
    if err > 1e-4:
        raise SystemExit(f"sharded pt steady state off by {err:.2e} "
                         "normalized (> 1e-4) vs flat sharding")
    print("  equivalence check passed")


def _main() -> None:
    ap = argparse.ArgumentParser(
        description="fleetsim throughput benchmark / scaling trajectory")
    ap.add_argument("--scaling", action="store_true",
                    help="run the full n_flows scaling curve and append "
                         "it to BENCH_fleetsim.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke subset of --scaling (10k flows, k=4 "
                         "fat tree, fast-path guards)")
    ap.add_argument("--quick", action="store_true",
                    help="with --scaling: stop at 100k flows")
    ap.add_argument("--backend", default="auto",
                    choices=list(fl.LOAD_BACKENDS),
                    help="load backend for the layout points (default "
                         "auto: PathTable-compressed where a table is "
                         "attached)")
    ap.add_argument("--block", type=int, default=None,
                    help="Pallas flow-block size override (default: "
                         "picked from n_flows)")
    ap.add_argument("--check-equivalence", action="store_true",
                    help="CI gate: pin the pt/pt_pallas backends to the "
                         "reference scatter on the smoke fat tree "
                         "(needs 2 forced host devices for the sharded "
                         "variant)")
    ap.add_argument("--fault-smoke", action="store_true",
                    help="CI gate: kill a WAN path mid-run on a small "
                         "multipath dumbbell; assert finite recovery and "
                         "write results/fault_smoke.json")
    args = ap.parse_args()

    if args.fault_smoke:
        _fault_smoke()
    elif args.check_equivalence:
        check_equivalence()
    elif args.scaling or args.smoke:
        mode = "smoke" if args.smoke else \
            ("quick" if args.quick else "full")
        scaling_curve(mode, backend=args.backend, block=args.block)
    else:
        print(json.dumps(run(quick=True), indent=1))


if __name__ == "__main__":
    _main()
