"""Persistent sweep service: content-addressed scenario cache + batched
what-if query planning + streamed grid results.

The sweeps/shard layers already amortize work *within* a process (the
module-level `sweeps._grid_core` jit cache, `shard._compiled`'s lru), but
a capacity-planning service answers queries across many processes and
hosts, and the expensive artifacts — a k=8 fat-tree spec build is ~10s of
path-oracle work before jax even traces — died with each process.  This
module is the one-stop query surface over three layers of reuse:

**Content-addressed scenario cache.**  A scenario is addressed by the
hash of its *build request* — builder kind plus canonicalized kwargs
(k, n_wan, flow counts, seeds, Rel/Lb/Churn specs), defaults bound in so
`fat_tree(k=4)` and `fat_tree(k=4, n_paths=8)` share one address — NOT by
the built spec, because building the spec is exactly the cost being
avoided.  `cached_scenario` maps the request to a versioned `.npz` bundle
(FluidNet arrays, the compiled RouteLayout + optional PathTable,
FleetParams, lb/churn/rel families, `link_tier`) under
`$UNO_SCENARIO_CACHE` (default `~/.cache/uno_fleetsim/scenarios`): a cold
process loads the bundle instead of rebuilding the spec, and the
benchmark's sharded-subprocess handoff reuses the same artifact.  Writes
are atomic (tmp + rename); a corrupted or version-skewed bundle loads as
None and is rebuilt in place.  Bump `CACHE_VERSION` whenever the scenario
compiler's *output* changes — the version folds into every address, so
stale bundles are simply never hit again.

**Bucket-ladder query planner.**  `SweepService.submit/stream` buckets
queries by shape signature — the treedef + leaf shapes/dtypes of the
normalized scenario pytree plus the static config (scheme, n_warm,
n_meas, backend) — so only stackable queries share a batch.  Each bucket
is then cut against `ladder` (default 1/2/4/8/16): greedily the largest
rung that fits, descending, with a remainder below the smallest rung
padded UP to it by replicating the last cell.  N same-shape queries thus
cost one `run_grid` trace per rung shape (which recur, and
`sweeps._grid_core`'s cache persists), at most `len(ladder)` distinct
executables exist per signature, and padding — wasted scan compute —
never happens with 1 on the ladder.  Per-query seeds ride an explicit seeds array, so
a cell's result is independent of which batch the planner put it in.

**Streamed partial results.**  `SweepService.stream` yields
`(query_index, final_state, rates)` per completed cell as each rung batch
finishes (bucket by bucket, submission order within a bucket);
`sweeps.run_grid_streamed` is the same idea for one homogeneous grid.
`benchmarks/sweep_server.py` is the thin CLI: JSONL queries in, JSONL
results out as they complete, plus the warm/cold service benchmark.

`SweepService.stats()` reports all three layers: scenario-cache
memo/disk/build counts, `sweeps.grid_traces()`, and the sharded
executable cache's hit/miss counters (`shard.cache_stats`).
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import tempfile
import zipfile
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleetsim import links as fl
from repro.fleetsim import shard, sweeps
from repro.fleetsim.faults import FaultSchedule
from repro.fleetsim.reliability import RelParams
from repro.fleetsim.state import ChurnParams, FleetParams, LbParams

# bump when the bundle format OR the scenario compiler's output changes:
# the version folds into every content address, so old bundles are
# orphaned (never loaded) rather than trusted.
# v2: Scenario grew the fault axis (FaultSchedule family in bundles,
# `faults` in every spec fingerprint) and RelParams grew the optional
# ladder fields.
# v3: the N-datacenter topology layer — FleetScenario grew `link_dc`,
# the "multi_dc" builder joined the registry, and `_home_links` switched
# to per-flow hub counting (shard plans, and thus any cached plan-derived
# payloads, differ from v2 for multipath scenarios).
# v4: RouteLayout's one route index is the hop-major, lane-padded
# `hop_idx` (in place of the flow-major `pad_idx` and `hop_mask`).
CACHE_VERSION = 4

_META_KEY = "__meta__"

# (prefix, NamedTuple type) families the bundle [de]serializes generically
_FAMILIES = (("par_", FleetParams), ("lb_", LbParams),
             ("churn_", ChurnParams), ("rel_", RelParams),
             ("fault_", FaultSchedule))

_EVICTIONS = [0]        # process-lifetime prune_cache eviction counter


def default_cache_dir() -> pathlib.Path:
    """$UNO_SCENARIO_CACHE, else ~/.cache/uno_fleetsim/scenarios."""
    env = os.environ.get("UNO_SCENARIO_CACHE")
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "uno_fleetsim" / "scenarios"


def cache_size_cap() -> int:
    """$FLEETSIM_CACHE_BYTES as an int cap; 0 / unset / junk = unlimited."""
    try:
        return max(int(os.environ.get("FLEETSIM_CACHE_BYTES", "0")), 0)
    except ValueError:
        return 0


def prune_cache(cache_dir=None, max_bytes: Optional[int] = None) -> int:
    """Evict least-recently-used bundles until the cache fits `max_bytes`.

    Recency is file mtime — `load_bundle` touches a bundle on every
    successful read, so mtime order IS access order.  `max_bytes` defaults
    to `$FLEETSIM_CACHE_BYTES` (0 = unlimited: no-op).  Runs after every
    `save_bundle`, so any writer keeps the shared cache bounded; returns
    the number of bundles evicted (also accumulated into `cache_stats`).
    """
    if max_bytes is None:
        max_bytes = cache_size_cap()
    if max_bytes <= 0:
        return 0
    root = pathlib.Path(cache_dir or default_cache_dir())
    sized = []
    try:
        for p in root.glob("*.npz"):
            with contextlib.suppress(OSError):
                st = p.stat()
                sized.append((st.st_mtime, st.st_size, p))
    except OSError:
        return 0
    sized.sort()                       # oldest access first
    total = sum(s for _, s, _ in sized)
    evicted = 0
    for _, size, p in sized:
        if total <= max_bytes:
            break
        with contextlib.suppress(OSError):
            p.unlink()
            total -= size
            evicted += 1
    _EVICTIONS[0] += evicted
    return evicted


def cache_stats(cache_dir=None) -> dict:
    """On-disk scenario-cache occupancy + this process's eviction count."""
    root = pathlib.Path(cache_dir or default_cache_dir())
    n = total = 0
    with contextlib.suppress(OSError):
        for p in root.glob("*.npz"):
            with contextlib.suppress(OSError):
                total += p.stat().st_size
                n += 1
    return {"bundles": n, "bytes": total,
            "max_bytes": cache_size_cap(), "evictions": _EVICTIONS[0]}


def bundle_path(key: str, cache_dir=None) -> pathlib.Path:
    return pathlib.Path(cache_dir or default_cache_dir()) / f"{key}.npz"


# ------------------------------------------------------- content addresses

def scenario_key(kind: str, **kwargs) -> str:
    """Content address of a scenario BUILD REQUEST.

    Binds `kwargs` against the builder's signature with defaults applied
    (so explicitly passing a default value does not change the address),
    then fingerprints (kind, bound kwargs, CACHE_VERSION).  NamedTuple
    values — LbSpec, ChurnSpec, RelSpec — fingerprint structurally, so a
    changed EC geometry or churn duty cycle changes the address.
    """
    import inspect

    from repro.scenarios.spec import fingerprint
    bound = inspect.signature(_builder(kind)).bind(**kwargs)
    bound.apply_defaults()
    return fingerprint({"kind": kind, "kwargs": dict(bound.arguments)},
                       CACHE_VERSION)


def _builder(kind: str):
    from repro.scenarios import (dumbbell_scenario, fat_tree_spec,
                                 multi_dc_spec)
    builders = {"dumbbell": dumbbell_scenario, "fat_tree": fat_tree_spec,
                "multi_dc": multi_dc_spec}
    if kind not in builders:
        raise ValueError(f"unknown scenario kind {kind!r}; "
                         f"expected one of {sorted(builders)}")
    return builders[kind]


# --------------------------------------------------------- bundle save/load

def save_bundle(path, fs, *, key: str = "") -> pathlib.Path:
    """Write a FleetScenario to a content-addressed `.npz` bundle.

    Atomic: the arrays land in a same-directory tempfile that is renamed
    over `path`, so concurrent writers (two benchmark runs racing on one
    host) and readers never observe a partial bundle.  None-valued
    optional members (lb/churn/rel/fault/p_loss/is_inter/link_tier/
    link_dc/layout) are simply absent — presence is part of the format, and the
    loader reconstructs the same Nones; the rule applies per FIELD inside
    a family too (a ladder-less RelParams stores no ladder arrays).
    """
    path = pathlib.Path(path)
    net = fs.net
    arrays = {"net_" + f: np.asarray(getattr(net, f))
              for f in net._fields
              if f != "layout" and getattr(net, f) is not None}
    if net.layout is not None:
        arrays.update(fl.layout_to_arrays(net.layout))
    for prefix, cls in _FAMILIES:
        field = prefix.rstrip("_")
        val = getattr(fs, "params" if field == "par" else field, None)
        if val is not None:
            arrays.update({prefix + f: np.asarray(getattr(val, f))
                           for f in cls._fields
                           if getattr(val, f) is not None})
    if fs.is_inter is not None:
        arrays["is_inter"] = np.asarray(fs.is_inter)
    if fs.link_tier is not None:
        arrays["link_tier"] = np.asarray(fs.link_tier)
    if fs.link_dc is not None:
        arrays["link_dc"] = np.asarray(fs.link_dc)
    arrays[_META_KEY] = np.asarray(json.dumps(
        {"version": CACHE_VERSION, "key": key, "seed": int(fs.seed)}))
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise
    prune_cache(path.parent)
    return path


def _load_family(z, prefix: str, cls):
    """One family out of an open npz, or None when the family is absent.

    A field missing from the bundle loads as None only when the class
    declares None as its default (the optional trailing fields); a
    missing REQUIRED field raises KeyError, which `load_bundle` treats
    as an untrustworthy bundle.
    """
    if not any(k.startswith(prefix) for k in z.files):
        return None
    vals = {}
    for f in cls._fields:
        k = prefix + f
        if k in z:
            vals[f] = jnp.asarray(z[k])
        elif cls._field_defaults.get(f, _MISSING) is None:
            vals[f] = None
        else:
            raise KeyError(k)
    return cls(**vals)


_MISSING = object()


def load_bundle(path):
    """Load a bundle back into a FleetScenario, or None when it cannot be
    trusted — missing, truncated, corrupted, wrong format version, or
    missing required arrays all degrade to None so the caller rebuilds
    from the spec and overwrites (a cache must never crash its process).
    """
    from repro.scenarios.compile_fleetsim import FleetScenario
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z[_META_KEY][()]))
            if meta.get("version") != CACHE_VERSION:
                return None
            net_kw = {f: jnp.asarray(z["net_" + f])
                      for f in fl.FluidNet._fields
                      if "net_" + f in z}
            net = fl.FluidNet(**net_kw,
                              layout=fl.layout_from_arrays(z))
            fams = {prefix: _load_family(z, prefix, cls)
                    for prefix, cls in _FAMILIES}
            fs = FleetScenario(
                net=net, params=fams["par_"], lb=fams["lb_"],
                churn=fams["churn_"], rel=fams["rel_"],
                fault=fams["fault_"],
                is_inter=(jnp.asarray(z["is_inter"])
                          if "is_inter" in z else None),
                link_tier=(np.asarray(z["link_tier"])
                           if "link_tier" in z else None),
                link_dc=(np.asarray(z["link_dc"])
                         if "link_dc" in z else None),
                seed=int(meta.get("seed", 0)))
        # a read is a cache hit: refresh mtime so prune_cache's
        # LRU-by-mtime order tracks ACCESS recency, not write recency
        with contextlib.suppress(OSError):
            os.utime(path)
        return fs
    except (OSError, ValueError, KeyError, TypeError, EOFError,
            zipfile.BadZipFile, json.JSONDecodeError):
        return None


def cached_scenario(kind: str, *, cache_dir=None, refresh: bool = False,
                    **kwargs):
    """Compile a scenario through the content-addressed cache.

    Returns `(FleetScenario, source)` with source in {"disk", "build"}:
    "disk" loaded the existing bundle (no spec build, no layout
    compilation); "build" ran the spec builder + `to_fleetsim` and
    published the bundle for every later process.  `refresh=True` forces
    a rebuild (and overwrites the bundle) — the escape hatch when the
    compiler changed without a CACHE_VERSION bump.
    """
    key = scenario_key(kind, **kwargs)
    path = bundle_path(key, cache_dir)
    if not refresh:
        fs = load_bundle(path)
        if fs is not None:
            return fs, "disk"
    from repro.scenarios import to_fleetsim
    fs = to_fleetsim(_builder(kind)(**kwargs))
    save_bundle(path, fs, key=key)
    return fs, "build"


def publish_scenario(fs, key: str, cache_dir=None) -> pathlib.Path:
    """Ensure an already-compiled scenario's bundle exists; return its path.

    The dedupe primitive for callers that built the arrays themselves
    (the benchmark's subprocess handoff): same key -> the bundle is
    written once per host, then every run just points at it.
    """
    path = bundle_path(key, cache_dir)
    if not path.exists():
        save_bundle(path, fs, key=key)
    return path


# ------------------------------------------------------------ query planner

DEFAULT_LADDER = (1, 2, 4, 8, 16)


class SweepQuery(NamedTuple):
    """One what-if query: a scenario plus its static run config.

    `scenario` is anything `sweeps.run_grid` accepts as a cell — a
    FleetScenario or a bare (net, params, is_inter[, lb[, churn[, rel]]])
    tuple.  Queries sharing a shape signature AND identical (scheme,
    n_warm, n_meas, backend) batch into one vmapped executable; `seed`
    stays per-query (an explicit seeds array rides into the grid).
    """
    scenario: object
    scheme: str = "uno"
    n_warm: int = 2_000
    n_meas: int = 500
    seed: int = 0
    backend: str = "auto"


def _query_signature(q: SweepQuery):
    norm = sweeps._norm_scenario(q.scenario)
    leaves, treedef = jax.tree.flatten(norm)
    shapes = tuple((jnp.shape(x), np.dtype(jnp.result_type(x)).name)
                   for x in leaves)
    return (treedef, shapes, q.scheme, q.n_warm, q.n_meas, q.backend)


def _cut_ladder(n: int, ladder: Sequence[int]):
    """Decompose a bucket of n cells into ladder rungs.

    Yields (n_live, rung): greedily the largest rung that fits, descending
    until no rung fits, then the remainder padded UP to the smallest rung.
    At most len(ladder) distinct batch shapes ever exist per signature,
    and padding — which wastes real scan compute per padded cell — only
    happens when the remainder is below the smallest rung (never with 1
    on the ladder).
    """
    rungs = sorted(set(int(r) for r in ladder))
    if not rungs or rungs[0] < 1:
        raise ValueError(f"ladder must be positive ints, got {ladder!r}")
    while n > 0:
        if n >= rungs[0]:
            rung = max(r for r in rungs if r <= n)
            yield rung, rung
            n -= rung
        else:
            yield n, rungs[0]
            n = 0


class SweepService:
    """The persistent query surface: scenario cache + planner + streaming.

    One instance per process; scenarios load through the shared on-disk
    cache (plus an in-memory memo, so repeat queries against the same
    address cost a dict lookup), queries batch through the bucket ladder,
    and `stats()` reports every cache layer.  Thread-unsafe by design —
    wrap submissions in your own executor if you need concurrency.
    """

    def __init__(self, cache_dir=None, ladder=DEFAULT_LADDER):
        self.cache_dir = pathlib.Path(cache_dir or default_cache_dir())
        self.ladder = tuple(ladder)
        self._memo: dict = {}
        self._stats = {"memo_hits": 0, "disk_hits": 0, "builds": 0,
                       "queries": 0, "batches": 0, "padded_cells": 0}

    # ------------------------------------------------------------ scenarios

    def scenario(self, kind: str, *, refresh: bool = False, **kwargs):
        """`cached_scenario` + in-memory memo; returns the FleetScenario."""
        key = scenario_key(kind, **kwargs)
        if not refresh and key in self._memo:
            self._stats["memo_hits"] += 1
            return self._memo[key]
        fs, source = cached_scenario(kind, cache_dir=self.cache_dir,
                                     refresh=refresh, **kwargs)
        self._stats["disk_hits" if source == "disk" else "builds"] += 1
        self._memo[key] = fs
        return fs

    # -------------------------------------------------------------- queries

    def stream(self, queries: Sequence[SweepQuery]):
        """Yield `(query_index, final_state, rates)` per completed cell.

        Cells arrive bucket by bucket (same-signature queries together),
        in submission order within a bucket, as each rung batch finishes
        — the streamed-partial-results contract.  Results are identical
        to running each query alone (per-query seeds; padding cells are
        replicas whose outputs are dropped).
        """
        queries = list(queries)
        buckets: dict = {}
        for i, q in enumerate(queries):
            buckets.setdefault(_query_signature(q), []).append(i)
        for sig, idxs in buckets.items():
            q0 = queries[idxs[0]]
            pos = 0
            for live, rung in _cut_ladder(len(idxs), self.ladder):
                take = idxs[pos:pos + live]
                pos += live
                cells = [queries[i].scenario for i in take]
                seeds = [queries[i].seed for i in take]
                if live < rung:
                    cells += [cells[-1]] * (rung - live)
                    seeds += [seeds[-1]] * (rung - live)
                    self._stats["padded_cells"] += rung - live
                final, rates = sweeps.run_grid(
                    cells, scheme=q0.scheme, n_warm=q0.n_warm,
                    n_meas=q0.n_meas, seeds=np.asarray(seeds, np.int32),
                    backend=q0.backend)
                jax.block_until_ready(rates)
                self._stats["batches"] += 1
                self._stats["queries"] += live
                for j, qid in enumerate(take):
                    yield (qid, jax.tree.map(lambda a, k=j: a[k], final),
                           rates[j])

    def submit(self, queries: Sequence[SweepQuery]):
        """Blocking `stream`: list of (final_state, rates) in input order."""
        out = [None] * len(queries)
        for qid, final, rates in self.stream(queries):
            out[qid] = (final, rates)
        return out

    # ---------------------------------------------------------------- stats

    def stats(self) -> dict:
        """Effectiveness of every cache layer, for reports and CI guards."""
        return {"scenario_cache": dict(self._stats),
                "bundle_cache": cache_stats(self.cache_dir),
                "grid_traces": sweeps.grid_traces(),
                "executable_cache": shard.cache_stats(),
                "ladder": self.ladder,
                "cache_dir": str(self.cache_dir)}


def summarize_rates(rates) -> dict:
    """Compact per-cell result summary (what the CLI emits as JSONL)."""
    r = np.asarray(rates)
    return {"n_flows": int(r.shape[-1]),
            "mean_rate": round(float(r.mean()), 6),
            "min_rate": round(float(r.min()), 6),
            "max_rate": round(float(r.max()), 6),
            "jain": round(float(sweeps.jain(jnp.asarray(r))), 4)}
