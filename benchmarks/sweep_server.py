"""Thin CLI over the persistent sweep service (repro.fleetsim.service).

Two jobs: (1) serve what-if queries as a batch-planned, streamed JSONL
pipeline; (2) measure and gate the warm/cold service economics in the
BENCH_fleetsim.json trajectory.

USAGE

  # Evaluate queries from a JSONL file (one query object per line),
  # streaming one JSONL result line per completed cell to stdout:
  #
  #   echo '{"kind": "fat_tree", "k": 4, "n_flows": 2000, "n_warm": 2000,
  #          "n_meas": 500}'  > queries.jsonl
  #   echo '{"kind": "dumbbell", "n_intra": 64, "n_inter": 64,
  #          "drain_frac": 0.85}' >> queries.jsonl
  python -m benchmarks.sweep_server --queries queries.jsonl

  # Same, reading stdin and appending results to a file:
  cat queries.jsonl | python -m benchmarks.sweep_server --queries - \
      --out results.jsonl

  # Warm/cold service benchmark (smoke scale; appends service points to
  # the current BENCH_fleetsim.json entry, gated by benchmarks/compare.py):
  python -m benchmarks.sweep_server --bench --smoke

Query objects take "kind" ("dumbbell" | "fat_tree"), the run config keys
("scheme", "n_warm", "n_meas", "seed", "backend"), and any scalar builder
kwargs (k, n_wan, n_flows, drain_frac, ...).  Scenarios compile through
the content-addressed cache ($UNO_SCENARIO_CACHE, or --cache-dir): the
first process to request a spec builds and publishes its .npz bundle,
every later one loads it.  Same-shape queries batch through the bucket
ladder into shared vmapped executables; results stream as each batch
completes, tagged with the query index ("id") and originating input line
("line").  A malformed or unservable line — broken JSON, missing/unknown
"kind", kwargs the builder rejects — emits a per-query
{"error": ..., "line": N} record and the stream keeps draining: one
poisoned query must never take down the batch behind it.  A final
"stats" line reports every cache layer (scenario bundles, grid traces,
sharded-executable hits).

THE BENCHMARK (--bench) measures, and CI gates:
  * cold_s:  fresh cache dir -> spec build + bundle publish + first
             query (trace + compile + scan), end to end;
  * warm_s:  the same query repeated in-process (pure scan) — must be
             >= FLEETSIM_SERVICE_SPEEDUP x faster than cold (default
             20x full / 6x smoke);
  * bundle_load_s: a fresh service on the warm cache dir (the
             cold-process path: bundle load replaces the spec build);
  * a 4-query drain-frac what-if batch: must add AT MOST ONE grid trace
    cold and ZERO warm, recording steady-state queries/s;
  * two passes of a mixed dumbbell + fat-tree batch: the second pass
    must hit the caches end to end (0 spec builds, 0 new traces).
Points land as path="service-cold" / "service-warm" / "service-batch4"
under the fat-tree variant, merged into the current trajectory entry
(same git sha + mode) so benchmarks/compare.py diffs and floors them
against the previous run like every other point.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

import jax

from repro.compile_cache import use_compile_cache
from repro.fleetsim import service, sweeps

# end-to-end cold/warm floor per bench mode (CI gate; env-overridable for
# noisy shared runners)
_SPEEDUP_FLOOR = {"smoke": 6.0, "full": 20.0}

_QUERY_KEYS = ("scheme", "n_warm", "n_meas", "seed", "backend")


def _parse_query(line: str, defaults: dict):
    obj = json.loads(line)
    kind = obj.pop("kind")
    cfg = {k: obj.pop(k) for k in _QUERY_KEYS if k in obj}
    cfg = {**defaults, **cfg}
    return kind, obj, cfg


def serve(args) -> int:
    svc = service.SweepService(cache_dir=args.cache_dir)
    src = sys.stdin if args.queries == "-" else open(args.queries)
    out = sys.stdout if args.out is None else open(args.out, "a")
    defaults = {"n_warm": args.n_warm, "n_meas": args.n_meas}
    queries, qlines = [], []
    with src:
        for lineno, line in enumerate(src, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                kind, kwargs, cfg = _parse_query(line, defaults)
                fs = svc.scenario(kind, **kwargs)
                queries.append(service.SweepQuery(fs, **cfg))
                qlines.append(lineno)
            except Exception as e:
                # per-line isolation: a poisoned query emits an error
                # record and the rest of the stream keeps draining
                print(json.dumps({"error": f"{type(e).__name__}: {e}",
                                  "line": lineno}), file=out, flush=True)
    t0 = time.time()
    for qid, _final, rates in svc.stream(queries):
        rec = {"id": qid, "line": qlines[qid],
               "wall_s": round(time.time() - t0, 3),
               **service.summarize_rates(rates)}
        print(json.dumps(rec), file=out, flush=True)
    print(json.dumps({"stats": svc.stats()}), file=out, flush=True)
    if out is not sys.stdout:
        out.close()
    return 0


# ------------------------------------------------------------- benchmark

def drain_whatifs(fs, factors):
    """Shape-compatible what-if cells: the same compiled scenario with the
    phantom drain target scaled per cell (a capacity-planning knob)."""
    return [(fs.net._replace(drain=fs.net.drain * f), fs.params,
             fs.is_inter, fs.lb, fs.churn, fs.rel) for f in factors]


def _merge_into_trajectory(points: list, mode: str) -> None:
    """Append service points to the CURRENT trajectory entry (same git
    sha + mode — the CI run that just produced the fleetsim_sweep entry),
    so compare.py sees one entry per run; standalone runs append a fresh
    entry instead."""
    from benchmarks.fleetsim_sweep import (BENCH_PATH, _git_sha,
                                           load_history)
    import datetime
    hist = load_history()
    sha = _git_sha()
    if hist and hist[-1].get("meta", {}).get("git_sha") == sha \
            and hist[-1].get("meta", {}).get("mode") == mode:
        entry = hist[-1]
        keyed = {(p["n_flows"], p.get("variant"), p["path"]): i
                 for i, p in enumerate(entry["points"])}
        for p in points:
            k = (p["n_flows"], p.get("variant"), p["path"])
            if k in keyed:
                entry["points"][keyed[k]] = p
            else:
                entry["points"].append(p)
    else:
        hist.append({"meta": {
            "generated": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "git_sha": sha, "mode": mode, "cpu_count": os.cpu_count(),
            "jax": jax.__version__,
            "scenario": "sweep_server service bench"}, "points": points})
    BENCH_PATH.write_text(json.dumps(
        {"schema": "trajectory-v1", "history": hist}, indent=1))
    print(f"merged {len(points)} service points into {BENCH_PATH}")


def bench(mode: str, cache_dir=None, refresh_floor=None) -> dict:
    """The warm/cold service benchmark + CI assertions (see module doc)."""
    k, n = (4, 12_000) if mode == "smoke" else (8, 100_000)
    # short steady-state windows on purpose: the service bench measures
    # what the caches amortize (spec build + bundle + trace + compile),
    # so the scan must not dominate the warm side — epoch-count scaling
    # itself is the scaling curve's job (fleetsim_sweep)
    n_warm, n_meas = (150, 30) if mode == "smoke" else (10, 2)
    ne = n_warm + n_meas
    floor = refresh_floor if refresh_floor is not None else float(
        os.environ.get("FLEETSIM_SERVICE_SPEEDUP", _SPEEDUP_FLOOR[mode]))
    cache_dir = pathlib.Path(
        cache_dir or tempfile.mkdtemp(prefix="uno_svc_bench_"))
    ft_kw = dict(k=k, n_wan=k, n_flows=n, n_paths=8, seed=1)
    cfg = dict(n_warm=n_warm, n_meas=n_meas)

    # cold: spec build + bundle publish + trace + compile + scan
    svc = service.SweepService(cache_dir=cache_dir)
    t0 = time.time()
    fs = svc.scenario("fat_tree", **ft_kw)
    spec_build_s = time.time() - t0
    q = service.SweepQuery(fs, **cfg)
    svc.submit([q])
    cold_s = time.time() - t0

    # warm: the same query, in-process (executable + scenario memo hit)
    warm_s = float("inf")
    for _ in range(3):
        t0 = time.time()
        svc.submit([q])
        warm_s = min(warm_s, time.time() - t0)
    speedup = cold_s / warm_s

    # cold-process-with-warm-cache: fresh service, same cache dir — the
    # bundle load is what replaces the ~10s spec build across processes
    svc2 = service.SweepService(cache_dir=cache_dir)
    t0 = time.time()
    svc2.scenario("fat_tree", **ft_kw)
    bundle_load_s = time.time() - t0
    assert svc2.stats()["scenario_cache"]["disk_hits"] == 1, \
        "second process missed the scenario bundle"

    # 4-query what-if batch: one rung-4 executable, at most one new trace
    whatifs = [service.SweepQuery(c, **cfg) for c in
               drain_whatifs(fs, (0.80, 0.85, 0.90, 0.95))]
    tr0 = sweeps.grid_traces()
    t0 = time.time()
    svc.submit(whatifs)
    batch_cold_s = time.time() - t0
    batch_traces = sweeps.grid_traces() - tr0
    t0 = time.time()
    svc.submit(whatifs)
    batch_warm_s = time.time() - t0
    warm_traces = sweeps.grid_traces() - tr0 - batch_traces
    qps = len(whatifs) / batch_warm_s

    # mixed dumbbell + fat-tree batch, twice: pass 2 must be all-warm
    db_kw = dict(n_intra=1_000, n_inter=1_000, multipath=True, n_wan=4)
    def mixed(s):
        return [service.SweepQuery(s.scenario("dumbbell", **db_kw), **cfg),
                service.SweepQuery(s.scenario("fat_tree", **ft_kw), **cfg)]
    t0 = time.time()
    svc.submit(mixed(svc))
    mixed_pass1_s = time.time() - t0
    svc3 = service.SweepService(cache_dir=cache_dir)   # fresh process-alike
    tr1 = sweeps.grid_traces()
    t0 = time.time()
    svc3.submit(mixed(svc3))
    mixed_pass2_s = time.time() - t0
    pass2 = svc3.stats()["scenario_cache"]
    pass2_traces = sweeps.grid_traces() - tr1

    rec = {
        "mode": mode, "k": k, "n_flows": n, "n_epochs": ne,
        "spec_build_s": round(spec_build_s, 2),
        "bundle_load_s": round(bundle_load_s, 3),
        "cold_s": round(cold_s, 2), "warm_s": round(warm_s, 3),
        "warm_speedup": round(speedup, 1),
        "speedup_floor": floor,
        "batch": {"n_queries": len(whatifs), "cold_traces": batch_traces,
                  "warm_traces": warm_traces,
                  "cold_s": round(batch_cold_s, 2),
                  "warm_s": round(batch_warm_s, 3),
                  "queries_per_s": round(qps, 2)},
        "mixed_two_pass": {"pass1_s": round(mixed_pass1_s, 2),
                           "pass2_s": round(mixed_pass2_s, 3),
                           "pass2_builds": pass2["builds"],
                           "pass2_disk_hits": pass2["disk_hits"],
                           "pass2_traces": pass2_traces},
        "stats": svc.stats(),
    }
    print(json.dumps(rec, indent=1))

    failures = []
    if speedup < floor:
        failures.append(f"warm speedup {speedup:.1f}x < {floor}x floor "
                        f"(cold {cold_s:.1f}s, warm {warm_s:.2f}s)")
    if batch_traces > 1:
        failures.append(f"4-query what-if batch traced {batch_traces}x "
                        "cold (must batch into <= 1 vmapped trace)")
    if warm_traces != 0:
        failures.append(f"warm 4-query batch re-traced {warm_traces}x")
    if pass2["builds"] != 0:
        failures.append(f"mixed pass 2 rebuilt {pass2['builds']} "
                        "scenario(s) — bundle cache missed")
    if pass2_traces != 0:
        failures.append(f"mixed pass 2 traced {pass2_traces}x — "
                        "executable cache missed")
    if failures:
        raise SystemExit("service bench failed:\n  " + "\n  ".join(failures))

    variant = f"fat_tree_k{k}"
    points = [
        {"n_flows": n, "n_epochs": ne, "variant": variant,
         "path": "service-cold", "warm_s": round(cold_s, 2),
         "flow_epochs_per_s": round(n * ne / cold_s),
         "spec_build_s": round(spec_build_s, 2)},
        {"n_flows": n, "n_epochs": ne, "variant": variant,
         "path": "service-warm", "warm_s": round(warm_s, 3),
         "flow_epochs_per_s": round(n * ne / warm_s),
         "warm_speedup": round(speedup, 1),
         "bundle_load_s": round(bundle_load_s, 3)},
        {"n_flows": n, "n_epochs": ne, "variant": variant,
         "path": "service-batch4", "warm_s": round(batch_warm_s, 3),
         "flow_epochs_per_s": round(len(whatifs) * n * ne / batch_warm_s),
         "queries_per_s": round(qps, 2)},
    ]
    for p in points:
        print("  ", json.dumps(p))
    _merge_into_trajectory(points, mode)

    from benchmarks import common
    common.RESULTS.parent.mkdir(parents=True, exist_ok=True)
    out_path = common.RESULTS.parent / "sweep_service.json"
    out_path.write_text(json.dumps(rec, indent=1))
    print(f"service bench written to {out_path}")
    return rec


def _main() -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.sweep_server",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--queries", metavar="FILE",
                    help="JSONL query file ('-' = stdin); one result "
                         "line streams out per completed cell")
    ap.add_argument("--out", default=None,
                    help="append result JSONL here instead of stdout")
    ap.add_argument("--cache-dir", default=None,
                    help="content-addressed scenario cache dir "
                         "(default $UNO_SCENARIO_CACHE or "
                         "~/.cache/uno_fleetsim/scenarios)")
    ap.add_argument("--n-warm", type=int, default=2_000,
                    help="default warmup epochs per query "
                         "(default %(default)s)")
    ap.add_argument("--n-meas", type=int, default=500,
                    help="default measured epochs per query "
                         "(default %(default)s)")
    ap.add_argument("--bench", action="store_true",
                    help="run the warm/cold service benchmark, assert "
                         "the cache guarantees, and merge service "
                         "points into BENCH_fleetsim.json")
    ap.add_argument("--smoke", action="store_true",
                    help="with --bench: CI scale (k=4 / 12k flows) "
                         "instead of k=8 / 100k")
    args = ap.parse_args()
    use_compile_cache()
    if args.bench:
        bench("smoke" if args.smoke else "full", cache_dir=args.cache_dir)
        return 0
    if args.queries:
        return serve(args)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(_main())
