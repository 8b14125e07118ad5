"""Shared helpers for the paper-figure benchmarks.

Every benchmark module exposes `run(quick: bool) -> dict` and is registered in
`benchmarks.run`.  Results are written to results/paper/<name>.json and a
one-line summary is printed (tee'd into bench_output.txt by the top-level
driver).

Scale note: the paper simulates 1 GiB incast flows and open-loop workloads in
htsim (C++).  This simulator is faithful but runs in Python on one core, so
`quick` mode scales flow sizes/counts down (ratios — RTT gap, BDP gap, load —
are preserved; EXPERIMENTS.md records the scaling next to each result).
"""
from __future__ import annotations

import json
import pathlib
import statistics
import time

from repro.compile_cache import use_compile_cache

# every benchmark imports this module before its first compile
use_compile_cache()

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "paper"

MS = 1_000_000.0
US = 1_000.0
KIB = 1024
MIB = 1024 * 1024

SCHEMES = ("uno", "gemini", "mprdma+bbr")


def save(name: str, payload: dict) -> None:
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{name}.json").write_text(json.dumps(payload, indent=1))


def pctl(xs, q: float):
    xs = sorted(xs)
    if not xs:
        return None
    i = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[i]


def summarize_ms(xs):
    if not xs:
        return {}
    return {"n": len(xs),
            "mean_ms": statistics.mean(xs) / MS,
            "p50_ms": pctl(xs, 0.50) / MS,
            "p99_ms": pctl(xs, 0.99) / MS,
            "max_ms": max(xs) / MS}


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.wall_s = round(time.time() - self.t0, 1)


def new_net(scheme: str, *, kind: str = "fattree", seed: int = 0, **kw):
    """Build the paper topology; Uno runs get phantom queues (§4.1.3).

    Dumbbells go through the shared scenario layer (repro.scenarios) — the
    same spec repro.fleetsim compiles — so netsim and fleetsim agree on
    link layout and flow->bottleneck assignment.  `kw` forwards to
    `dumbbell_scenario` (n_intra/n_inter/...) for dumbbells (defaulting to
    the paper's 4+4 incast with the WAN as separate sprayable border
    links — the aggregated-pipe view is a fluid-only approximation) and to
    TwoDCFatTree otherwise.
    """
    if kind == "fattree":
        from repro.netsim.topology import TwoDCFatTree
        net = TwoDCFatTree(seed=seed, **kw)
        if scheme.startswith("uno"):
            net.attach_phantoms()
        return net
    from repro.scenarios import dumbbell_scenario, to_netsim
    kw.setdefault("n_intra", 4)
    kw.setdefault("n_inter", 4)
    kw.setdefault("multipath", True)
    spec = dumbbell_scenario(seed=seed, phantom=scheme.startswith("uno"),
                             **kw)
    return to_netsim(spec)


def scheme_lb(scheme: str, default_uno_lb: str = "unolb") -> tuple[str, str]:
    """'uno' -> UnoCC+UnoLB, 'uno+ecmp' -> UnoCC+ECMP, baselines -> ECMP."""
    if scheme == "uno":
        return "uno", default_uno_lb
    if scheme.startswith("uno+"):
        return "uno", scheme.split("+", 1)[1]
    return scheme, "ecmp"


def drain(net, until, step=None):
    net.sim.run(until=until)
