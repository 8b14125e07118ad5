"""Closed-loop what-if batches through `SweepService.submit`.

One client submits `batch` what-if queries at a time and waits for the
answers before it sends the next batch.  Each query is the cell's scenario
with every link's phantom drain scaled by a factor drawn from the seed in
`drain_factor` (the capacity planner's knob); the batch shares one route
tensor, so the service runs it as one vmapped `sweeps._grid_core` call of
`n_warm + n_meas` epochs.  A query's answer is each flow's mean goodput
over its `n_meas` measured epochs.

Traffic keys: batch, n_warm, n_meas, drain_factor [lo, hi], backend,
trace_calls, sample_batches.
"""
from __future__ import annotations

import time

import numpy as np

from bench import check as ck
from bench import harness, scenario


def whatif_net(net, factor):
    """One what-if's links: the phantom drain target scaled by `factor`."""
    return net._replace(drain=net.drain * factor)


def _queries(st, factors, seeds):
    from repro.fleetsim import service
    tr, fs = st["traffic"], st["fs"]
    return [service.SweepQuery(
        (whatif_net(fs.net, f), fs.params, fs.is_inter, fs.lb, fs.churn,
         fs.rel), n_warm=tr["n_warm"], n_meas=tr["n_meas"], seed=int(s),
        backend=tr["backend"]) for f, s in zip(factors, seeds)]


def _draw(st):
    tr = st["traffic"]
    b = tr["batch"]
    factors = st["rng"].uniform(*tr["drain_factor"], b).tolist()
    seeds = st["rng"].integers(0, 2 ** 31 - 1, b)
    return factors, seeds


def setup(run):
    from repro.fleetsim import service
    tr = run.cell["traffic"]
    svc = service.SweepService(cache_dir=scenario.CACHE_DIR)
    fs = scenario.load(run)
    st = {"svc": svc, "fs": fs, "traffic": tr,
          "rng": np.random.default_rng(run.seed),
          "n_flows": int(fs.params.bdp.shape[0])}
    factors, seeds = _draw(st)
    with run.span("warmup"):
        svc.submit(_queries(st, factors, seeds))
    return st


def window(run, st, prof) -> None:
    from repro.fleetsim import sweeps
    tr = st["traffic"]
    epochs = tr["n_warm"] + tr["n_meas"]
    work = tr["batch"] * st["n_flows"] * epochs

    def call():
        with run.span("build"):
            factors, seeds = _draw(st)
            queries = _queries(st, factors, seeds)
        t0 = time.perf_counter()
        with run.span("submit"):
            out = st["svc"].submit(queries)
        return t0, time.perf_counter(), work, epochs, (factors, seeds, out)

    traces0 = sweeps.grid_traces()
    st["sample"] = harness.measure(run, prof, call, tr["sample_batches"])
    run.counters["window_grid_traces"] = sweeps.grid_traces() - traces0


def items(run, st):
    """(reference inputs, program answer) of every sampled query; a
    what-if has no start state of the program's to compare."""
    from bench import reference as ref
    tr = st["traffic"]
    fs = st["fs"]
    out = []
    for factors, seeds, answers in st["sample"]:
        for f, (_final, rates) in zip(factors, answers):
            inp = ref.inputs(ck.scenario_inputs(fs, whatif_net(fs.net, f)))
            out.append({"inp": inp, "state0": ref.init_state(inp),
                        "n_epochs": tr["n_warm"] + tr["n_meas"],
                        "n_meas": tr["n_meas"],
                        "prog": {"goodput": np.asarray(rates)}})
    st["sample"] = []
    return out, None


def check(run, st) -> dict:
    return ck.run_check(run, *items(run, st))
