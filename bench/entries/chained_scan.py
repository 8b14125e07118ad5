"""One steady-state query longer than any window, as chained scan calls.

Each call is `fleetsim.simulate` over `chunk_epochs` epochs from the state
the previous call returned (`state0`), recording each flow's goodput, so
the query's clocks, queues, recovery machine and fault schedule carry
across calls.  The query starts from each flow's cwnd drawn from the seed
in `cwnd0_frac` x BDP, with the seed's PRNG key driving the burst-loss
chains.  A chunk's answer is each flow's mean goodput over the chunk and
the state it hands on.

The check always takes the window's first chunk, run by the reference
from its own start state (built from the same seeded draw, and compared
with the program's), and a sample of later chunks, each run by the
reference from the state the program handed to it.

Traffic keys: chunk_epochs, cwnd0_frac [lo, hi], scenario (builder kwargs
on top of the configuration's: the conditions on the path), trace_calls,
sample_chunks.
"""
from __future__ import annotations

import time

import numpy as np

from bench import check as ck
from bench import harness, scenario


def _call(st, state0):
    from repro.fleetsim import simulate
    fs = st["fs"]
    return simulate(fs.net, fs.params, n_epochs=st["chunk"], state0=state0,
                    is_inter=fs.is_inter, lb=fs.lb, churn=fs.churn,
                    rel=fs.rel, fault=fs.fault, record=True)


def setup(run):
    import jax
    import jax.numpy as jnp
    from repro.fleetsim import init_state, uniform_split
    tr = run.cell["traffic"]
    fs = scenario.load(run)
    rng = np.random.default_rng(run.seed)
    lo, hi = tr["cwnd0_frac"]
    n = int(fs.params.bdp.shape[0])
    frac = jnp.asarray(rng.uniform(lo, hi, n), jnp.float32)
    seed = run.seed % (2 ** 31)
    state0 = init_state(fs.params, fs.net.n_links, fs.params.bdp * frac,
                        n_paths=fs.net.n_paths, split0=uniform_split(fs.net),
                        seed=seed, rel=fs.rel, fault=fs.fault)
    st = {"fs": fs, "chunk": int(tr["chunk_epochs"]), "state0": state0,
          "n_flows": n, "frac": frac, "seed": seed}
    with run.span("warmup"):
        jax.block_until_ready(_call(st, state0))
    return st


def window(run, st, prof) -> None:
    import jax
    work = st["n_flows"] * st["chunk"]
    state = [st["state0"]]

    def call():
        t0 = time.perf_counter()
        with run.span("call"):
            nxt, traj = _call(st, state[0])
        with run.span("block"):
            jax.block_until_ready((nxt, traj))
        start, state[0] = state[0], nxt
        answer = (start, nxt, traj)
        if "first" not in st:
            st["first"] = answer
        return t0, time.perf_counter(), work, st["chunk"], answer

    st["sample"] = harness.measure(run, prof, call,
                                   run.cell["traffic"]["sample_chunks"])


def _item(inp, start, answer, chunk) -> dict:
    _, nxt, traj = answer
    return {"inp": inp, "state0": start, "n_epochs": chunk,
            "n_meas": chunk,
            "prog": {"goodput": np.asarray(traj).mean(axis=0),
                     "cwnd": np.asarray(nxt.cwnd)}}


def items(run, st):
    """(reference inputs, program answer) of the first and every sampled
    chunk, and the (program, reference) start states."""
    from bench import reference as ref
    inp = ref.inputs(ck.scenario_inputs(st["fs"]))
    ref0 = ref.init_state(inp, inp["params"]["bdp"] * st["frac"],
                          seed=st["seed"])
    first = st.pop("first")
    out = [_item(inp, ref0, first, st["chunk"])]
    out += [_item(inp, ck.ref_state(a[0]), a, st["chunk"])
            for a in st["sample"] if a is not first]
    st["sample"] = []
    init = (ck.ref_state(st.pop("state0")), ref0)
    return out, init


def check(run, st) -> dict:
    answers, init = items(run, st)
    return ck.run_check(run, answers, init)
