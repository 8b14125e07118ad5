"""A run with its timed path broken underneath comes out not correct:
once for each fault a cell can have (a step that hands back its state
unchanged; half of a batch left out; an answer altered where it is
produced).  The cells run on one chip, so no exchange can be left out."""
import jax
import jax.numpy as jnp
import pytest

import tiny
from repro import fleetsim
from repro.fleetsim import cc, sweeps


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    tiny.isolate(monkeypatch, tmp_path)
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()


def stale_state(monkeypatch):
    make_step = cc.make_step

    def broken(*a, **kw):
        step = make_step(*a, **kw)

        def frozen(state, x):
            _, goodput = step(state, x)
            return state, goodput
        return frozen
    monkeypatch.setattr(cc, "make_step", broken)


def half_batch(monkeypatch):
    run_grid = sweeps.run_grid

    def broken(scenarios, **kw):
        half = len(scenarios) // 2
        kw["seeds"] = kw["seeds"][:half]
        final, rates = run_grid(list(scenarios[:half]), **kw)
        fill = lambda a: jnp.concatenate([a, a], axis=0)
        return jax.tree.map(fill, final), fill(rates)
    monkeypatch.setattr(sweeps, "run_grid", broken)


def half_flows(monkeypatch):
    simulate = fleetsim.simulate

    def broken(net, params, **kw):
        final, traj = simulate(net, params, **kw)
        n = params.bdp.shape[0]
        keep = jnp.arange(n) < n // 2
        cwnd = jnp.where(keep, final.cwnd, kw["state0"].cwnd)
        return final._replace(cwnd=cwnd), traj
    monkeypatch.setattr(fleetsim, "simulate", broken)


def altered_answer(monkeypatch, workload):
    if workload.endswith(".whatif_sweep"):
        run_grid = sweeps.run_grid

        def broken(scenarios, **kw):
            final, rates = run_grid(scenarios, **kw)
            return final, rates * 1.01
        monkeypatch.setattr(sweeps, "run_grid", broken)
    else:
        simulate = fleetsim.simulate

        def broken(net, params, **kw):
            final, traj = simulate(net, params, **kw)
            return final, traj * 1.01
        monkeypatch.setattr(fleetsim, "simulate", broken)


CASES = [(w, "stale_state") for w in tiny.CELLS] + \
    [("fat_tree_k8.whatif_sweep", "half_batch")] + \
    [(w, "half_flows") for w in tiny.CELLS[1:]] + \
    [(w, "altered_answer") for w in tiny.CELLS]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    plant = {"stale_state": stale_state, "half_batch": half_batch,
             "half_flows": half_flows}.get(fault)
    if plant is None:
        altered_answer(monkeypatch, workload)
    else:
        plant(monkeypatch)
    out = tiny.run(workload)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1
