"""fleetsim's own trace names: the `jax.named_scope` stages every scan
epoch is traced in (they land in each HLO op's `op_name` metadata), and
the `TraceAnnotation` host phases of `simulate` and the sweep service
(they land in the profiler's host events).  The benchmark's per-stage
metrics read both (bench/stages.py)."""
import glob
import re

import jax
import numpy as np
import pytest

from repro.fleetsim import cc, service, sweeps
from repro.scenarios import FaultSpec, RelSpec, dumbbell_scenario, \
    to_fleetsim
from repro.scenarios.spec import MS

STAGES = {"fleetsim.offered_load", "fleetsim.link_gathers", "fleetsim.cc",
          "fleetsim.reliability", "fleetsim.faults"}


@pytest.fixture(scope="module")
def lossy():
    """Four flows over two WAN paths with random loss, EC + NACK
    recovery, and a flapping WAN link: every stage does work."""
    return to_fleetsim(dumbbell_scenario(
        2, 2, multipath=True, n_wan=2, wan_p_loss=1e-3,
        inter_rel=RelSpec(),
        faults=(FaultSpec(link="wan0", kind="flap", period=1 * MS,
                          duty=0.5),)))


def _scopes(compiled) -> set:
    return {seg for op in re.findall(r'op_name="([^"]*)"',
                                     compiled.as_text())
            for seg in op.split("/") if seg.startswith("fleetsim.")}


def test_simulate_names_every_stage(lossy):
    s0 = cc._default_state(lossy.net, lossy.params, 0, lossy.rel,
                           lossy.fault)
    compiled = cc._simulate.lower(
        lossy.net, lossy.params, s0, lossy.is_inter, lossy.lb, lossy.churn,
        "uno", 3, True, "auto", None, lossy.rel, lossy.fault).compile()
    assert _scopes(compiled) == STAGES


def test_grid_core_names_its_stages(lossy):
    cells = [(lossy.net, lossy.params, lossy.is_inter, lossy.lb)] * 2
    nets, params, inters, lb, churn, rel, fault = \
        sweeps.stack_scenarios(cells)
    compiled = sweeps._grid_core.lower(
        nets, params, inters, lb, churn, rel, np.arange(2, dtype=np.int32),
        fault, scheme="uno", n_warm=3, n_meas=2, backend="auto").compile()
    assert _scopes(compiled) == {"fleetsim.offered_load",
                                 "fleetsim.link_gathers", "fleetsim.cc"}


def test_host_phases_in_the_profiler_trace(lossy, tmp_path):
    svc = service.SweepService(cache_dir=tmp_path / "bundles")
    queries = [service.SweepQuery((lossy.net, lossy.params, lossy.is_inter,
                                   lossy.lb), n_warm=3, n_meas=2, seed=i)
               for i in range(3)]
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        svc.submit(queries)
        with jax.profiler.TraceAnnotation("test.simulate"):
            jax.block_until_ready(cc.simulate(lossy.net, lossy.params,
                                              n_epochs=3, rel=lossy.rel,
                                              fault=lossy.fault))
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events]
    names = [n for _, _, n in events if n.startswith("fleetsim.")]
    # the ladder cuts 3 queries into rungs 2 + 1: one plan, two batches
    assert names.count("fleetsim.plan") == 1
    for phase in ("stack", "wait", "unstack"):
        assert names.count(f"fleetsim.{phase}") == 2, phase
    [sim] = [e for e in events if e[2] == "test.simulate"]
    inside = [n for s, e, n in events
              if sim[0] <= s and e <= sim[1] and n.startswith("fleetsim.")]
    assert inside == ["fleetsim.dispatch"]
    assert names.count("fleetsim.dispatch") == 3


def test_compile_cache_keys_cover_the_scopes(monkeypatch, tmp_path):
    """A cache entry written by a revision without (or with other) scopes
    must not be read back for this one: its ops would carry that
    revision's `op_name`s into the profile."""
    from repro.compile_cache import use_compile_cache
    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update(flag, False)
        assert use_compile_cache() == str(tmp_path)
        assert getattr(jax.config, flag)
    finally:
        jax.config.update(flag, old)
