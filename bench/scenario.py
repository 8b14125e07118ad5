"""Scenario set-up shared by the entries: the configuration's builder
kwargs (with the traffic mix's conditions on top) decoded from JSON, the
spec built by `bench/builders/<builder>.py` (`build(**kwargs) ->
Scenario`, named by the configuration) and compiled to a FleetScenario
through the program's bundle cache, kept in the checkout at
`.scenario_cache`.
"""
from __future__ import annotations

import hashlib

from bench.harness import BENCH, ROOT, load_module

CACHE_DIR = ROOT / ".scenario_cache"


def decode(value):
    """JSON -> builder kwargs: {"RelSpec": {...}} becomes that spec type of
    `repro.scenarios`, lists become tuples."""
    from repro import scenarios
    if isinstance(value, dict):
        if len(value) == 1:
            (name, body), = value.items()
            cls = getattr(scenarios, name, None)
            if isinstance(cls, type) and hasattr(cls, "_fields"):
                return cls(**{k: decode(v) for k, v in body.items()})
        return {k: decode(v) for k, v in value.items()}
    if isinstance(value, list):
        return tuple(decode(v) for v in value)
    return value


def build_kwargs(cfg: dict, traffic: dict) -> dict:
    kw = dict(cfg["kwargs"])
    kw.update(traffic.get("scenario", {}))
    return {k: decode(v) for k, v in kw.items()}


def load(run):
    """The cell's FleetScenario, loaded from its bundle, or built and
    published for every later run.  The bundle's key covers the builder's
    source, its kwargs and the bundle format.  The load is the
    `bundle_load` span, and a build is counted in `bundle_builds`."""
    from repro.fleetsim import service
    from repro.scenarios import to_fleetsim
    from repro.scenarios.spec import fingerprint
    cfg, traffic = run.cell["config"], run.cell["traffic"]
    kw = build_kwargs(cfg, traffic)
    path = BENCH / "builders" / f"{cfg['builder']}.py"
    key = fingerprint({"builder": cfg["builder"], "kwargs": kw},
                      hashlib.sha256(path.read_bytes()).hexdigest(),
                      service.CACHE_VERSION)
    with run.span("bundle_load"):
        fs = service.load_bundle(service.bundle_path(key, CACHE_DIR))
        run.counters["bundle_builds"] = int(fs is None)
        if fs is None:
            fs = to_fleetsim(load_module("builders", cfg["builder"])
                             .build(**kw))
            service.publish_scenario(fs, key, CACHE_DIR)
    return fs
