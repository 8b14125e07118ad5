"""On-chip smoke test: drive fleetsim's main paths and the cross-pod wire
format once on a TPU at the sizes their users run, check the results, and
print one JSON line per phase plus a final status line.

  python chip_smoke.py             # one chip: phases 1-3
  python chip_smoke.py --chips 4   # a four-chip host: the sharded paths and
                                   # the Uno cross-pod step, each against
                                   # its single-chip / plain-psum reference

One chip:
  1. service — the fat_tree_k8 scenario (100k flows, 8 ECMP paths) built
     fresh through `SweepService.scenario`, one `SweepQuery` cold then warm
     (the warm pass must trace nothing), a batch of 4 drain-fraction
     what-ifs in one `submit`, and the `auto` backend's link loads against
     the reference scatter evaluated in float64.
  2. scan — a 1M-flow multipath dumbbell through `fleetsim.steady_state`.
  3. pallas — the cross-pod wire format (int8 quant -> RS(8,2) encode ->
     drop two rows -> RS decode -> dequant) with compiled kernels, and the
     Pallas link-aggregation backends against the float64 reference.

Four chips (`--chips 4`):
  * the Uno cross-pod grad-sync step on a (pod=2, data=2) mesh: one
    train step of smollm-135m at its published widths (depth cut to
    UNO_LAYERS), 8 sequences of 2,048 tokens, against the plain psum step;
  * the 3-DC ring sharded DC-major, one DC per chip (3 of the 4 chips);
  * fat_tree_k8 at 100k flows, locality-sharded over 4 chips.

Exits nonzero when the first device is not a TPU (there is no CPU
fallback) and when any check fails.  Timings in the phase lines are
bring-up observations of one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.fleetsim import links as fl  # noqa: E402

# normalized link-load bound of `fleetsim_sweep --check-equivalence`
LOAD_TOL = 1e-6
FAT_TREE_PATHS = 8
UNO_LAYERS = 8              # depth of the Uno step's smollm-135m (of 30)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_cache_counts = {"hits": 0, "misses": 0}


class SmokeError(RuntimeError):
    """A phase produced a wrong or non-finite result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def _on_event(event: str, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _cache_counts[_CACHE_EVENTS[event]] += 1


def _cache_delta(before: dict) -> dict:
    return {k: _cache_counts[k] - before[k] for k in _cache_counts}


def _peak_bytes(devices=None) -> list:
    """peak_bytes_in_use per device (None where the backend has no stats)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _finite_positive(rates) -> bool:
    r = np.asarray(rates)
    return bool(np.isfinite(r).all() and (r > 0).all())


def link_load_errors(net, backends, seed: int = 0) -> dict:
    """Max |load - truth| / max(1, max |truth|) of each backend's offered
    load, where truth is the reference scatter evaluated in float64 on the
    host, for seeded random rates and splits."""
    n, p = net.routes.shape[:2]
    rng = np.random.default_rng(seed)
    rates = jnp.asarray(rng.uniform(0.1, 2.0, n), jnp.float32)
    split = fl.normalize_split(
        jnp.asarray(rng.uniform(0.0, 1.0, (n, p)), jnp.float32),
        fl.path_mask(net))
    routes = np.asarray(net.routes)
    if routes.ndim == 2:
        routes = routes[:, None, :]
    sub = np.asarray(rates, np.float64)[:, None] * np.asarray(split,
                                                              np.float64)
    n_l = int(net.n_links)
    truth = np.zeros(n_l + 1)
    np.add.at(truth, np.where(routes >= 0, routes, n_l).ravel(),
              np.repeat(sub.ravel(), routes.shape[2]))
    truth = truth[:n_l]
    scale = max(1.0, float(np.abs(truth).max()))
    out = {}
    for be in backends:
        got = np.asarray(fl.offered_load(net, rates, split, backend=be))
        out[be] = float(np.abs(got - truth).max()) / scale
    return out


# ------------------------------------------------------------- one chip

def phase_service(cache_dir, *, k: int = 8, n_flows: int = 100_000,
                  n_warm: int = 90, n_meas: int = 10,
                  drains=(0.80, 0.85, 0.90, 0.95)):
    """Phase 1; returns (record, FleetScenario)."""
    from benchmarks.sweep_server import drain_whatifs
    from repro.fleetsim import service, sweeps
    svc = service.SweepService(cache_dir=cache_dir)
    fs, build_s = _timed(lambda: svc.scenario(
        "fat_tree", refresh=True, k=k, n_wan=k, n_flows=n_flows,
        n_paths=FAT_TREE_PATHS, seed=1))
    pt = fs.net.layout.path_table if fs.net.layout is not None else None
    q = service.SweepQuery(fs, n_warm=n_warm, n_meas=n_meas,
                           backend="auto")
    cache0 = dict(_cache_counts)
    tr0 = sweeps.grid_traces()
    [(_, rates)], cold_s = _timed(lambda: svc.submit([q]))
    cold_traces = sweeps.grid_traces() - tr0
    [(_, rates_w)], warm_s = _timed(lambda: svc.submit([q]))
    warm_traces = sweeps.grid_traces() - tr0 - cold_traces
    batch = [service.SweepQuery(c, n_warm=n_warm, n_meas=n_meas,
                                backend="auto")
             for c in drain_whatifs(fs, drains)]
    tr1 = sweeps.grid_traces()
    results, batch_s = _timed(lambda: svc.submit(batch))
    batch_traces = sweeps.grid_traces() - tr1
    errs = link_load_errors(fs.net, ("auto", "reference"))
    epochs = n_warm + n_meas
    rec = {"phase": "service", "scenario": "fat_tree", "k": k,
           "n_flows": n_flows, "n_paths": FAT_TREE_PATHS,
           "n_links": int(fs.net.n_links),
           "n_unique_paths": None if pt is None else int(pt.n_segments),
           "n_epochs": epochs, "spec_build_s": build_s,
           "cold_s": cold_s, "warm_s": warm_s,
           "compile_s": max(cold_s - warm_s, 0.0),
           "flow_epochs_per_s": n_flows * epochs / warm_s,
           "cold_traces": cold_traces, "warm_traces": warm_traces,
           "batch": {"n_queries": len(batch), "s": batch_s,
                     "traces": batch_traces},
           "load_err_vs_f64": errs, "compile_cache": _cache_delta(cache0),
           "peak_bytes": _peak_bytes()}
    _check(_finite_positive(rates) and _finite_positive(rates_w),
           "service query rates not finite and positive")
    _check(all(_finite_positive(r) for _, r in results),
           "what-if batch rates not finite and positive")
    _check(warm_traces == 0, f"warm query traced {warm_traces}x")
    _check(batch_traces <= 1, f"4-query batch traced {batch_traces}x")
    _check(errs["auto"] <= LOAD_TOL,
           f"auto link loads off by {errs['auto']:.2e} normalized")
    return rec, fs


def phase_scan(*, n_flows: int = 1_000_000, n_warm: int = 150,
               n_meas: int = 50):
    """Phase 2: a multipath dumbbell through `fleetsim.steady_state`."""
    from repro.fleetsim import steady_state
    from repro.scenarios import dumbbell_scenario, to_fleetsim
    fs, build_s = _timed(lambda: to_fleetsim(dumbbell_scenario(
        n_flows // 2, n_flows - n_flows // 2, multipath=True, n_wan=4,
        n_bottleneck=max(1, n_flows // 64))))

    def run():
        return steady_state(fs.net, fs.params, n_warm=n_warm,
                            n_meas=n_meas, is_inter=fs.is_inter, lb=fs.lb)[1]
    cache0 = dict(_cache_counts)
    _, cold_s = _timed(run)
    rates, warm_s = _timed(run)
    epochs = n_warm + n_meas
    rec = {"phase": "scan", "scenario": "dumbbell_multipath",
           "n_flows": n_flows, "n_paths": 4, "n_epochs": epochs,
           "spec_build_s": build_s, "cold_s": cold_s, "warm_s": warm_s,
           "compile_s": max(cold_s - warm_s, 0.0),
           "flow_epochs_per_s": n_flows * epochs / warm_s,
           "compile_cache": _cache_delta(cache0),
           "peak_bytes": _peak_bytes()}
    _check(bool(np.isfinite(np.asarray(rates)).all()),
           "1M-flow scan rates not finite")
    return rec


def phase_pallas(net, *, n: int = 1 << 22, r: int = 2, k: int = 8):
    """Phase 3: the cross-pod wire format and the Pallas link backends."""
    from repro.kernels import interpret_mode, ops
    x = jnp.asarray(np.random.default_rng(0).normal(size=n), jnp.float32)
    lost = tuple(range(r))

    def wire(x):
        q, s, n0 = ops.quant_int8(x)
        rows = jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(k, -1)
        parity = ops.rs_encode(rows, r)
        survivors = jnp.concatenate([rows[r:], parity], axis=0)
        rebuilt = ops.rs_decode(survivors, k, r, lost, lost)
        full = jnp.concatenate([rebuilt, rows[r:]], axis=0)
        back = jax.lax.bitcast_convert_type(full.reshape(-1), jnp.int8)
        return rows, rebuilt, s, ops.dequant_int8(back, s, n0)

    wire_jit = jax.jit(wire)
    hlo = wire_jit.lower(x).as_text()
    cache0 = dict(_cache_counts)
    _, cold_s = _timed(lambda: wire_jit(x))
    (rows, rebuilt, s, out), warm_s = _timed(lambda: wire_jit(x))
    scale_rep = np.repeat(np.asarray(s), ops.QUANT_BLOCK)[:n]
    err = np.abs(np.asarray(out) - np.asarray(x))
    errs = link_load_errors(net, ("pt_pallas", "pallas"))
    rec = {"phase": "pallas", "payload_f32": n, "rs": [k, r],
           "interpret": interpret_mode(),
           "tpu_custom_calls": hlo.count("tpu_custom_call"),
           "cold_s": cold_s, "warm_s": warm_s,
           "max_err_over_half_step": float(
               (err / (0.5 * scale_rep + 1e-6)).max()),
           "load_err_vs_f64": errs, "compile_cache": _cache_delta(cache0),
           "peak_bytes": _peak_bytes()}
    _check(bool(np.array_equal(np.asarray(rebuilt),
                               np.asarray(rows[:r]))),
           "RS decode did not rebuild the dropped rows")
    _check(bool((err <= 0.5 * scale_rep + 1e-6).all()),
           "quant round trip outside half a quant step")
    for be, e in errs.items():
        _check(e <= LOAD_TOL, f"{be} link loads off by {e:.2e} normalized")
    return rec


# ----------------------------------------------------------- four chips

def _sharded_vs_single(fs, devices, *, n_warm: int, n_meas: int,
                       dc: bool = False) -> dict:
    """Sharded steady state over `devices` against the unsharded run on
    devices[0], held to the fat-tree sharding test's rule: within
    max(1e-4 * max(1, scale), 4 * noise), noise being what a
    single-device backend swap (reference scatter) moves the rates."""
    from repro.fleetsim import steady_state
    from repro.fleetsim.shard import (flow_mesh, shard_scenario,
                                      steady_state_prepared)
    kw = dict(n_warm=n_warm, n_meas=n_meas, is_inter=fs.is_inter, lb=fs.lb)
    with jax.default_device(devices[0]):
        _, r1 = steady_state(fs.net, fs.params, **kw)
        _, r1b = steady_state(fs.net, fs.params, backend="reference", **kw)
    dc_kw = dict(link_dc=fs.link_dc, exchange="auto", seed=fs.seed) \
        if dc else {}
    sf, plan_s = _timed(lambda: shard_scenario(
        fs.net._replace(layout=None), fs.params, is_inter=fs.is_inter,
        lb=fs.lb, mesh=flow_mesh(devices=devices), link_tier=fs.link_tier,
        **dc_kw))

    def sharded():
        return steady_state_prepared(sf, n_warm=n_warm, n_meas=n_meas)[1]
    _, cold_s = _timed(sharded)
    r2, warm_s = _timed(sharded)
    r1, r1b, r2 = (np.asarray(a) for a in (r1, r1b, r2))
    err = float(np.abs(r1 - r2).max())
    noise = float(np.abs(r1 - r1b).max())
    scale = float(np.abs(r1).max())
    tol = max(1e-4 * max(1.0, scale), 4.0 * noise)
    rec = {"n_flows": int(fs.params.bdp.shape[0]),
           "n_epochs": n_warm + n_meas, "n_devices": len(devices),
           "n_boundary": int(sf.plan.n_boundary),
           "exchange": "psum" if sf.nbr is None else "nbr",
           "err": err, "noise": noise, "tol": tol, "shard_plan_s": plan_s,
           "cold_s": cold_s, "warm_s": warm_s,
           "peak_bytes": _peak_bytes(jax.devices())}
    _check(bool(np.isfinite(r2).all()), "sharded rates not finite")
    _check(err < tol, f"sharded vs single-chip rates off by {err:.3e} "
                      f"(tol {tol:.3e})")
    return rec


def phase_multi_dc(devices, cache_dir, *, n_flows: int = 60_000,
                   n_warm: int = 90, n_meas: int = 10):
    """The 3-DC k=4 ring, one datacenter per device."""
    from repro.fleetsim import service
    fs = service.SweepService(cache_dir=cache_dir).scenario(
        "multi_dc", k=4, n_dc=3, mesh="ring", oversub=1.0,
        n_flows=n_flows, n_paths=4, seed=1)
    rec = _sharded_vs_single(fs, devices[:3], n_warm=n_warm, n_meas=n_meas,
                             dc=True)
    return {"phase": "multi_dc", "k": 4, "n_dc": 3, "mesh": "ring", **rec}


def phase_fat_tree_sharded(devices, cache_dir, *, k: int = 8,
                           n_flows: int = 100_000, n_warm: int = 90,
                           n_meas: int = 10):
    """fat_tree_k8 locality-sharded over every device."""
    from repro.fleetsim import service
    fs = service.SweepService(cache_dir=cache_dir).scenario(
        "fat_tree", k=k, n_wan=k, n_flows=n_flows, n_paths=FAT_TREE_PATHS,
        seed=1)
    rec = _sharded_vs_single(fs, devices, n_warm=n_warm, n_meas=n_meas)
    return {"phase": "fat_tree_sharded", "k": k, **rec}


def uno_config(n_layers: int = UNO_LAYERS):
    """smollm-135m at its published widths (d_model 576, 9 heads / 3 KV,
    d_ff 1536, vocab 49152), depth cut to `n_layers`."""
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config("smollm-135m"), n_layers=n_layers)


def phase_uno_step(devices, cfg=None, *, batch: int = 8, seq: int = 2048):
    """One Uno cross-pod grad-sync train step on (pod=2, data=n/2) against
    the plain psum step, held to the bounds of
    test_uno_sync_matches_psum_2pods.  `cfg` defaults to `uno_config()`;
    `batch` sequences of `seq` tokens split over the mesh."""
    from repro import sharding, train
    from repro.configs.base import RunConfig
    from repro.core.uno_collectives import make_uno_grad_sync
    cfg = cfg or uno_config()
    mesh = sharding.make_mesh((2, len(devices) // 2), ("pod", "data"),
                              devices=devices)
    run = RunConfig(uno_chunks=4)
    with sharding.use_mesh(mesh):
        state = train.make_train_state(cfg, jax.random.PRNGKey(0))
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        data = {"inputs": jax.random.randint(ks[0], (batch, seq), 0,
                                             cfg.vocab),
                "targets": jax.random.randint(ks[1], (batch, seq), 0,
                                              cfg.vocab)}
        base = jax.jit(train.make_train_step(cfg, run))
        uno = jax.jit(train.make_train_step(
            cfg, run, uno_sync=make_uno_grad_sync(mesh, cfg, run),
            mesh=mesh))
        (s1, m1), base_s = _timed(lambda: base(state, data, jnp.int32(1)))
        (s2, m2), uno_s = _timed(lambda: uno(state, data, jnp.int32(1)))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1["params"], s2["params"])
    delta = max(jax.tree.leaves(d))
    n_params = sum(x.size for x in jax.tree.leaves(s1["params"]))
    loss_base, loss_uno = float(m1["loss"]), float(m2["loss"])
    rec = {"phase": "uno_step", "model": cfg.name, "n_layers": cfg.n_layers,
           "d_model": cfg.d_model, "vocab": cfg.vocab, "n_params": n_params,
           "batch": batch, "seq": seq,
           "mesh": {"pod": 2, "data": len(devices) // 2},
           "max_param_delta": delta, "loss_base": loss_base,
           "loss_uno": loss_uno, "base_cold_s": base_s,
           "uno_cold_s": uno_s, "peak_bytes": _peak_bytes(devices)}
    _check(bool(np.isfinite([loss_base, loss_uno]).all()),
           "Uno step losses not finite")
    _check(delta < 5e-4, f"Uno vs psum params differ by {delta:.3e}")
    _check(abs(loss_base - loss_uno) < 1e-2,
           f"Uno vs psum loss {loss_uno} vs {loss_base}")
    return rec


# ------------------------------------------------------------------ main

def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: service, scan and Pallas phases; 4: only the "
                         "multi-chip paths and their references")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "nothing ran", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.monitoring.register_event_listener(_on_event)
    cache_dir = ROOT / ".scenario_cache"
    try:
        if args.chips == 4:
            devices = devices[:4]
            _emit(phase_uno_step(devices))
            _emit(phase_multi_dc(devices, cache_dir))
            _emit(phase_fat_tree_sharded(devices, cache_dir))
        else:
            rec, fs = phase_service(cache_dir)
            _emit(rec)
            _emit(phase_scan())
            _emit(phase_pallas(fs.net))
    except SmokeError as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
