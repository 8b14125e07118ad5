"""The benchmark harness: one run of one cell, driven by data.

A cell (`BENCHMARK.json` -> `workloads`) names a configuration and a
traffic mix; the harness finds each by name:

  bench/configs/<config>.json    the deployment: scenario builder + kwargs
  bench/traffic/<traffic>.json   the mix: which entry drives the system
                                 (bench/entries/<entry>.py) and its knobs
  bench/limits/<cell>.json       the limit of every number `correct` checks
  bench/metrics/<metric>.py      one reader per metric, `read(run)`

A run: set-up (scenario bundle, compile cache, one warm-up call at the
cell's own shapes), a measured window of `seconds`, the correctness check
of a sample of the window's answers against `bench/reference.py`, then the
metrics the cell reports.  With `trace` the window also records a device
trace of a few calls, and the per-layer metrics read it.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

COMPILE_EVENTS = ("/jax/compilation_cache/cache_misses",
                  "/jax/compilation_cache/cache_hits")


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py, imported by path (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> dict:
    """Everything one cell needs, from BENCHMARK.json and its files."""
    bj = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"workload": workload, "chips": int(w["chips"]),
            "config": load_json(BENCH / "configs" / f"{w['config']}.json"),
            "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json"),
            "end_to_end": [m for m in bj["end_to_end"] if listed(m)],
            "per_layer": [m for m in bj["per_layer"] if listed(m)]}


class Run:
    """What one run knows: inputs, host spans and counters, the window's
    calls, the reduced trace.  Metric readers take it as their argument."""

    def __init__(self, cell: dict, seed: int, seconds: float):
        self.cell, self.seed, self.seconds = cell, int(seed), seconds
        self.spans: dict = {}           # name -> host seconds
        self.counters: dict = {}        # name -> count
        self.calls: list = []           # (t_issue, t_done, flow_epochs, epochs)
        self.setup_s = None
        self.trace_summary = None       # bench/trace.py reduction
        self.traced_calls: list = []    # the calls inside the traced slice
        self.compiles = 0               # compile-cache events so far

    def on_event(self, event: str, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, run: Run, name: str):
        self.run, self.name = run, name

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(f"bench.{self.name}")
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.run.spans[self.name] = self.run.spans.get(self.name, 0.0) + \
            time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        return False


class Profiler:
    """Starts and stops the device trace around a slice of the window."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no per-call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> None:
        from bench import trace as tr
        from bench import stages
        modules = self.run.cell["traffic"]["scan_modules"]
        try:
            path = tr.find_xplane(self.dir)
            loaded = tr.load(path)
            self.run.trace_summary = tr.summarize(loaded, modules)
            self.run.trace_summary["op_names"] = stages.module_op_names(
                loaded, stages.op_names(path), modules)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def measure(run: Run, prof, call, sample_k: int) -> list:
    """The measured window: `call()` -> (t_issue, t_done, flow_epochs,
    epochs, answer) until `run.seconds` have passed; every call counts.
    With `prof`, calls 1 .. `trace_calls` are traced.  Returns a
    reservoir sample of `sample_k` answers, drawn from the seed."""
    n_trace = run.cell["traffic"]["trace_calls"]
    pick = np.random.default_rng([run.seed, 1])
    sample: list = []
    compiles0 = run.compiles
    t_end = time.perf_counter() + run.seconds
    i = 0
    while True:
        if prof is not None and i == 1:
            prof.start()
        t0, t1, work, epochs, answer = call()
        run.calls.append((t0, t1, work, epochs))
        if prof is not None and prof.t0 is not None and prof.t1 is None:
            run.traced_calls.append((t0, t1, work, epochs))
            if len(run.traced_calls) >= n_trace:
                prof.stop()
        if len(sample) < sample_k:
            sample.append(answer)
        else:
            j = pick.integers(0, i + 1)
            if j < sample_k:
                sample[j] = answer
        del answer
        i += 1
        if t1 >= t_end and (prof is None or prof.t1 is not None):
            break
    run.counters["window_compiles"] = run.compiles - compiles0
    return sample


def use_compile_cache() -> None:
    """The program's persistent compile cache (`repro.compile_cache`: one
    fixed path in the checkout, or JAX_COMPILATION_CACHE_DIR), made to
    keep every program, however small or quick to compile."""
    import jax

    from repro.compile_cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    known = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(known) if known else None}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float) -> dict:
    """One run of one cell; returns the result object the CLI prints."""
    import jax
    t_cell = time.perf_counter()
    use_compile_cache()
    run = Run(cell, seed, seconds)
    jax.monitoring.register_event_listener(run.on_event)
    entry = load_module("entries", cell["traffic"]["entry"])
    state = entry.setup(run)
    run.setup_s = time.perf_counter() - t_start
    parts = "".join(f", {k} {v:.3f}" for k, v in run.spans.items())
    print(f"bench: set-up {run.setup_s:.3f} s: imports and devices "
          f"{t_cell - t_start:.3f}{parts}", file=sys.stderr)
    prof = Profiler(run) if trace else None
    entry.window(run, state, prof)
    device = device_info(cell["chips"])
    if prof is not None:
        prof.reduce()
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
    t_check = time.perf_counter()
    checks = entry.check(run, state)
    run.spans["check"] = time.perf_counter() - t_check
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": len(run.calls),
           "failed": run.counters["answers_off"], "metrics": metrics,
           "device": device}
    if trace and run.trace_summary is not None:
        out["breakdown"] = run.trace_summary["breakdown"]
    out["check_s"] = run.spans["check"]
    out["checks"] = checks
    return out


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
