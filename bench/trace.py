"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read.

What a TPU trace holds, as JAX 0.9 writes it: a plane per chip named
`/device:TPU:<n>` with the lines "XLA Modules" (one event per executable
run, named `jit_<function>(<fingerprint>)`) and "XLA Ops" (one event per
HLO op run, named by the op's HLO text `%name = <shape> <opcode>(...)`;
a `while` op's event spans its body's ops), and host planes whose threads
carry the benchmark's own `TraceAnnotation` spans (`bench.<name>`).  All
event times are nanoseconds on one clock.

`summarize` returns:
  window_s      the traced slice: first to last `bench.` host span;
  busy_s        union of the device's op intervals inside it, averaged
                over the chips traced;
  scan_s        summed run time of the scan modules (`jit_<name>` for each
                name in `scan_modules`);
  scan_ops      self time of every op that ran inside a scan module run,
                by its full HLO text, in seconds (averaged over chips);
  breakdown     the ten ops with most self time, and the ten longest idle
                gaps, each tagged with the innermost host span open over
                its middle ("none" where no span was open).
"""
from __future__ import annotations

import glob
import os
import re

_OPCODE = re.compile(r"%(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """The trace as plain lists: device op and module events per chip,
    host spans; each event a (start_ns, end_ns, name) tuple."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                out[key][plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
    return out


def op_label(text: str) -> str:
    """`%fusion.95 = f32[32768]{...} fusion(...), ...` -> the op up to
    its operands: `%fusion.95 = f32[32768]{...} fusion`."""
    m = _OPCODE.match(text)
    return text[:120] if m is None else \
        f"%{m.group(1)} = {m.group(2)} {m.group(3)}"


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(events) -> dict:
    """Self time (ns) by name of nested events on one line: an event's
    duration minus the part its children cover."""
    out: dict = {}
    stack: list = []          # [end, name, start, child_ns]

    def close(item):
        end, name, start, child = item
        out[name] = out.get(name, 0.0) + (end - start) - child

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][0]) - s
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def summarize(tr: dict, scan_modules, host_prefix: str = "bench.",
              top: int = 10) -> dict:
    spans = [h for h in tr["host"] if h[2].startswith(host_prefix)]
    devices = sorted(tr["ops"])
    if not spans or not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "scan_s": 0.0,
                "scan_ops": {}, "scan_op_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    want = tuple(f"jit_{m}(" for m in scan_modules)
    n_dev = len(devices)
    busy, scan_ns, gaps = [], 0.0, []
    scan_ops: dict = {}
    all_ops: dict = {}
    for dev in devices:
        ops = [o for o in tr["ops"][dev] if o[1] > lo and o[0] < hi]
        merged = union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        runs = [(s, e) for s, e, n in tr["modules"].get(dev, [])
                if n.startswith(want) and e > lo and s < hi]
        scan_ns += sum(e - s for s, e in runs)
        for name, ns in self_times(ops).items():
            label = op_label(name)
            all_ops[label] = all_ops.get(label, 0.0) + ns
        inside = [o for o in ops
                  if any(s <= o[0] and o[1] <= e for s, e in runs)]
        for name, ns in self_times(inside).items():
            scan_ops[name] = scan_ops.get(name, 0.0) + ns * 1e-9 / n_dev
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i + 1] - edges[i],
                             (edges[i] + edges[i + 1]) / 2))
    def host_at(t):
        open_ = [h for h in spans if h[0] <= t < h[1]]
        if not open_:
            return "none"
        inner = min(open_, key=lambda h: h[1] - h[0])
        return inner[2][len(host_prefix):]

    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "scan_s": scan_ns / n_dev * 1e-9,
        "scan_ops": scan_ops,
        "scan_op_s": sum(scan_ops.values()),
        "breakdown": {
            "device_ops": [[k, v / n_dev * 1e-9] for k, v in sorted(
                all_ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[host_at(t), g * 1e-9] for g, t in gaps[:top]]},
    }
