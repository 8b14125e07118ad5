"""Per-stage reduction of a profiler trace: the scan's device time by the
program's own `jax.named_scope` stages, the host time in its
`TraceAnnotation` phases, and the device's idle time while the host waits.

Where the stage of an op is written: the program opens one scope per
stage of an epoch (`fleetsim.offered_load`, `.link_gathers`, `.cc`,
`.reliability`, `.faults`), so each HLO op's `op_name` metadata holds the
scopes it was traced in, outermost first.  On a TPU trace, as JAX 0.9
writes it, an "XLA Ops" event's own stats carry only its device times;
the `op_name` is the `tf_op` stat of the event's metadata (with a
trailing `:`), which `jax.profiler.ProfileData` does not expose.
`op_names` reads it from the `.xplane.pb` itself.  A fusion's metadata
carries the `op_name` of its root.

The attribution rule: an op that ran inside a run of a scan module
belongs to the innermost `fleetsim.<stage>` segment of its `op_name`;
an op with no such segment (loop plumbing, copies, the per-call initial
state, the measured pass's accumulator) is `unscoped`.  Self times are
`bench.trace.self_times` over the same ops `bench.trace.summarize` counts,
so the stages plus `unscoped` add up to its `scan_op_s`.

`summarize` returns, over the traced slice (first to last `bench.` host
span, as `bench.trace.summarize` takes it):
  stage_s        device self time of the scan's ops by stage (averaged over
                 the chips traced), `unscoped` included;
  scoped         whether any op of the scan carried a stage scope;
  host_s         host seconds in the program's `fleetsim.plan`, `.stack`,
                 `.dispatch` and `.unstack` spans, None where none ran;
  wait_idle_s    device idle time during which the innermost open host
                 span (`bench.` or `fleetsim.`) is a wait (`fleetsim.wait`
                 or the harness's `bench.block`), an exact interval
                 intersection, averaged over the chips, None where no wait
                 span was open;
  window_s       the traced slice.

The harness does not call it yet (a traced run deletes its trace once
`bench.trace.summarize` has read it); on a kept trace:

    python3 -m bench.stages <trace.xplane.pb> <scan module>...

prints the reduction as one JSON line.
"""
from __future__ import annotations

import json
import sys

from bench import trace as tr

STAGES = ("offered_load", "link_gathers", "cc", "reliability", "faults")
HOST_PHASES = ("fleetsim.plan", "fleetsim.stack", "fleetsim.dispatch",
               "fleetsim.unstack")
WAITS = ("fleetsim.wait", "bench.block")
SCOPE = "fleetsim."


# ------------------------------------------------- the .xplane.pb, by hand

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varint
    fields, a memoryview for length-delimited ones; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_op_names(plane) -> tuple:
    """(name, {op text: (program_id, tf_op)}) of one XPlane.  XPlane:
    name = 2, event_metadata = 4, stat_metadata = 5 (maps: key 1, value
    2).  XEventMetadata: name = 2, stats = 5.  XStat: metadata_id = 1,
    uint64 = 3, int64 = 4, str = 5, ref = 7.  XStatMetadata: name = 2."""
    name, stat_names, events = "", {}, []
    for f, v in _fields(plane):
        if f == 2:
            name = _text(v)
        elif f in (4, 5):
            meta = next((val for k, val in _fields(v) if k == 2), b"")
            if f == 5:
                sid = sname = None
                for k, val in _fields(meta):
                    if k == 1:
                        sid = val
                    elif k == 2:
                        sname = _text(val)
                stat_names[sid] = sname
            else:
                ename, stats = "", []
                for k, val in _fields(meta):
                    if k == 2:
                        ename = _text(val)
                    elif k == 5:
                        stats.append(dict(_fields(val)))
                events.append((ename, stats))
    if not name.startswith("/device:"):
        return name, {}
    out = {}
    for ename, stats in events:
        program = op = None
        for st in stats:
            what = stat_names.get(st.get(1))
            if what == "program_id":
                program = st.get(3, st.get(4))
            elif what == "tf_op":
                op = _text(st[5]) if 5 in st else stat_names.get(st.get(7))
        if op is not None:
            out[ename] = (program, op.rstrip(":"))
    return name, out


def op_names(path: str) -> dict:
    """{device plane: {op text, as the trace names the op: (program id,
    op_name)}} from the `tf_op` stat of each op's event metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field == 1:                  # XSpace.planes
            name, ops = _plane_op_names(plane)
            if ops:
                out[name] = ops
    return out


# ---------------------------------------------------------------- reduction

def stage_of(op_name) -> str:
    """The innermost `fleetsim.<stage>` segment of an `op_name`, or
    `unscoped`."""
    for seg in reversed((op_name or "").split("/")):
        if seg.startswith(SCOPE):
            return seg[len(SCOPE):]
    return "unscoped"


def _program(module: str):
    """`jit__simulate(1262994364340521680)` -> 1262994364340521680."""
    try:
        return int(module[module.rindex("(") + 1:-1])
    except ValueError:
        return None


def _innermost_waits(spans, lo, hi) -> list:
    """Sorted disjoint intervals inside [lo, hi] in which the innermost
    open span (the latest started; the shorter on a tie) is a wait."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [h for h in spans if h[0] <= a and b <= h[1]]
        if not open_:
            continue
        inner = max(open_, key=lambda h: (h[0], h[0] - h[1]))
        if inner[2] in WAITS:
            if out and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b])
    return out


def _overlap(xs, ys) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def summarize(trace: dict, names: dict, scan_modules,
              host_prefix: str = "bench.") -> dict:
    """`trace` as `bench.trace.load` gives it, `names` as `op_names`."""
    spans = [h for h in trace["host"] if h[2].startswith(host_prefix)]
    devices = sorted(trace["ops"])
    if not spans or not devices:
        return {"stage_s": {}, "scoped": False, "host_s": None,
                "wait_idle_s": None, "window_s": 0.0}
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    named = [h for h in trace["host"]
             if h[2].startswith((host_prefix, SCOPE)) and h[1] > lo
             and h[0] < hi]
    waits = _innermost_waits(named, lo, hi)
    want = tuple(f"jit_{m}(" for m in scan_modules)
    n_dev = len(devices)
    stage_s: dict = {}
    scoped = False
    wait_idle = 0.0
    for dev in devices:
        runs = [(s, e, n) for s, e, n in trace["modules"].get(dev, [])
                if n.startswith(want) and e > lo and s < hi]
        programs = {_program(n) for _, _, n in runs}
        dev_names = names.get(dev, {})
        ops = [o for o in trace["ops"][dev] if o[1] > lo and o[0] < hi]
        inside = [o for o in ops
                  if any(s <= o[0] and o[1] <= e for s, e, _ in runs)]
        for name, ns in tr.self_times(inside).items():
            program, op = dev_names.get(name, (None, None))
            stage = stage_of(op if program in programs else None)
            scoped |= stage != "unscoped"
            stage_s[stage] = stage_s.get(stage, 0.0) + ns * 1e-9 / n_dev
        busy = tr.union([(max(s, lo), min(e, hi)) for s, e, _ in ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        wait_idle += _overlap(idle, waits) * 1e-9 / n_dev
    host = [e - s for s, e, n in named if n in HOST_PHASES]
    return {"stage_s": stage_s, "scoped": scoped,
            "host_s": sum(host) * 1e-9 if host else None,
            "wait_idle_s": wait_idle if waits else None,
            "window_s": (hi - lo) * 1e-9}


def module_op_names(trace: dict, names: dict, scan_modules) -> dict:
    """{op text: op_name} of the ops of the scan modules' programs, on
    every device traced (`trace` as `bench.trace.load` gives it, `names`
    as `op_names`)."""
    want = tuple(f"jit_{m}(" for m in scan_modules)
    out = {}
    for dev, ops in names.items():
        programs = {_program(n) for _, _, n in trace["modules"].get(dev, [])
                    if n.startswith(want)}
        out.update({text: op for text, (program, op) in ops.items()
                    if program in programs})
    return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) < 2:
        print("usage: python3 -m bench.stages <trace.xplane.pb> "
              "<scan module>...", file=sys.stderr)
        return 2
    path, scan = args[0], args[1:]
    print(json.dumps(summarize(tr.load(path), op_names(path), scan)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
