"""How `correct` is decided: a sample of the window's answers, each
recomputed by `bench/reference.py` from the same inputs, and compared.

An answer is what one timed call produced for one query: a what-if's mean
goodput per flow over its measured epochs, or one chunk of a chained
steady-state query (its mean goodput per flow over the chunk, and the
state it hands to the next call).  A cell compares the numbers its
`bench/limits/<cell>.json` lists, each against its limit there (the file
also keeps the two readings it was set from):

  goodput_median_err  the median over flows of the goodput's relative gap;
  goodput_p99_err     the 99th percentile over flows of that gap;
  goodput_max_err     the largest over flows of that gap;
  cwnd_p75_err        (chunks) the 75th percentile over flows of the
                      relative gap of the cwnd handed to the next call;
  init_off_share      (chained queries) the share of the program's start
                      state that is off the reference's own start state
                      from the same seeded draw: a float entry whose
                      relative gap exceeds OFF_TOL, or an integer entry
                      that differs at all.

Each is the worst over the sampled answers.  A relative gap is |program -
reference| / (|reference| + 1e-6 * the field's largest finite
|reference|); equal entries (infinities too) have none, and any other
non-finite pair an infinite one.

After hundreds of epochs the controller's state differs from the
reference's in a few flows by the order in which float32 sums round (its
oscillation's phase moves), so the cwnd is compared by a quantile that
such flows do not reach.  Under loss the same rounding can move the epoch
in which one flow's pending loss reaches the NACK quantum; the
multiplicative decrease that follows changes that flow's goodput over the
rest of the chunk by up to tens of percent, so a cell with loss compares
the 99th percentile of the goodput gap, not its largest.
"""
from __future__ import annotations

import numpy as np

OFF_TOL = 1e-3


def _fields(fam):
    return {f: (None if getattr(fam, f) is None else np.asarray(
        getattr(fam, f))) for f in fam._fields}


def scenario_inputs(fs, net=None) -> dict:
    """The plain arrays of a compiled scenario (with `net` in place of its
    own links, for a what-if) that the reference reads: links, raw routes,
    per-flow constants, EC geometry, fault events.  No layout, path table
    or coefficient table goes across."""
    net = fs.net if net is None else net
    netd = {f: None if getattr(net, f) is None else np.asarray(
        getattr(net, f)) for f in ("cap", "qcap", "ecn_lo", "ecn_hi",
                                   "drain", "vcap", "use_phantom",
                                   "p_loss")}
    rel = None
    if fs.rel is not None:
        rel = {f: v for f, v in _fields(fs.rel).items()
               if f not in ("coef", "ladder_coef")}
    return {"routes": np.asarray(net.routes), "dt": float(net.dt),
            "is_inter": np.asarray(fs.is_inter), "net": netd,
            "params": _fields(fs.params),
            "lb": None if fs.lb is None else _fields(fs.lb),
            "rel": rel,
            "fault": None if fs.fault is None else _fields(fs.fault)}


def ref_state(st) -> dict:
    """A program FleetState as the reference's state dict: per-subflow
    fields transposed to (paths, flows), families as dicts."""
    out = {}
    for f in st._fields:
        v = getattr(st, f)
        if v is None:
            continue
        if f in ("rel", "fault"):
            out[f] = {g: getattr(v, g) for g in v._fields}
        elif f in ("split", "path_frac", "bad_count"):
            out[f] = v.T
        else:
            out[f] = v
    return out


def _gap(p, r) -> np.ndarray:
    p = np.asarray(p, np.float64).ravel()
    r = np.asarray(r, np.float64).ravel()
    fin = np.isfinite(r)
    scale = np.abs(r) + 1e-6 * (np.abs(r[fin]).max() if fin.any() else 0.0)
    with np.errstate(invalid="ignore"):
        g = np.abs(p - r) / np.maximum(scale, 1e-30)
    return np.where(p == r, 0.0, np.where(np.isfinite(g), g, np.inf))


def _leaves(tree: dict, prefix: str = ""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}{k}.")
        elif v is not None:
            yield prefix + k, _host(v)


def _host(v) -> np.ndarray:
    import jax
    if hasattr(v, "dtype") and jax.dtypes.issubdtype(v.dtype,
                                                     jax.dtypes.prng_key):
        v = jax.random.key_data(v)
    return np.asarray(v)


def off_share(prog: dict, ref: dict) -> float:
    """Share of the entries of two state dicts that are off; a field that
    one side lacks, or whose shape differs, is off in every entry."""
    p, r = dict(_leaves(prog)), dict(_leaves(ref))
    off, total = 0, 0
    for name in sorted(set(p) | set(r)):
        a, b = p.get(name), r.get(name)
        n = max(np.size(a), np.size(b))
        total += n
        if a is None or b is None or a.shape != b.shape:
            off += n
        elif b.dtype.kind == "f":
            off += int(np.sum(_gap(a, b) > OFF_TOL))
        else:
            off += int(np.sum(a.ravel() != b.ravel()))
    return off / max(total, 1)


def compare(prog: dict, ref: dict) -> dict:
    """Numbers of one answer: `prog`/`ref` hold "goodput", and a chunk's
    also the "cwnd" it handed on."""
    g = _gap(prog["goodput"], ref["goodput"])
    out = {"goodput_median_err": float(np.median(g)),
           "goodput_p99_err": float(np.quantile(g, 0.99)),
           "goodput_max_err": float(np.max(g))}
    if "cwnd" in ref:
        out["cwnd_p75_err"] = float(np.quantile(
            _gap(prog["cwnd"], ref["cwnd"]), 0.75))
    return out


def combine(per_answer: list) -> dict:
    """The run's numbers: the worst over all sampled answers."""
    keys = {k for a in per_answer for k in a}
    return {k: max(a[k] for a in per_answer if k in a) for k in keys}


def with_limits(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number the limits file lists."""
    return {k: {"value": numbers[k], "limit": v["limit"]}
            for k, v in limits.items()}


def reference_answer(item: dict, dtype=None) -> dict:
    """The reference's answer to one sampled query, in float32, or in
    `dtype` (the control) with the result read back as float32."""
    import jax.numpy as jnp

    from bench import reference as ref
    inp, st0 = item["inp"], item["state0"]
    if dtype is not None:
        inp, st0 = ref.cast(inp, dtype), ref.cast(st0, dtype)
    st, good = ref.run(inp, st0, item["n_epochs"], item["n_meas"])
    out = {"goodput": np.asarray(good.astype(jnp.float32))}
    if "cwnd" in item["prog"]:
        out["cwnd"] = np.asarray(st["cwnd"].astype(jnp.float32))
    return out


def run_check(run, items: list, init=None, control: bool = False) -> dict:
    """Compare every sampled answer with the reference's; `init` is the
    (program, reference) start state of a chained query.  `control` puts
    the bfloat16 reference in the program's place."""
    import jax.numpy as jnp

    from bench import reference as ref
    per = []
    for it in items:
        want = reference_answer(it)
        got = reference_answer(it, jnp.bfloat16) if control else it["prog"]
        per.append(compare(got, want))
    if init is not None:
        prog0, ref0 = init
        if control:
            prog0 = ref.cast(ref.cast(ref0, jnp.bfloat16), jnp.float32)
        per.append({"init_off_share": off_share(prog0, ref0)})
    limits = run.cell["limits"]
    run.counters["answers_off"] = sum(
        any(a[k] > v["limit"] for k, v in limits.items() if k in a)
        for a in per)
    return with_limits(combine(per), limits)
