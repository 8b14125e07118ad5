"""How `correct` is decided in a training cell: the reference follows the
step's first `check_steps` steps from the same weights and batches
(`bench/train_reference.py`, float32 at `highest`), and four numbers
compare what the program produced with what the reference computes:

  loss_err         the largest over the steps of the loss's relative gap;
  grad_norm_err    the first step's gradient, as the optimizer got it
                   (read back from its first moment after one step,
                   m1 / (1 - b1), since m0 = 0), by the worst leaf: the
                   gap between the program's norm of that leaf and the
                   reference's, over the larger of the reference's norm
                   of the leaf and of the median leaf;
  change_norm_err  the same for the weights' change over the steps,
                   ||w_n - w_0|| per leaf;
  grad_l2_err      ||g_program - g_reference|| / ||g_reference|| over the
                   whole first gradient.

A leaf counts only where the reference's gradient norm is at least a
thousandth of the median leaf's (none of this model's is smaller; the
rule keeps a leaf that only round-off moves out of the norms).  A cell's
`bench/limits/<cell>.json` lists the numbers compared and their limits.
"""
from __future__ import annotations

import numpy as np

from bench import train_reference as ref

MIN_SHARE = 1e-3


def host_leaves(tree) -> dict:
    """{leaf path: float32 numpy array} of a parameter-shaped tree."""
    import jax
    return {p: np.asarray(jax.device_get(ref.leaf(tree, p)), np.float32)
            for p in ref.LEAVES}


def change_norms(new, old) -> dict:
    """{leaf path: ||new - old||} of two parameter trees, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {"/".join(p): jnp.linalg.norm(
            (ref.leaf(a, p).astype(jnp.float32) -
             ref.leaf(b, p).astype(jnp.float32)).ravel())
            for p in ref.LEAVES}
    out = jax.device_get(norms(new, old))
    return {p: float(out["/".join(p)]) for p in ref.LEAVES}


def program_summary(first_m: dict, change: dict, losses, b1: float) -> dict:
    return {"losses": list(losses),
            "grad": {p: v / np.float32(1 - b1) for p, v in first_m.items()},
            "change": change}


def _train(cell, params, batches, dot, rows=None, **opt_change):
    m, tr = cell["config"]["model"], cell["traffic"]
    opt = dict(cell["config"]["optimizer"], **opt_change)
    out = ref.train(params, batches, tr["step0"], m, opt, dot, rows=rows)
    return {"losses": out["losses"],
            "grad": {p: np.asarray(ref.leaf(out["grad"], p), np.float32)
                     for p in ref.LEAVES},
            "change": change_norms(out["params"], params)}


def seeded(cell: dict, seed: int):
    """The weights and the first `check_steps` token batches of a seed,
    made as the entry makes them, on the default device."""
    from bench.entries import train_step
    tr, m = cell["traffic"], cell["config"]["model"]
    k_weights, k_tokens = train_step.seed_keys(seed)
    params = train_step.make_weights(k_weights, m)["params"]
    tokens = ref.token_pool(k_tokens, tr["pool"], tr["rows"], tr["seq"],
                            m["vocab"])[:tr["check_steps"]]
    return params, tokens


def reference_summary(cell: dict, seed: int, dot=ref.f32_dot,
                      plant: str | None = None) -> dict:
    """What the reference computes for the seed's first steps; `dot` and
    `plant` make the control and the faults that are run in the
    reference's place:

      "half_batch"  every step on the first half of its rows only;
      "pod_local"   the exchange left out: each pod trains a copy on its
                    own rows, the reported loss is the mean of the pods'
                    and the state the first pod's;
      "lr_low"      a wrong update: the learning rate 10% low;
      "no_decay"    a wrong update: the weight decay left out.
    """
    params, tokens = seeded(cell, seed)
    rows = cell["traffic"]["rows"]
    if plant == "half_batch":
        return _train(cell, params, tokens, dot, slice(0, rows // 2))
    if plant == "pod_local":
        pods = cell["traffic"]["mesh"]["pod"]
        per = rows // pods
        runs = [_train(cell, params, tokens, dot,
                       slice(i * per, (i + 1) * per)) for i in range(pods)]
        return dict(runs[0], losses=list(np.mean(
            [r["losses"] for r in runs], axis=0)))
    opt = cell["config"]["optimizer"]
    if plant == "lr_low":
        return _train(cell, params, tokens, dot,
                      learning_rate=0.9 * opt["learning_rate"])
    if plant == "no_decay":
        return _train(cell, params, tokens, dot, weight_decay=0.0)
    return _train(cell, params, tokens, dot)


def altered(want: dict) -> dict:
    """The fault of an answer altered where it is produced: each step's
    loss reported 1% high."""
    return dict(want, losses=[x * 1.01 for x in want["losses"]])


def stale(want: dict) -> dict:
    """The fault of a step that hands its state back unchanged: the first
    moment and the weights' change stay 0."""
    return dict(want, grad={p: np.zeros_like(g)
                            for p, g in want["grad"].items()},
                change={p: 0.0 for p in want["change"]})


def _leaf_gaps(got: dict, want: dict, keep) -> dict:
    med = float(np.median([want[p] for p in keep]))
    return {p: abs(got[p] - want[p]) / max(want[p], med) for p in keep}


def _norm_gap(got: dict, want: dict, keep) -> float:
    return max(_leaf_gaps(got, want, keep).values())


def _norm(a) -> float:
    """The L2 norm of an array, summed in float64: float32 sums over a
    leaf of tens of millions of values drift by about 1e-3."""
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def _grad_norms(got: dict, want: dict):
    """The reference's first-gradient norm of each leaf, the leaves that
    count (at least MIN_SHARE of the median leaf's), and `got`'s norms of
    those."""
    gnorm = {p: _norm(g) for p, g in want["grad"].items()}
    med = float(np.median(list(gnorm.values())))
    keep = [p for p in ref.LEAVES if gnorm[p] >= MIN_SHARE * med]
    return gnorm, keep, {p: _norm(got["grad"][p]) for p in keep}


def numbers(got: dict, want: dict) -> dict:
    """The four numbers of `got` (program, control or fault) against
    `want` (the reference)."""
    gnorm, keep, pnorm = _grad_norms(got, want)
    diff = sum(_norm(np.asarray(got["grad"][p], np.float64) -
                     np.asarray(want["grad"][p], np.float64)) ** 2
               for p in keep)
    return {
        "loss_err": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_norm_err": _norm_gap(pnorm, gnorm, keep),
        "change_norm_err": _norm_gap(got["change"], want["change"], keep),
        "grad_l2_err": float(np.sqrt(diff / sum(gnorm[p] ** 2
                                                for p in keep))),
    }


def worst_leaves(got: dict, want: dict) -> dict:
    """The leaf that sets `grad_norm_err` and the one that sets
    `change_norm_err`, as "/"-joined paths."""
    gnorm, keep, pnorm = _grad_norms(got, want)
    out = {}
    for name, gaps in (("grad_norm_err", _leaf_gaps(pnorm, gnorm, keep)),
                       ("change_norm_err", _leaf_gaps(
                           got["change"], want["change"], keep))):
        out[name] = "/".join(max(gaps, key=gaps.get))
    return out


def run_check(run, got: dict, want: dict) -> dict:
    """{name: {"value", "limit"}} of every number the cell's limits list;
    `answers_off` counts the failed ones and the window's non-finite
    losses."""
    limits = run.cell["limits"]
    nums = numbers(got, want)
    run.counters["answers_off"] = run.counters.get("window_nonfinite", 0) \
        + sum(nums[k] > v["limit"] for k, v in limits.items())
    return {k: {"value": nums[k], "limit": v["limit"]}
            for k, v in limits.items()}
