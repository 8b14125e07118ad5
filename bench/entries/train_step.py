"""Closed-loop data-parallel training through the Uno cross-pod exchange.

One trainer drives `repro.train.make_train_step` with the Uno gradient
sync (`repro.core.uno_collectives.make_uno_grad_sync`) on a (pod, data)
mesh of the host's chips, under the configuration's sharding profile.
Each call is one step: the state the last step returned, the next batch
of the pool, the step index; the call ends in `block_until_ready` on the
step's metrics, and the next one starts only then.

Set-up makes, each in one jitted call from the seed, the weights
(`bench/train_reference.init_params`, in the stored type the
configuration states) with zero AdamW moments, and a pool of `pool`
token batches, split over the mesh ahead of the window, so that no host
input pipeline is timed.  It compiles the step once for these shapes and
runs its first `check_steps` steps through the window's own call and
feed: those are the steps that `correct` follows (`bench/train_check.py`),
and the window goes on from the state they leave.

Traffic keys: mesh {axis: size}, rows, seq, pool, check_steps, step0
(the step index of the first step), scan_modules, trace_calls.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench import train_check as tc
from bench import train_reference as ref


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**cfg["model"])


def seed_keys(seed: int):
    """(weights key, tokens key) from any whole seed below 2**64."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.random.split(key)


def make_weights(key, m: dict, sharding=None):
    """The benchmark's weights and zero AdamW state, the program's
    `{"params", "opt": {"m", "v", "step"}}` layout, in one jitted call."""
    import jax
    import jax.numpy as jnp

    def make(k):
        params = ref.init_params(k, m)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, m["opt_state_dtype"]), params)
        return {"params": params,
                "opt": {"m": zeros, "v": jax.tree.map(jnp.zeros_like, zeros),
                        "step": jnp.zeros((), jnp.int32)}}
    return jax.jit(make, out_shardings=sharding)(key)


def make_pool(key, tr: dict, vocab: int, sharding=None) -> list:
    """`pool` batches {"inputs", "targets"} of rows x seq token ids."""
    import jax

    def make(k):
        tokens = ref.token_pool(k, tr["pool"], tr["rows"], tr["seq"], vocab)
        inputs, targets = ref.split_batch(tokens)
        return [{"inputs": inputs[i], "targets": targets[i]}
                for i in range(tr["pool"])]
    return jax.jit(make, out_shardings=sharding)(key)


def build(run) -> dict:
    """What does not depend on the seed: the mesh and the step compiled
    for the cell's shapes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import sharding, train
    from repro.configs.base import RunConfig
    from repro.core.uno_collectives import make_uno_grad_sync
    cfg, tr = run.cell["config"], run.cell["traffic"]
    opt = cfg["optimizer"]
    model = model_config(cfg)
    axes = tuple(tr["mesh"])
    mesh = sharding.make_mesh(tuple(tr["mesh"][a] for a in axes), axes)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(axes))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        train.make_train_state(model, abstract=True))
    batch = {k: jax.ShapeDtypeStruct((tr["rows"], tr["seq"]), "int32",
                                     sharding=rows)
             for k in ("inputs", "targets")}
    runcfg = RunConfig(learning_rate=opt["learning_rate"],
                       warmup_steps=opt["warmup_steps"], **cfg["uno"])
    with run.span("compile"), \
            sharding.use_mesh(mesh, sharding.profile_rules(model)):
        step = train.make_train_step(
            model, runcfg, uno_sync=make_uno_grad_sync(mesh, model, runcfg),
            mesh=mesh)
        compiled = jax.jit(step, in_shardings=(rep, rows, rep),
                           out_shardings=rep).lower(
            state, batch, jax.ShapeDtypeStruct((), "int32",
                                               sharding=rep)).compile()
    return {"step": compiled, "rep": rep, "rows": rows,
            "layout": jax.tree.map(lambda s: (s.shape, s.dtype), state)}


def start(run, built: dict) -> dict:
    """The seed's weights and batches, and the first `check_steps` steps
    through the window's own call."""
    import jax
    cfg, tr = run.cell["config"], run.cell["traffic"]
    k_weights, k_tokens = seed_keys(run.seed)
    with run.span("weights"):
        state = make_weights(k_weights, cfg["model"], built["rep"])
        pool = make_pool(k_tokens, tr, cfg["model"]["vocab"], built["rows"])
        jax.block_until_ready((state, pool))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), state)
    if got != built["layout"]:
        raise ValueError(f"benchmark weights {got} are not the program's "
                         f"layout {built['layout']}")
    st = {"step": built["step"], "state": state, "pool": pool, "i": 0,
          "losses": [], "tokens": tr["rows"] * tr["seq"]}
    p0 = state["params"]
    with run.span("warmup"):
        for _ in range(tr["check_steps"]):
            call(run, st)
            if st["i"] == 1:
                first_m = tc.host_leaves(st["state"]["opt"]["m"])
        st["prog"] = tc.program_summary(
            first_m, tc.change_norms(st["state"]["params"], p0),
            [float(x) for x in st.pop("losses")], cfg["optimizer"]["b1"])
    return st


def setup(run):
    return start(run, build(run))


def call(run, st):
    """One step: the window's call, also the set-up's first steps."""
    import jax
    tr = run.cell["traffic"]
    batch = st["pool"][st["i"] % tr["pool"]]
    idx = np.int32(tr["step0"] + st["i"])
    t0 = time.perf_counter()
    with run.span("call"):
        st["state"], metrics = st["step"](st["state"], batch, idx)
    with run.span("block"):
        jax.block_until_ready(metrics)
    t1 = time.perf_counter()
    st["i"] += 1
    st["losses"].append(metrics["loss"])
    return t0, t1, st["tokens"], 1, metrics


def window(run, st, prof) -> None:
    st["losses"] = []
    harness.measure(run, prof, lambda: call(run, st), 0)


def free(st) -> dict:
    """Deletes the program's state and batches; returns its summary of
    the first steps."""
    import jax
    prog = st["prog"]
    for a in jax.tree.leaves((st["state"], st["pool"])):
        a.delete()
    st.clear()
    return prog


def check(run, st) -> dict:
    """Frees the program's state, then follows the first steps with the
    reference on one chip, from the same seed."""
    losses = np.asarray([float(x) for x in st["losses"]])
    run.counters["window_nonfinite"] = int(np.sum(~np.isfinite(losses)))
    prog = free(st)
    return tc.run_check(run, prog, tc.reference_summary(run.cell, run.seed))
