"""Uno cross-pod grad-sync bench (fig 13 C's trainer-side counterpart).

Runs baseline-GSPMD vs Uno train steps with a reduced model, measuring (a)
numerical agreement, (b) wall time per step, (c) DCI payload accounting
(bytes on the pod hop with/without int8+RS), and exercises the host window
scheduler against a synthetic straggler trace.

On an accelerator host the mesh is built in this process over the host's
devices — (pod=2, data=2, model=2) with 8 of them, else (pod=2, data=n/2) —
because a chip belongs to the process that touched it first.  A CPU run
forks a child with 8 forced host devices (the count must be fixed before
jax initializes).
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks import common


def measure(mesh) -> dict:
    """One baseline and one Uno step from the same state, five timed steps
    of each, the DCI payload accounting and the scheduler trace."""
    from repro import sharding, train
    from repro.configs.base import RunConfig, reduced
    from repro.configs.registry import get_config
    from repro.core.uno_collectives import make_uno_grad_sync
    from repro.core.window_scheduler import (ChunkWindowScheduler,
                                             SchedulerConfig)

    cfg = reduced(get_config("granite-8b"), n_layers=4, d_model=128,
                  d_ff=512)
    run_cfg = RunConfig(uno_chunks=4)
    rng = jax.random.PRNGKey(0)
    with sharding.use_mesh(mesh):
        state = train.make_train_state(cfg, rng)
        ks = jax.random.split(rng, 2)
        batch = {"inputs": jax.random.randint(ks[0], (16, 64), 0, 255),
                 "targets": jax.random.randint(ks[1], (16, 64), 0, 255)}
        base = jax.jit(train.make_train_step(cfg, run_cfg))
        uno = jax.jit(train.make_train_step(
            cfg, run_cfg, uno_sync=make_uno_grad_sync(mesh, cfg, run_cfg),
            mesh=mesh))
        s1, _ = base(state, batch, jnp.int32(1))
        s2, _ = uno(state, batch, jnp.int32(1))
        jax.block_until_ready((s1, s2))
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
            s1["params"], s2["params"])
        delta = max(jax.tree.leaves(d))

        def timeit(fn, st):
            t0 = time.perf_counter()
            for i in range(5):
                st, _ = fn(st, batch, jnp.int32(i + 2))
            jax.block_until_ready(st)
            return (time.perf_counter() - t0) / 5

        t_base = timeit(base, s1)
        t_uno = timeit(uno, s2)

    n_params = sum(math.prod(l.shape) for l in jax.tree.leaves(s1["params"]))
    raw = n_params * 4                      # f32 DCI payload, no compression
    q = n_params * 1                        # int8
    ec = q * (1 + run_cfg.uno_ec_parity / run_cfg.uno_ec_data) \
        + 4 * n_params // 256               # parity + scales
    sched = ChunkWindowScheduler(SchedulerConfig(chunk_bytes=1e6))
    lat = [[2.1e-3] * 8] * 10 + [[2.1e-3] * 4 + [9e-3] * 4] * 3 + \
        [[2.1e-3] * 8] * 10
    pre = sched.n_chunks
    for step_lat in lat:
        sched.on_step(step_lat)
    dev = jax.devices()[0]
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": int(mesh.devices.size)},
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "max_param_delta": delta, "step_ms_base": t_base * 1e3,
        "step_ms_uno": t_uno * 1e3,
        "dci_bytes_raw": raw, "dci_bytes_uno": int(ec),
        "dci_compression_x": raw / ec,
        "sched_chunks_start": pre, "sched_chunks_end": sched.n_chunks,
        "sched_qa_events": sched.cc.n_qa, "sched_reroutes": sched.n_reroutes}


def bench_mesh(n: int):
    """(pod=2, data=2, model=2) over 8 devices, else (pod=2, data=n/2)."""
    from repro.sharding import make_mesh
    if n >= 8:
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    if n < 2:
        raise RuntimeError(f"the pod exchange needs >= 2 devices, have {n}")
    return make_mesh((2, n // 2), ("pod", "data"))


def run(quick: bool = True) -> dict:
    if jax.default_backend() != "cpu" or jax.device_count() >= 8:
        res = measure(bench_mesh(jax.device_count()))
    else:
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from benchmarks.uno_collectives_bench import bench_mesh, measure
print(json.dumps(measure(bench_mesh(jax.device_count()))))
"""
        root = pathlib.Path(__file__).resolve().parents[1]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(root / "src"), str(root), env.get("PYTHONPATH", "")])
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=900,
                             env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-2000:])
        res = json.loads(out.stdout.strip().splitlines()[-1])
    common.save("uno_collectives_bench", res)
    return res
