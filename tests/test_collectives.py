"""Uno cross-pod collectives: equivalence with psum, 2-pod and 4-pod rings,
window scheduler behavior.  Multi-device tests run in subprocesses (device
count must be fixed before jax initializes; conftest must NOT set it
globally)."""
import json
import subprocess
import sys

import pytest

from repro.core.window_scheduler import ChunkWindowScheduler, SchedulerConfig


def _run(code: str) -> dict:
    # subprocesses see repro/ via the PYTHONPATH exported in conftest.py
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_uno_sync_matches_psum_2pods():
    res = _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro import sharding, train
from repro.configs.base import reduced, RunConfig
from repro.configs.registry import get_config
from repro.core.uno_collectives import make_uno_grad_sync
mesh = sharding.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced(get_config("granite-8b"))
run = RunConfig(uno_chunks=4)
with sharding.use_mesh(mesh):
    state = train.make_train_state(cfg, jax.random.PRNGKey(0))
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    batch = {"inputs": jax.random.randint(ks[0], (8, 32), 0, 255),
             "targets": jax.random.randint(ks[1], (8, 32), 0, 255)}
    base = jax.jit(train.make_train_step(cfg, run))
    uno = jax.jit(train.make_train_step(
        cfg, run, uno_sync=make_uno_grad_sync(mesh, cfg, run), mesh=mesh))
    s1, m1 = base(state, batch, jnp.int32(1))
    s2, m2 = uno(state, batch, jnp.int32(1))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1["params"], s2["params"])
    print(json.dumps({"delta": max(jax.tree.leaves(d)),
                      "loss_base": float(m1["loss"]),
                      "loss_uno": float(m2["loss"])}))
""")
    assert res["delta"] < 5e-4
    assert abs(res["loss_base"] - res["loss_uno"]) < 1e-2


@pytest.mark.slow
def test_uno_ring_4pods_matches_mean():
    """The >2-pod protected ring reduces a raw vector to the pod mean."""
    res = _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from repro.configs.base import RunConfig
from repro.core.uno_collectives import _pod_ring_psum
from repro.sharding import make_mesh
mesh = make_mesh((4, 2), ("pod", "data"))
run = RunConfig(uno_chunks=2)
n = 4 * 8 * 256 * 2
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, n)).astype(np.float32))
f = jax.shard_map(lambda v: _pod_ring_psum(v[0], run, 4),
                  mesh=mesh, in_specs=P("pod"), out_specs=P(),
                  axis_names={"pod", "data"}, check_vma=False)
with jax.set_mesh(mesh):
    out = jax.jit(f)(x)
want = np.asarray(x).mean(axis=0)
err = float(np.max(np.abs(np.asarray(out) - want)))
scale = float(np.max(np.abs(want))) + 1e-9
print(json.dumps({"rel_err": err / scale}))
""")
    assert res["rel_err"] < 0.05      # int8 per-hop quantization, 3 hops


def test_window_scheduler_qa_on_straggler():
    sched = ChunkWindowScheduler(SchedulerConfig(chunk_bytes=1e6))
    for _ in range(10):
        sched.on_step([2.1e-3] * 8)
    healthy = sched.n_chunks
    for _ in range(4):
        dec = sched.on_step([2.1e-3] * 2 + [None] * 6)   # 6 chunks stall
    assert sched.cc.n_qa >= 1
    assert sched.n_chunks < healthy
    assert dec["reroute"]


def test_window_scheduler_recovers():
    sched = ChunkWindowScheduler(SchedulerConfig(chunk_bytes=1e6))
    for _ in range(10):
        sched.on_step([2.1e-3] * 8)
    for _ in range(3):
        sched.on_step([2.1e-3] * 2 + [None] * 6)
    low = sched.n_chunks
    for _ in range(200):
        sched.on_step([2.1e-3] * max(sched.n_chunks, 1))
    assert sched.n_chunks >= low


def test_protect_unprotect_roundtrip_both_kernel_paths():
    """The DCI wire format: int8 quant + RS(8,2) parity + decode-on-path
    reproduces the chunk within quantization tolerance, with the jnp-ref
    AND the Pallas(interpret) kernels."""
    import os

    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import RunConfig
    from repro.core import uno_collectives as uc

    run = RunConfig()
    n = 8 * 256 * 4                      # x * quant block * 4
    x = jnp.asarray(np.random.default_rng(0).normal(size=n).astype(np.float32))
    for mode in ("ref", "pallas"):
        os.environ["REPRO_UNO_KERNELS"] = mode
        try:
            rows, scales, parity, n0 = uc._protect(x, run)
            assert rows.shape[0] == run.uno_ec_data
            assert parity.shape[0] == run.uno_ec_parity
            out = uc._unprotect(rows, scales, parity, n0, run)
            scale_rep = np.repeat(np.asarray(scales), 256)[:n]
            err = np.abs(np.asarray(out) - np.asarray(x))
            assert (err <= 0.5 * scale_rep + 1e-6).all(), (mode, err.max())
        finally:
            os.environ.pop("REPRO_UNO_KERNELS", None)
