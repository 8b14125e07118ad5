"""Pipeline parallelism: schedule correctness vs sequential reference and
gradient flow through the rotated schedule (subprocess: multi-device)."""
import json
import subprocess
import sys

import pytest

from repro.sharding.pipeline import PipelineConfig


def test_bubble_fraction():
    cfg = PipelineConfig(n_stages=4, n_microbatches=12)
    assert cfg.n_ticks == 15
    assert abs(cfg.bubble_fraction - 3 / 15) < 1e-9


def _run(code: str) -> dict:
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_pipeline_matches_sequential_and_grads():
    res = _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from repro.sharding import make_mesh
from repro.sharding.pipeline import PipelineConfig, pipeline_apply, split_stack

L, D, MB, M, S = 8, 16, 4, 8, 4
mesh = make_mesh((4,), ("pipe",))
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.2)
x = jnp.asarray(rng.normal(size=(M, MB, D)).astype(np.float32))

def layer(w, h):
    return jnp.tanh(h @ w)

def stage_fn(w_stage, h):           # (L/S, D, D)
    def body(h, w):
        return layer(w, h), None
    h, _ = jax.lax.scan(body, h, w_stage)
    return h

def sequential(W, x):
    def body(h, w):
        return layer(w, h), None
    out = []
    for m in range(M):
        h, _ = jax.lax.scan(body, x[m], W)
        out.append(h)
    return jnp.stack(out)

cfg = PipelineConfig(n_stages=S, n_microbatches=M)
Wst = split_stack(W, S)

def loss_pipe(Wst, x):
    return jnp.sum(pipeline_apply(cfg, mesh, stage_fn, Wst, x) ** 2)

def loss_seq(W, x):
    return jnp.sum(sequential(W, x) ** 2)

with jax.set_mesh(mesh):
    piped = jax.jit(lambda Wst, x: pipeline_apply(cfg, mesh, stage_fn, Wst, x))
    y_pipe = piped(Wst, x)
    g_pipe = jax.jit(jax.grad(loss_pipe))(Wst, x)
y_seq = sequential(W, x)
fwd_err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
g_seq = jax.grad(loss_seq)(W, x)
g_err = float(jnp.max(jnp.abs(g_pipe.reshape(L, D, D) - g_seq)))
print(json.dumps({"fwd_err": fwd_err, "g_err": g_err}))
""")
    assert res["fwd_err"] < 1e-5, res
    assert res["g_err"] < 1e-4, res
