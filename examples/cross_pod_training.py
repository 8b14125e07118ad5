"""Cross-pod training with the Uno DCI exchange — the paper's Fig 13 C
workload on a local 8-device (pod=2, data=2, model=2) mesh.

  PYTHONPATH=src python examples/cross_pod_training.py

Shows: (1) Uno grad sync numerically tracking the plain-psum baseline while
compressing the DCI payload (int8 + RS(8,2)); (2) the host window scheduler
reacting to an injected straggler step (Quick-Adapt window collapse +
subflow re-route), then recovering; (3) checkpoint + restart mid-run.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import tempfile  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import ckpt, data, sharding, train  # noqa: E402
from repro.configs.base import RunConfig, reduced  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.uno_collectives import make_uno_grad_sync  # noqa: E402
from repro.core.window_scheduler import (ChunkWindowScheduler,  # noqa: E402
                                         SchedulerConfig)


def main() -> None:
    mesh = sharding.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = reduced(get_config("granite-8b"), n_layers=4, d_model=128, d_ff=512)
    run = RunConfig(uno_chunks=8)
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"model {cfg.name}")

    with sharding.use_mesh(mesh):
        state = train.make_train_state(cfg, jax.random.PRNGKey(0))
        base = jax.jit(train.make_train_step(cfg, run))
        uno = jax.jit(train.make_train_step(
            cfg, run, uno_sync=make_uno_grad_sync(mesh, cfg, run), mesh=mesh))
        pipe = data.ShardedPipeline(cfg, batch=16, seq=64)
        sched = ChunkWindowScheduler(SchedulerConfig(chunk_bytes=1 << 18))

        s_base, s_uno = state, state
        with tempfile.TemporaryDirectory() as ckdir:
            for i in range(30):
                _, batch = next(pipe)
                t0 = time.perf_counter()
                s_base, m_base = base(s_base, batch, jnp.int32(i))
                s_uno, m_uno = uno(s_uno, batch, jnp.int32(i))
                jax.block_until_ready(s_uno)
                wall = time.perf_counter() - t0
                # feed the scheduler; inject a "DCI flap" at step 12
                n = sched.n_chunks
                lat = [3e-3] * n if i != 12 else [3e-3] * (n // 4) + \
                    [None] * (n - n // 4)
                dec = sched.on_step(lat)
                if i % 5 == 0 or dec["qa"]:
                    drift = abs(float(m_base["loss"]) - float(m_uno["loss"]))
                    print(f"step {i:3d} loss={float(m_uno['loss']):.4f} "
                          f"drift_vs_psum={drift:.2e} chunks={dec['n_chunks']}"
                          f"{'  << QA collapse + reroute' if dec['qa'] else ''}")
                if i == 15:
                    ckpt.save(ckdir, i, s_uno)
                    print(f"step {i:3d} checkpoint saved")
                if i == 20:
                    s_uno = ckpt.restore(ckdir, 15, s_uno)
                    print("step  20 restored from step-15 checkpoint "
                          "(restart drill)")
        pipe.close()
        print(f"\nscheduler: {sched.cc.n_qa} QA events, "
              f"{sched.n_reroutes} re-routes; final chunk window "
              f"{sched.n_chunks}")
        print("cross-pod example OK")


if __name__ == "__main__":
    main()
