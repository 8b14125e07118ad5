"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 256 [--uno] [--mesh 2x2x2] \
      [--ckpt-dir /tmp/ck] [--reduced]

On this CPU container use --reduced (tiny same-family config) or the small
archs; on a pod, drop --reduced and pass the production mesh.  --uno routes
cross-pod gradient sync through the protected DCI exchange and adapts the
chunk window across steps with the host scheduler (core/window_scheduler).
"""
from __future__ import annotations

import argparse
import math
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2x2 => (pod,data,model); needs that many "
                         "devices (or XLA_FLAGS host-device override)")
    ap.add_argument("--uno", action="store_true")
    ap.add_argument("--uno-chunks", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        need = math.prod(dims)
        import jax as _jax_probe  # noqa: F401  (device count locks here)
    import jax
    import jax.numpy as jnp

    from repro import data, ft, sharding, train
    from repro.configs.base import RunConfig, reduced
    from repro.configs.registry import get_config

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        names = ("pod", "data", "model")[-len(dims):] if len(dims) < 3 else \
            ("pod", "data", "model")
        mesh = sharding.make_mesh(dims, names)

    run = RunConfig(learning_rate=args.lr, uno_enabled=args.uno,
                    uno_chunks=args.uno_chunks, seed=args.seed)

    ctx = sharding.use_mesh(mesh) if mesh is not None else _null()
    with ctx:
        rng = jax.random.PRNGKey(args.seed)
        state = train.make_train_state(cfg, rng)
        sspecs = train.state_pspecs(cfg) if mesh is not None else None
        shardings = (sharding.spec_tree_to_shardings(mesh, sspecs)
                     if mesh is not None else None)
        uno_sync = None
        if args.uno and mesh is not None and "pod" in mesh.axis_names:
            from repro.core.uno_collectives import make_uno_grad_sync
            uno_sync = make_uno_grad_sync(mesh, cfg, run)
        step = jax.jit(train.make_train_step(cfg, run, uno_sync=uno_sync,
                                             mesh=mesh),
                       donate_argnums=(0,))

        batch_shardings = None
        if mesh is not None:
            specs = train.batch_pspecs(
                cfg, data.synth_batch(cfg, 0, args.batch, args.seq))
            batch_shardings = sharding.spec_tree_to_shardings(mesh, specs)
        pipe = data.ShardedPipeline(cfg, batch=args.batch, seq=args.seq,
                                    shardings=batch_shardings,
                                    seed=args.seed)
        sup = ft.Supervisor(
            ft.FTConfig(ckpt_dir=args.ckpt_dir or None,
                        ckpt_every=args.ckpt_every),
            state_template=state, state_shardings=shardings)

        t0 = time.time()
        losses = []

        def on_metrics(i, metrics, wall):
            losses.append(float(metrics["loss"]))
            if i % args.log_every == 0:
                tok_s = args.batch * args.seq / wall
                print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"{wall * 1e3:7.1f} ms/step  {tok_s:9.0f} tok/s",
                      flush=True)

        state, last = sup.run(state, step, iter(pipe), n_steps=args.steps,
                              on_metrics=on_metrics)
        pipe.close()
        print(f"done: {last} steps in {time.time() - t0:.1f}s; "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
              f"ft events: {len(sup.events)}")


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
