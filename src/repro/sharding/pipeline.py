"""Pipeline parallelism over a `pipe` mesh axis (GPipe schedule, shard_map).

For depth-dominated models (94-layer qwen3, 96-layer nemotron) a `pipe`
sub-axis trades the all-layer FSDP gathers for point-to-point activation
transfers.  Layout: the layer stack (L, ...) is split into S = |pipe|
stages of L/S layers; each pipe shard holds its stage's parameters.  The
rotation loop runs T = n_micro + S - 1 ticks; tick t:

    stage s computes its layers on its current microbatch activations,
    then every activation hops one stage forward (ppermute) while stage 0
    injects the next microbatch.

jax.grad differentiates straight through the scan — the reverse pass
replays the schedule backwards (ppermute transposes to the reverse
permutation), which is exactly pipelined backprop.  The schedule keeps
S in-flight microbatches (1F1B's steady-state working set; the classic
bubble of (S-1)/T ticks remains and is reported by `bubble_fraction`).

On the DCI question this module is Uno-relevant: a pipeline stage boundary
placed on the `pod` axis turns the cross-DC traffic from gradient-sized
all-reduces into activation-sized permutes — the same "what crosses the
slow link" decision the paper's §5.2.3 workload makes.  `pipe` can map to
any mesh axis, including `pod`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int          # must be >= n_stages for reasonable bubbles
    axis: str = "pipe"

    @property
    def n_ticks(self) -> int:
        return self.n_microbatches + self.n_stages - 1

    @property
    def bubble_fraction(self) -> float:
        return (self.n_stages - 1) / self.n_ticks


def pipeline_apply(cfg: PipelineConfig, mesh: Mesh, stage_fn: Callable,
                   stage_params, x_micro):
    """Run a layer-stack through the pipeline.

    stage_fn(params_stage, h) -> h        (one stage's layers, local)
    stage_params: pytree with leading dim n_stages (sharded over `axis`)
    x_micro:      (n_micro, mb, ...) microbatched activations (replicated
                  over `axis`; stage 0 consumes them in order)
    Returns (n_micro, mb, ...) outputs (as produced by the LAST stage).
    """
    S, M = cfg.n_stages, cfg.n_microbatches
    ax = cfg.axis
    fwd = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params_loc, xs_loc):
        # params_loc: (1, ...) this stage's slice;  xs_loc: (M, mb, ...)
        params_loc = jax.tree.map(lambda p: p[0], params_loc)
        idx = jax.lax.axis_index(ax)
        mb_shape = xs_loc.shape[1:]
        state = jnp.zeros(mb_shape, xs_loc.dtype)       # current activation
        outs = jnp.zeros((M,) + mb_shape, xs_loc.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 swaps in microbatch t (if still injecting)
            inject = jnp.where(t < M, t, M - 1)
            state = jnp.where((idx == 0) & (t < M),
                              xs_loc[inject], state)
            h = stage_fn(params_loc, state)
            # last stage records microbatch (t - (S-1)) when valid
            m_out = t - (S - 1)
            valid = (idx == S - 1) & (m_out >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h, jnp.maximum(m_out, 0), 0),
                lambda o: o, outs)
            # rotate: every stage hands its activation to the next
            state = jax.lax.ppermute(h, ax, fwd)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(cfg.n_ticks))
        # outputs live on the last stage; share them with every stage so
        # the caller sees a replicated result (loss runs data-parallel)
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), ax)
        return outs

    spec_params = jax.tree.map(lambda _: P(ax), stage_params)
    return jax.shard_map(per_stage, mesh=mesh,
                         in_specs=(spec_params, P()), out_specs=P(),
                         axis_names={ax}, check_vma=False)(
        stage_params, x_micro)


def split_stack(params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major."""
    def re(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])
    return jax.tree.map(re, params)
