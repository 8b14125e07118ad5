"""Pallas TPU kernels: blockwise-absmax int8 quantize / dequantize.

Used by uno_collectives to compress the cross-pod (DCI) gradient payload 2x
(bf16 -> int8 + 1 f32 scale per `block` elements; <2% overhead at block=256)
before RS parity is added.  Tiling: each grid step owns `ROWS` quant blocks
-> VMEM tile (ROWS, block) f32 in, (ROWS, block) int8 + (1, ROWS) f32 out.
Lane-friendly: block is a multiple of 128, reductions run along the minor
axis.  The scales leave the kernel as a lane-dense (1, n_blocks) row (a 1-D
block disagrees with XLA's TPU tiling of the array), so the kernel turns
its (ROWS, 1) column of scales into a row with one 2-D transpose.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

ROWS = 256          # quant blocks per grid step


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (ROWS, block)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (ROWS, 1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, (ROWS, 128)).T[0:1, :]


def _dequant_kernel(q_ref, s_ref, o_ref, *, dtype):
    q = q_ref[...].astype(jnp.float32)
    scale = jnp.broadcast_to(s_ref[...], (128, ROWS)).T[:, 0:1]
    o_ref[...] = (q * scale).astype(dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quant_int8(x, block: int = 256, interpret: Optional[bool] = None):
    """x: (N,) float -> (q int8 (N,), scales f32 (N/block,)).

    N must be a multiple of ROWS*block (ops.py pads)."""
    n = x.shape[0]
    nb = n // block
    assert n == nb * block and nb % ROWS == 0, (n, block)
    xb = x.reshape(nb, block)
    grid = (nb // ROWS,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                   pl.BlockSpec((1, ROWS), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((1, nb), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xb)
    return q.reshape(n), s.reshape(nb)


@functools.partial(jax.jit, static_argnames=("block", "dtype", "interpret"))
def dequant_int8(q, scales, block: int = 256, dtype=jnp.float32,
                 interpret: Optional[bool] = None):
    n = q.shape[0]
    nb = n // block
    qb = q.reshape(nb, block)
    grid = (nb // ROWS,)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, dtype=dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, ROWS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), dtype),
        interpret=interpret_mode(interpret),
    )(qb, scales.reshape(1, nb))
    return out.reshape(n)
