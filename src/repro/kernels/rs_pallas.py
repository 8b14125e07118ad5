"""Pallas TPU kernels for Reed-Solomon GF(2^8) encode/decode.

The per-byte GF mult-accumulate is UnoRC's compute hot spot (on the paper's
software shim it is the CPU bottleneck; here it must not eat into the MXU
budget of the training step).  The classical table-based algorithm needs a
per-lane gather — which the TPU VPU does not have — so the kernel uses the
bit-sliced xtime ladder from repro.kernels.gf: per input row, 8 shift/mask/
XOR "multiply-by-2" steps shared across all output rows, then masked XOR
accumulation.  Integer ops on full 8x128 lanes, zero gathers, MXU-free.

Layout: payload bytes as uint8 (k, B) with the byte axis tiled in
`TILE_B`-sized VMEM blocks (grid over ceil(B / TILE_B)).  The coefficient
matrix is tiny and static (it is baked into the kernel at trace time — one
kernel specialization per (k, r) or per decode pattern, matching how a real
deployment pins its EC geometry).

VMEM budget at TILE_B=2048, k=8, r=2 (int32 widened):
  in  8*2048*4  = 64 KiB,  out 2*2048*4 = 16 KiB, + ladder temp -> ~100 KiB,
comfortably inside the ~16 MiB v5e VMEM even with double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gf, interpret_mode

TILE_B = 2048


def _gf_matmul_kernel(x_ref, o_ref, *, coeffs):
    """One byte-tile: o[m] = XOR_k coeffs[m][k] * x[k] over GF(256)."""
    M = len(coeffs)
    x = x_ref[...].astype(jnp.int32)               # (k, TILE_B)
    outs = [jnp.zeros(x.shape[1:], jnp.int32) for _ in range(M)]
    K = x.shape[0]
    for k in range(K):
        cur = x[k]
        live = [m for m in range(M) if coeffs[m][k]]
        if not live:
            continue
        maxbit = max(coeffs[m][k] for m in live).bit_length()
        for bit in range(maxbit):
            for m in live:
                if (coeffs[m][k] >> bit) & 1:
                    outs[m] = outs[m] ^ cur
            if bit + 1 < maxbit:
                cur = gf.xtime(cur)
    o_ref[...] = jnp.stack(outs).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("coeffs", "interpret"))
def gf_matmul(x, coeffs: tuple[tuple[int, ...], ...],
              interpret: Optional[bool] = None):
    """(M,K) static GF coeffs x (K,B) uint8 -> (M,B) uint8 via pallas_call.

    B must be a multiple of TILE_B (ops.py pads).  `interpret` resolves
    through repro.kernels.interpret_mode (compiled on a TPU).
    """
    K, B = x.shape
    M = len(coeffs)
    assert B % TILE_B == 0, B
    grid = (B // TILE_B,)
    return pl.pallas_call(
        functools.partial(_gf_matmul_kernel, coeffs=coeffs),
        grid=grid,
        in_specs=[pl.BlockSpec((K, TILE_B), lambda i: (0, i))],
        out_specs=pl.BlockSpec((M, TILE_B), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((M, B), jnp.uint8),
        interpret=interpret_mode(interpret),
    )(x)


def rs_encode(data, r: int, interpret: Optional[bool] = None):
    """Systematic RS parity: data (k, B) uint8 -> (r, B) uint8."""
    k = data.shape[0]
    return gf_matmul(data, gf.rs_generator_rows(k, r), interpret=interpret)


def rs_decode(survivors, k: int, r: int, missing: tuple[int, ...],
              parity_avail: tuple[int, ...],
              interpret: Optional[bool] = None):
    """Reconstruct `missing` data rows from survivor rows.

    survivors: (n_sur, B) uint8 ordered [present data asc] + [avail parity
    asc] (see gf.rs_decode_matrix).  The erasure pattern is static — the
    decode matrix is solved on host at trace time and baked into the kernel.
    """
    C = gf.rs_decode_matrix(k, r, tuple(missing), tuple(parity_avail))
    return gf_matmul(survivors, C, interpret=interpret)
