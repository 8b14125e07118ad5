"""Public jit'd kernel API — pads/reshapes to the kernels' tile multiples.

The kernels compile on a TPU and run in the Pallas interpreter on every other
backend (repro.kernels.interpret_mode decides; the interpreter executes the
same kernel bodies, so CPU tests validate the exact TPU program).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gf, quant_pallas, ref, rs_pallas


def _pad_axis(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# ----------------------------------------------------------------- RS coding

def rs_encode(data, r: int):
    """Systematic RS parity over packet rows: (k, B) uint8 -> (r, B) uint8."""
    x, b0 = _pad_axis(data, 1, rs_pallas.TILE_B)
    out = rs_pallas.rs_encode(x, r)
    return out[:, :b0]


def rs_decode(survivors, k: int, r: int, missing, parity_avail):
    """Reconstruct missing data rows; see rs_pallas.rs_decode for ordering."""
    missing = tuple(sorted(int(i) for i in missing))
    parity_avail = tuple(sorted(int(i) for i in parity_avail))
    if not missing:
        return survivors[:0]
    x, b0 = _pad_axis(survivors, 1, rs_pallas.TILE_B)
    out = rs_pallas.rs_decode(x, k, r, missing, parity_avail)
    return out[:, :b0]


def rs_block_roundtrip(data, r: int, missing):
    """Encode, drop `missing` data rows, decode them back (test/bench path)."""
    k = data.shape[0]
    parity = rs_encode(data, r)
    present = [i for i in range(k) if i not in set(missing)]
    survivors = jnp.concatenate([data[jnp.asarray(present)], parity], axis=0)
    rec = rs_decode(survivors, k, r, missing, tuple(range(r)))
    return parity, rec


# ---------------------------------------------------------------- int8 quant

QUANT_BLOCK = 256
_QCHUNK = quant_pallas.ROWS * QUANT_BLOCK


def quant_int8(x):
    """Flat float array -> (q int8, scales f32, original length)."""
    flat = x.reshape(-1)
    padded, n0 = _pad_axis(flat, 0, _QCHUNK)
    q, s = quant_pallas.quant_int8(padded, QUANT_BLOCK)
    return q, s, n0


def dequant_int8(q, scales, n0: int, dtype=jnp.float32):
    out = quant_pallas.dequant_int8(q, scales, QUANT_BLOCK, dtype)
    return out[:n0]


# ------------------------------------------------------------ float <-> bytes

def f32_to_bytes_rows(x, k: int):
    """Pack a float32 vector into k equal uint8 rows (RS packet framing)."""
    raw = jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)
    padded, n0 = _pad_axis(raw, 0, k)
    return padded.reshape(k, -1), n0


def bytes_rows_to_f32(rows, n0: int):
    flat = rows.reshape(-1)[:n0]
    # bitcast u8 (M, 4) -> f32 collapses the trailing dim -> (M,)
    return jax.lax.bitcast_convert_type(flat.reshape(-1, 4), jnp.float32)
