"""Pure-jnp oracles for every kernel (the `ref.py` of the kernel contract).

Table-based GF(256) (gathers via jnp.take — correct everywhere, slow on TPU)
and straightforward quantization math.  tests/test_kernels.py sweeps shapes
and dtypes asserting the Pallas kernels match these exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gf

_EXP = jnp.asarray(gf.EXP, jnp.int32)
_LOG = jnp.asarray(gf.LOG, jnp.int32)


def gf_mul_ref(a, b):
    """Elementwise GF(256) multiply via log/exp tables (uint8-valued int32)."""
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    la = jnp.take(_LOG, a)
    lb = jnp.take(_LOG, b)
    prod = jnp.take(_EXP, la + lb)
    return jnp.where((a == 0) | (b == 0), 0, prod)


def gf_matmul_ref(coeffs, x):
    """(M,K) uint8 coeff matrix x (K,B) uint8 data -> (M,B) uint8."""
    c = coeffs.astype(jnp.int32)[:, :, None]        # (M,K,1)
    d = x.astype(jnp.int32)[None, :, :]             # (1,K,B)
    prods = gf_mul_ref(jnp.broadcast_to(c, (c.shape[0], d.shape[1], d.shape[2])),
                       jnp.broadcast_to(d, (c.shape[0], d.shape[1], d.shape[2])))
    out = prods[:, 0, :]
    for k in range(1, prods.shape[1]):
        out = jnp.bitwise_xor(out, prods[:, k, :])
    return out.astype(jnp.uint8)


def rs_encode_ref(data, r: int):
    """data: (k, B) uint8 -> parity (r, B) uint8 (systematic Vandermonde)."""
    k = data.shape[0]
    rows = gf.rs_generator_rows(k, r)
    coeffs = jnp.asarray(np.array(rows, dtype=np.uint8))
    return gf_matmul_ref(coeffs, data)


def rs_decode_ref(survivors, k: int, r: int, missing: tuple[int, ...],
                  parity_avail: tuple[int, ...]):
    """survivors: (n_sur, B) uint8 in gf.rs_decode_matrix order -> missing
    data rows (m, B) uint8."""
    C = gf.rs_decode_matrix(k, r, tuple(missing), tuple(parity_avail))
    coeffs = jnp.asarray(np.array(C, dtype=np.uint8))
    return gf_matmul_ref(coeffs, survivors)


# ----------------------------------------------------------------- int8 quant

def quant_int8_ref(x, block: int = 256):
    """Blockwise absmax int8 quantization.  x: (..., N) with N % block == 0.
    Returns (q int8 same shape, scales f32 (..., N/block))."""
    shape = x.shape
    xb = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // block, block))
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(shape), scale


def dequant_int8_ref(q, scale, block: int = 256, dtype=jnp.float32):
    shape = q.shape
    qb = q.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // block, block))
    out = qb * scale[..., None]
    return out.reshape(shape).astype(dtype)


# ------------------------------------------------- fleetsim flow<->link ops

def fleet_offered_load_ref(routes, rates, split, n_links: int):
    """The original ravel'd `.at[].add` link aggregation.

    routes: (n_flows, n_paths, max_hops) int32 with -1 padding; rates:
    (n_flows,); split: (n_flows, n_paths).  Returns the (n_links + 1,)
    offered-load buffer (pad slot last) — the oracle the segment/CSR/Pallas
    fast paths must match.
    """
    pad_idx = jnp.where(routes >= 0, routes, n_links)
    hop_mask = (routes >= 0).astype(rates.dtype)
    per_hop = (rates[:, None] * split)[:, :, None] * hop_mask
    buf = jnp.zeros(n_links + 1, rates.dtype)
    return buf.at[pad_idx.ravel()].add(per_hop.ravel())


def fleet_offered_load_tiles_ref(routes, rates, split, n_links: int,
                                 n_boundary: int):
    """Oracle for a per-shard scatter under a halo exchange
    (links.offered_load(halo=n_boundary)): the (n_links + 1,) reference
    buffer split at `n_links - n_boundary` into (private, boundary +
    scratch) tiles — only the boundary tile is exchanged.
    Only the real links are part of the contract — the scratch slot is
    backend-specific (see fleet_offered_load_ref).
    """
    buf = fleet_offered_load_ref(routes, rates, split, n_links)
    return buf[:n_links - n_boundary], buf[n_links - n_boundary:]


def fleet_link_gathers_ref(routes, scale, clean, delay):
    """Three separate link -> flow gathers (the fused-kernel oracle).

    scale / clean / delay: (n_links,) per-link values.  Returns
    (sub_scale, sub_frac, sub_delay), each (n_flows, n_paths): min over
    hops of scale, 1 - prod over hops of clean, sum over hops of delay,
    with -1 hops contributing the identity (1 / 1 / 0).
    """
    n_links = scale.shape[0]
    pad_idx = jnp.where(routes >= 0, routes, n_links)
    scale_ext = jnp.concatenate([scale, jnp.ones(1, scale.dtype)])
    clean_ext = jnp.concatenate([clean, jnp.ones(1, clean.dtype)])
    delay_ext = jnp.concatenate([delay, jnp.zeros(1, delay.dtype)])
    return (jnp.min(scale_ext[pad_idx], axis=2),
            1.0 - jnp.prod(clean_ext[pad_idx], axis=2),
            jnp.sum(delay_ext[pad_idx], axis=2))


# --------------------------------------- PathTable compressed-pipeline oracles
# (dense jnp restatements of repro.fleetsim.links' two-stage factorization;
# tests pin the blocked-CSR and Pallas path-table backends to these AND the
# table pipeline itself to the flat fleet_offered_load_ref oracle)

def fleet_pt_offered_load_ref(pre_id, suf_id, seg_idx, rates, split,
                              n_links: int):
    """Two-stage unique-segment aggregation via plain `.at[].add` scatters.

    pre_id / suf_id: (n_flows, n_paths) unique-segment ids; seg_idx:
    (U, hseg) segment hop links in [0, n_links] (pads already redirected
    to the scratch slot).  Returns the (n_links + 1,) offered-load buffer.
    """
    sub = (rates[:, None] * split).ravel()
    u = seg_idx.shape[0]
    seg = jnp.zeros(u, sub.dtype)
    seg = seg.at[pre_id.ravel()].add(sub).at[suf_id.ravel()].add(sub)
    buf = jnp.zeros(n_links + 1, sub.dtype)
    per_hop = jnp.broadcast_to(seg[:, None], seg_idx.shape)
    return buf.at[seg_idx.ravel()].add(per_hop.ravel())


def fleet_pt_gathers_ref(pre_id, suf_id, seg_idx, scale, clean, delay):
    """Per-unique-segment reductions composed per subflow (the oracle of
    links._pt_gathers / fleet_pallas.path_table_gathers): min / prod / sum
    over each segment's hops, then min / product / sum across the
    prefix-suffix split.  Same return contract as fleet_link_gathers_ref.
    """
    scale_ext = jnp.concatenate([scale, jnp.ones(1, scale.dtype)])
    clean_ext = jnp.concatenate([clean, jnp.ones(1, clean.dtype)])
    delay_ext = jnp.concatenate([delay, jnp.zeros(1, delay.dtype)])
    seg_scale = jnp.min(scale_ext[seg_idx], axis=1)
    seg_clean = jnp.prod(clean_ext[seg_idx], axis=1)
    seg_delay = jnp.sum(delay_ext[seg_idx], axis=1)
    return (jnp.minimum(seg_scale[pre_id], seg_scale[suf_id]),
            1.0 - seg_clean[pre_id] * seg_clean[suf_id],
            seg_delay[pre_id] + seg_delay[suf_id])
