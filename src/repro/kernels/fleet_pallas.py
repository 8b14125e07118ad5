"""Pallas kernels for the fleetsim flow<->link exchange.

Two blocked kernels over the route tensor, the per-epoch hot path of
repro.fleetsim:

  * `link_scatter`  — flow -> link: accumulate every subflow's wire rate
    onto each hop of its path, producing the (n_links + 1,) offered-load
    buffer (the pad slot absorbs -1 hops).
  * `link_gathers`  — link -> flow, fused: ONE pass over the route tensor
    yields all three per-subflow reductions (min over hops of the link
    scale, mark composition 1 - prod(1 - p), and the queue-delay sum) that
    the reference path (`repro.kernels.ref.fleet_link_gathers_ref`, the
    jnp oracle) computes with three separate gathers.

The TPU VPU has no per-lane gather/scatter, so both kernels express the
sparse access as a one-hot matmul against the link axis on the MXU (f32
contraction precision, so the sums are exact to f32 rounding).  That makes
them O(entries * n_links): right for fat-tree-scale link counts, wrong for
the degenerate one-uplink-per-flow topologies where n_links ~ n_flows (the
CSR / PathTable paths in repro.fleetsim.links cover that regime).

Layout: the kernels read the route tensor hop-major, as the (max_hops,
n_paths, lanes) index `repro.fleetsim.links.RouteLayout.hop_idx` holds,
flows on the 128-wide lane axis and padded to a whole number of blocks
(`pad_lanes`; padding flows point every hop at the scratch slot with zero
rate).  Each grid step takes `block` flows (a multiple of 128) and loops
over the (path, hop) rows, so no in-kernel reshape ever crosses the
lane/sublane tiling.  The one-hot tile is (link columns, block): the
scatter tiles the link axis (grid = link tiles x flow blocks, the output
tile revisited and accumulated across flow blocks), the gather holds the
whole link axis in one tile — `pick_block` keeps every one-hot tile
inside ONEHOT_BUDGET elements of VMEM.

Every wrapper takes `interpret=None`, resolved by
`repro.kernels.interpret_mode`: compiled on a TPU, interpreted elsewhere.

The `path_table_*` wrappers run repro.fleetsim.links.PathTable's
compressed two-stage pipeline through the SAME kernel bodies: stage 1
scatters subflow rates over the (n, p, 2) prefix/suffix segment-id tensor
(segments play the role of links), stage 2 scatters the (U, 1, hseg)
unique-segment table into real links, and the fused gather pass runs once
per unique segment before two per-subflow takes compose the halves.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANES = 128                 # TPU lane width: flow blocks are multiples
BLOCK_ROWS = 512            # widest flow block per grid step
# f32 elements of one (link columns, block) one-hot tile: 2 MiB, so the
# tile, its iota/compare temporaries and the double-buffered inputs stay
# well inside v5e's 16 MiB of scoped VMEM
ONEHOT_BUDGET = 1 << 19

_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pick_block(n_rows: int, n_cols: Optional[int] = None) -> int:
    """Flow lanes per grid step for `n_rows` flows.

    128 (one lane tile) for small inputs, doubling while the rows still
    fill eight grid steps, up to BLOCK_ROWS.  With `n_cols` (the gather,
    whose one-hot tile spans every link column at once) the block also
    stays within ONEHOT_BUDGET.
    """
    block = LANES
    while (block < BLOCK_ROWS and block * 8 < n_rows and
           (n_cols is None or
            2 * block * _round_up(n_cols, LANES) <= ONEHOT_BUDGET)):
        block *= 2
    return block


def pad_lanes(n_rows: int) -> int:
    """Lane length of a hop-major index over `n_rows` flows: a whole
    number of `pick_block(n_rows)` blocks, so every block `pick_block`
    chooses for it (the gather's budgeted one included) divides it."""
    return _round_up(n_rows, pick_block(n_rows))


def _pad_to(x, m: int, fill):
    """`x` with its last (lane) axis padded to length `m` with `fill`."""
    pad = m - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=fill)


def _scatter_kernel(idx_ref, val_ref, o_ref, *, tile):
    h, p, block = idx_ref.shape
    base = pl.program_id(0) * tile
    iota = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0) + base
    partial = None
    for j in range(p):
        # hop multiplicity of each (link, subflow) pair — exact small ints
        hits = (idx_ref[0, j:j + 1, :] == iota).astype(jnp.float32)
        for k in range(1, h):
            hits = hits + (idx_ref[k, j:j + 1, :] == iota).astype(
                jnp.float32)
        val = jnp.broadcast_to(val_ref[j:j + 1, :], (8, block))
        d = jax.lax.dot_general(
            val, hits, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)         # (8, tile)
        partial = d if partial is None else partial + d

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial[0:1, :]


@functools.partial(jax.jit,
                   static_argnames=("n_links", "block", "interpret"))
def link_scatter(hop_idx, sub_vals, n_links: int,
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Offered-load buffer from per-subflow rates.

    hop_idx: (max_hops, n_paths, m) int32 in [0, n_links], hop-major with
    m >= n_flows lanes (-1 hops and lanes past n_flows hold the n_links
    scratch slot — `RouteLayout.hop_idx`); sub_vals: (n_flows, n_paths)
    f32 wire rates.  Returns (n_links + 1,) f32.  `block` is the flow
    count per grid step (rounded up to a multiple of 128; None picks it
    from the lane count).
    """
    h, p, m = hop_idx.shape
    n_cols = n_links + 1
    block = (pick_block(m) if block is None
             else _round_up(block, LANES))
    tile = min(_round_up(n_cols, LANES),
               max(LANES, ONEHOT_BUDGET // block // LANES * LANES))
    cols = _round_up(n_cols, tile)
    idx = _pad_to(hop_idx.astype(jnp.int32), _round_up(m, block), n_links)
    vals = _pad_to(sub_vals.T.astype(jnp.float32), idx.shape[2], 0.0)
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile),
        grid=(cols // tile, idx.shape[2] // block),
        in_specs=[pl.BlockSpec((h, p, block), lambda j, i: (0, 0, i)),
                  pl.BlockSpec((p, block), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, cols), jnp.float32),
        interpret=interpret_mode(interpret),
    )(idx, vals)
    return out[0, :n_cols]


def _gathers_kernel(idx_ref, packed_ref, scale_ref, frac_ref, delay_ref):
    h, p, block = idx_ref.shape
    packed = packed_ref[...]                            # (8, cols)
    iota = jax.lax.broadcasted_iota(jnp.int32, (packed.shape[1], block), 0)
    for j in range(p):
        for k in range(h):
            onehot = (idx_ref[k, j:j + 1, :] == iota).astype(jnp.float32)
            g = jax.lax.dot_general(
                packed, onehot, (((1,), (0,)), ((), ())),
                precision=_HIGHEST,
                preferred_element_type=jnp.float32)     # (8, block)
            if k == 0:
                scale, clean, delay = g[0:1, :], g[1:2, :], g[2:3, :]
            else:
                scale = jnp.minimum(scale, g[0:1, :])
                clean = clean * g[1:2, :]
                delay = delay + g[2:3, :]
        scale_ref[j:j + 1, :] = scale
        frac_ref[j:j + 1, :] = 1.0 - clean
        delay_ref[j:j + 1, :] = delay


@functools.partial(jax.jit,
                   static_argnames=("n_flows", "block", "interpret"))
def link_gathers(hop_idx, scale, clean, delay, n_flows: int,
                 block: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Fused link -> flow pass: all three per-subflow reductions at once.

    hop_idx: the (max_hops, n_paths, m >= n_flows) hop-major index of
    `link_scatter`; scale / clean / delay: (n_links,) f32 per-link values
    (goodput scale cap/load, 1 - mark probability, queue delay q/cap).
    Returns (sub_scale, sub_frac, sub_delay), each (n_flows, n_paths) f32
    — identical contract to ref.fleet_link_gathers_ref.  Raises
    ValueError when one lane tile of the one-hot matrix over every link
    column would not fit ONEHOT_BUDGET.
    """
    n_links = scale.shape[0]
    cols = _round_up(n_links + 1, LANES)
    if LANES * cols > ONEHOT_BUDGET:
        raise ValueError(
            f"link_gathers: {n_links} links exceed the one-hot VMEM budget "
            f"({ONEHOT_BUDGET // LANES - 1} links at one lane tile)")
    h, p, m = hop_idx.shape
    block = (pick_block(m, n_links + 1) if block is None
             else _round_up(block, LANES))
    packed = jnp.zeros((8, cols), jnp.float32)
    packed = packed.at[0, :n_links].set(scale).at[1, :n_links].set(clean)
    packed = packed.at[2, :n_links].set(delay)
    packed = packed.at[0, n_links].set(1.0).at[1, n_links].set(1.0)
    idx = _pad_to(hop_idx.astype(jnp.int32), _round_up(m, block), n_links)
    m_pad = idx.shape[2]
    row = pl.BlockSpec((p, block), lambda i: (0, i))
    out = pl.pallas_call(
        _gathers_kernel,
        grid=(m_pad // block,),
        in_specs=[pl.BlockSpec((h, p, block), lambda i: (0, 0, i)),
                  pl.BlockSpec((8, cols), lambda i: (0, 0))],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((p, m_pad), jnp.float32)] * 3,
        interpret=interpret_mode(interpret),
    )(idx, packed)
    return tuple(o[:, :n_flows].T for o in out)


# ------------------------------------------------ PathTable compressed path
# (raw-array wrappers so repro.fleetsim.links can hand its PathTable fields
# straight in without this module importing links)

def path_rates(pre_id, suf_id, sub_vals, n_segments: int,
               block: Optional[int] = None,
               interpret: Optional[bool] = None):
    """Stage 1: (n_segments + 1,) total subflow rate per unique segment.

    pre_id / suf_id: (n_flows, n_paths) int32 unique-segment ids; sub_vals:
    (n_flows, n_paths) f32 wire rates.  Each subflow contributes its rate
    to BOTH halves' segments — the scatter kernel sees a two-hop
    (2, n_paths, n_flows) index whose link axis is the segment id space
    (no -1s, so the final scratch slot stays 0.0 — stage 2's pad entries
    rely on that).
    """
    ids = jnp.stack([pre_id.T, suf_id.T])
    return link_scatter(ids, sub_vals, n_segments,
                        block=block, interpret=interpret)


def path_table_scatter(pre_id, suf_id, seg_idx, sub_vals, n_links: int,
                       block: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Compressed offered-load scatter: `path_rates` then one scatter of
    the (U, hseg) unique-segment table into links (pad hops already point
    at the n_links scratch slot).  Returns the (n_links + 1,) buffer.
    """
    u = seg_idx.shape[0]
    seg = path_rates(pre_id, suf_id, sub_vals, u,
                     block=block, interpret=interpret)[:u]
    return link_scatter(seg_idx.T[:, None, :], seg[:, None], n_links,
                        block=block, interpret=interpret)


def path_table_gathers(pre_id, suf_id, seg_idx, scale, clean, delay,
                       block: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Compressed link -> flow pass: the fused gather kernel runs once per
    UNIQUE segment over the (hseg, 1, U) table, then two per-subflow takes
    compose the prefix/suffix halves (min of scales, product of the clean
    probabilities, sum of delays).  Same contract as `link_gathers`.
    """
    u = seg_idx.shape[0]
    seg_scale, seg_frac, seg_delay = link_gathers(
        seg_idx.T[:, None, :], scale, clean, delay, u,
        block=block, interpret=interpret)
    seg_scale, seg_delay = seg_scale[:, 0], seg_delay[:, 0]
    seg_clean = 1.0 - seg_frac[:, 0]
    sub_scale = jnp.minimum(seg_scale[pre_id], seg_scale[suf_id])
    sub_frac = 1.0 - seg_clean[pre_id] * seg_clean[suf_id]
    sub_delay = seg_delay[pre_id] + seg_delay[suf_id]
    return sub_scale, sub_frac, sub_delay
