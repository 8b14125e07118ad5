"""Fluid-model topology: links as (n_links,) arrays, routes as a padded
flow -> path -> link hop tensor, and a compiled `RouteLayout` that makes the
per-epoch flow<->link exchange cheap at million-flow scale.

The flow->link incidence is sparse: `routes[i, p, h]` is the h-th link on
flow i's p-th path (-1 padding past the last hop, all-(-1) rows padding past
the last path).  Everything the per-epoch hot path needs from that tensor is
*static per scenario*, so it is compiled ONCE into a `RouteLayout` pytree
(`compute_layout` / `with_layout`, attached by the scenario compiler in
repro.scenarios.compile_fleetsim):

  * `hop_idx` / `path_mask` — the -1-redirected hop indices, hop-major
    and lane-padded (`hop_major_idx`; the per-subflow hop reductions and
    the Pallas kernels read it), and the path validity mask every gather
    consumes (previously re-derived four times per epoch inside the
    `lax.scan` body);
  * a by-link-sorted CSR view of the incidence — `sort_sub` (which subflow
    each route entry belongs to), `sort_link` (its link, ascending),
    `link_ptr` (CSR segment offsets), and `csr_gather` (the same order
    reshaped into an (n_chunks, block) matrix for a blocked cumulative-sum
    aggregation).

On deep-multipath topologies the layout additionally carries a `PathTable`
— a unique-path factorization of the route tensor.  Fat-tree flows re-walk
the same few thousand hop sequences, but full paths barely dedupe (the
first/last hops are host-specific: only ~2.3x at k=8 / 100k flows), so the
table factors every path into a PREFIX and a SUFFIX segment (whole-path
prefix when it fits hseg columns, else split at half its real hop count)
and dedupes the segments: at k=8 / 100k flows the 800k flow-paths share
just ~58k unique segments, and with the all-padding segment's dead entries
dropped the per-epoch entry count shrinks ~5x.  The table stores the per-(flow, path-slot) `pre_id`/`suf_id`
indirection, the unique segment hop rows (`seg_idx`), and two compile-time
sorted blocked-CSR views: subflow -> segment (stage 1) and segment -> link
(stage 2).  Per epoch the compressed hot path is then O(F*P + U*H_seg)
instead of O(F*P*H): segment-sum subflow rates by segment id, scatter the
tiny unique-segment table into links, and run every link -> flow gather
once per unique segment before indexing back per subflow (min composes
exactly across the split; prod/sum regroup within the same ~1e-6 float
tolerance the CSR backend already carries).  `compute_layout` attaches the
table automatically when the routes are concrete AND the factorization
actually compresses (`PT_MIN_COMPRESS`) — single-path and shallow-multipath
dumbbells fail that test (2 hops dedupe to nothing) and stay on the flat
layout, which is also why the flat fields always remain populated: they are
the equivalence oracle the compressed path is tested against.

Per-link aggregation (`offered_load`) then has five jit/vmap-compatible
backends selected by `backend=`:

  * "reference" — the original ravel'd `.at[].add` scatter into an
    `n_links + 1` buffer (the pad slot absorbs the -1s).  Always available,
    needs no layout; XLA lowers it to a serial scatter on CPU.
  * "segment"   — `jax.ops.segment_sum` over the sorted layout with
    `indices_are_sorted=True`.
  * "csr"       — sorted values are cumulative-summed chunk-by-chunk via
    `csr_gather` and differenced at `link_ptr` (a segment sum with no
    scatter at all; the fast CPU path for flat layouts, ~7x the reference
    scatter at 100k flows).  Float summation order differs from the
    scatter, so results match the reference to ~1e-6, not bitwise.
  * "pt"        — the PathTable two-stage aggregation (both stages reuse
    the same blocked-CSR segment sum); needs a layout whose `path_table`
    is attached.  "auto" selects it whenever the table is present.
  * "pallas" / "pt_pallas" — repro.kernels.fleet_pallas runs the flat
    (respectively path-table) scatter and the link->flow gathers as
    blocked one-hot-matmul kernels (compiled on a TPU, the Pallas
    interpreter elsewhere — repro.kernels.interpret_mode decides).

`offered_load(..., axis_name=...)` psums the per-shard partial loads, which
is all `repro.fleetsim.shard` needs to run the flow axis under `shard_map`.
With `halo=B` the collective shrinks to the LAST `B` real links of the
buffer: the locality shard plan (repro.scenarios.plan_shards) relabels link
ids so every cross-shard ("boundary") link sits at the tail of the id
space, making the halo exchange one contiguous-slice psum — shard-private
links are reduced entirely locally by whatever backend is active.

Multipath: each flow carries an (n_paths,) `split` weight vector (rows sum
to 1 over valid paths) and its send rate is divided across its paths — the
fluid analogue of packet spraying / UnoLB subflows.  Every per-flow quantity
(bottleneck scale, mark fraction, queueing delay) exists in a per-subflow
form (`subflow_*`, shape (n_flows, n_paths)) and a split-weighted per-flow
form.  Single-path (n_flows, max_hops) route tables are still accepted and
treated as n_paths == 1.

Queue model per epoch `dt` (forward-Euler on the htsim analogue in
repro.netsim.engine):

  physical:  q' = clip(q + (arrivals - cap)    * dt, 0, qcap)
  phantom:   q' = clip(q + (arrivals - drain)  * dt, 0, vcap)   drain < cap

ECN is the *expectation* of the engine's RED: linear ramp between the
lo/hi thresholds of the marking queue (phantom where attached, else
physical).  A subflow's mark fraction composes independently across hops:
frac = 1 - prod(1 - p_link).  `link_epoch` runs the whole chain — offered
load, queue step, mark probabilities, and the link->flow hop reductions —
against one layout in one call.  On the flat backends every hop reduction
after the load (bottleneck scale, marks, delay, and the loss survivals
where the trace carries them) reads one take of a stacked (K, n_links + 1)
per-link table through `hop_idx`: one gather an epoch, not K.  The
dead-path drain (`faults.degrade_split`) keeps its own gather, since the
split it yields feeds the offered load that pass follows.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

GBPS = 0.125               # bytes per ns per Gbit/s (matches netsim.topology)
RATE_100G = 100 * GBPS
US = 1_000.0
MS = 1_000_000.0
MIB = 1024 * 1024
_EPS = 1e-9

LOAD_BACKENDS = ("auto", "reference", "segment", "csr", "pt",
                 "pallas", "pt_pallas")
CSR_BLOCK = 64             # chunk height of the blocked cumulative sum
# `compute_layout(path_table="auto")` only attaches a PathTable when the
# flat entry count exceeds this multiple of the compressed entry count
# (live stage-1 entries + U*hseg table rows) — below it the two-stage
# pipeline costs more than it saves (dumbbells: 2-hop paths dedupe to
# nothing).
PT_MIN_COMPRESS = 2.0


class PathTable(NamedTuple):
    """Unique-path-segment factorization of the route tensor.

    Every (flow, path-slot) subflow's real hops are split at half their
    count into a PREFIX and a SUFFIX segment (each left-packed into hseg =
    ceil(max_hops / 2) columns, -1-padded) and the 2*S segments are deduped
    to U unique rows.  Shapes: n = n_flows, p = n_paths, S = n*p,
    U = n_segments (possibly padded up so sharded tables stack), L =
    n_links, E1/E2 = block-rounded sorted entry counts of the two stages.
    All arrays are static per scenario — built host-side by
    `compute_path_table` (needs concrete routes).
    """
    pre_id: jnp.ndarray       # (n, p) unique-segment id of each prefix
    suf_id: jnp.ndarray       # (n, p) unique-segment id of each suffix
    seg_idx: jnp.ndarray      # (U, hseg) hop link ids, -1 -> L (scratch)
    seg_gather: jnp.ndarray   # (E1/block, block) subflow ids, by-segment
                              # sorted, one chunk per row; pads -> S
    seg_ptr: jnp.ndarray      # (U + 2,) CSR offsets of stage 1
    lcsr_gather: jnp.ndarray  # (E2/block, block) segment ids, by-link
                              # sorted, one chunk per row; pads -> U
    llink_ptr: jnp.ndarray    # (L + 2,) CSR offsets of stage 2

    @property
    def n_segments(self) -> int:
        return self.seg_idx.shape[0]


class RouteLayout(NamedTuple):
    """Compiled, static per-scenario view of the route tensor.

    Shapes: n = n_flows, p = n_paths, h = max_hops, S = n*p subflows,
    L = n_links, E = the (block-padded, optionally pad-trimmed) entry count.
    All arrays are int32/bool and constant across epochs — compute once per
    scenario (`compute_layout`), thread through FluidNet.
    """
    hop_idx: jnp.ndarray     # (h, p, m >= n) hop link ids, -1 -> L (scratch
                             # slot); lanes past n_flows -> L (hop_major_idx)
    path_mask: jnp.ndarray   # (n, p) bool: True on real paths
    sort_sub: jnp.ndarray    # (E,) subflow id per by-link-sorted entry; pads -> S
    sort_link: jnp.ndarray   # (E,) ascending link id per entry; pads -> L
    link_ptr: jnp.ndarray    # (L + 2,) CSR offsets into the sorted entries
    csr_gather: jnp.ndarray  # (E/block, block) sort_sub, one chunk per row
    path_table: Optional[PathTable] = None  # compressed view (deep multipath)


class FluidNet(NamedTuple):
    """Topology constants.  All (n_links,) float32 except `routes`/`dt`;
    `layout` is the optional compiled RouteLayout (None -> every link op
    falls back to deriving indices from `routes` on the fly).  `p_loss`
    (None on loss-free nets — the default trace carries no loss math) is
    a per-link random per-byte drop probability, modeling corrupting WAN
    segments independently of queue overflow: it thins each subflow's
    delivered fraction AND joins the composed loss signal the reliability
    axis recovers from."""
    cap: jnp.ndarray            # service rate (bytes/ns)
    qcap: jnp.ndarray           # physical queue capacity (bytes)
    ecn_lo: jnp.ndarray         # RED thresholds on the *marking* queue
    ecn_hi: jnp.ndarray
    drain: jnp.ndarray          # phantom drain rate; == cap where no phantom
    vcap: jnp.ndarray           # phantom capacity; == qcap where no phantom
    use_phantom: jnp.ndarray    # bool: mark on phantom (Uno) vs physical RED
    routes: jnp.ndarray         # (n_flows, n_paths, max_hops) int32, -1 pad
    dt: jnp.ndarray             # scalar epoch period (ns)
    layout: Optional[RouteLayout] = None
    p_loss: Optional[jnp.ndarray] = None  # (n_links,) random drop probability

    @property
    def n_links(self) -> int:
        return self.cap.shape[0]

    @property
    def n_paths(self) -> int:
        return self.routes.shape[1] if self.routes.ndim == 3 else 1


class LinkEpoch(NamedTuple):
    """Everything one epoch of link physics produces.

    `p_drop`/`sub_loss` exist only when `link_epoch` ran `with_loss=True`
    (the reliability axis); the default trace never materializes them."""
    load: jnp.ndarray        # (n_links,) offered load
    q_phys: jnp.ndarray      # (n_links,) stepped physical queues
    q_phantom: jnp.ndarray   # (n_links,) stepped phantom queues
    p_link: jnp.ndarray      # (n_links,) expected mark probability
    sub_scale: jnp.ndarray   # (n_flows, n_paths) min over hops of cap/load
    sub_frac: jnp.ndarray    # (n_flows, n_paths) 1 - prod(1 - p) over hops
    sub_delay: jnp.ndarray   # (n_flows, n_paths) sum of q/cap over hops (ns)
    p_drop: Optional[jnp.ndarray] = None    # (n_links,) queue-overflow drop
    sub_loss: Optional[jnp.ndarray] = None  # (n_flows, n_paths) composed loss


def _routes3(net: FluidNet) -> jnp.ndarray:
    """Route tensor normalized to (n_flows, n_paths, max_hops)."""
    r = net.routes
    return r if r.ndim == 3 else r[:, None, :]


def _pad_idx(net: FluidNet) -> jnp.ndarray:
    """(n, p, h) hop indices with -1 redirected to the scratch slot
    n_links (the reference scatter's flow-major view)."""
    r = _routes3(net)
    return jnp.where(r >= 0, r, net.n_links)


def hop_major_idx(routes: jnp.ndarray, n_links: int) -> jnp.ndarray:
    """(max_hops, n_paths, m) int32 hop-major route index, flows on the
    minor (lane) axis: -1 hops and the lanes past n_flows hold the
    scratch slot n_links, and m = `fleet_pallas.pad_lanes(n_flows)` is a
    whole number of the Pallas kernels' flow blocks."""
    from repro.kernels.fleet_pallas import pad_lanes
    r = routes if routes.ndim == 3 else routes[:, None, :]
    n = r.shape[0]
    idx = jnp.transpose(jnp.where(r >= 0, r, n_links).astype(jnp.int32),
                        (2, 1, 0))
    return jnp.pad(idx, ((0, 0), (0, 0), (0, pad_lanes(n) - n)),
                   constant_values=n_links)


def _hop_idx(net: FluidNet) -> jnp.ndarray:
    if net.layout is not None:
        return net.layout.hop_idx
    return hop_major_idx(net.routes, net.n_links)


def hop_reduce(net: FluidNet, vals: jnp.ndarray, reduce) -> jnp.ndarray:
    """(n_flows, n_paths) `reduce` over each subflow's hops of the
    (n_links + 1,) per-link `vals` (the last slot is what padding hops
    read).

    Gathers through the hop-major index, flows on the minor axis: the
    TPU compiler builds that gather in about a second at any fleet size,
    while the same gather through a flow-major (n, p, h) index takes it
    minutes at 100k+ flows.  One gather per call: `link_epoch` reduces
    all of an epoch's rows through `_hop_reduce_many` instead, one take
    for every row."""
    n = net.routes.shape[0]
    return reduce(vals[_hop_idx(net)[..., :n]], axis=0).T


def _hop_reduce_many(net: FluidNet, rows, reduces) -> list:
    """`hop_reduce` of every per-link row in one gather: [(n_flows,
    n_paths) `reduces[k]` over each subflow's hops of `rows[k]`].

    The rows (each (n_links + 1,), identity in the pad slot) stack into a
    (K, n_links + 1) table and ONE take through the hop index gathers all
    K columns: a TPU gather fetches a whole tile per index, so K values an
    index cost about what one does, where K separate gathers pay K tile
    fetches per (hop, path, flow) entry.  Each gathered row then passes a
    barrier before its reduction: where one fused reduction read all K
    rows of the take, the v5e epoch scan answered wrong (PERF.md §6).
    The gathered values and each reduction's axis are those of
    `hop_reduce`, so every column equals its own `hop_reduce` bit for
    bit."""
    if not rows:
        return []
    n = net.routes.shape[0]
    with jax.named_scope("hop_gather"):
        g = jnp.stack(rows)[:, _hop_idx(net)[..., :n]]     # (K, h, p, n)
    g = jax.lax.optimization_barrier(tuple(g[k] for k in range(len(rows))))
    return [reduce(x, axis=0).T for x, reduce in zip(g, reduces)]


def _blocked_csr(sort_key: np.ndarray, sort_val: np.ndarray, n_keys: int,
                 key_pad: int, val_pad: int, block: int):
    """Block-round a by-key-sorted entry list into (gather, ptr) CSR form.

    Pads the tail with (key_pad, val_pad) sentinel entries to a whole
    number of chunks, returns the values reshaped row-per-chunk
    ((n_chunks, block) — each chunk contiguous in memory, so the
    chunk-local prefix sum runs down the fast axis) plus the searchsorted
    offsets of each key in 0..n_keys+1 — the exact inputs
    `_blocked_segment_sum` consumes.
    """
    n = sort_key.shape[0]
    n_chunks = max(1, -(-n // block))
    pad = n_chunks * block - n
    sort_key = np.concatenate([sort_key, np.full(pad, key_pad, np.int32)])
    sort_val = np.concatenate([sort_val, np.full(pad, val_pad, np.int32)])
    ptr = np.searchsorted(
        sort_key, np.arange(n_keys + 2, dtype=np.int64)).astype(np.int32)
    return sort_val.reshape(n_chunks, block), ptr


def compute_path_table(routes, n_links: int, *, block: int = CSR_BLOCK,
                       pad_segments_to: Optional[int] = None,
                       pad_entries_to: Optional[int] = None,
                       min_compress: Optional[float] = None
                       ) -> Optional[PathTable]:
    """Build the unique-path-segment table for a concrete route tensor.

    Each subflow's real hops (the -1 padding may be interspersed) are split
    into a prefix and a suffix, each left-packed into hseg =
    ceil(max_hops/2) columns: paths short enough to fit one segment
    (m <= hseg real hops) go whole into the prefix (their suffix is the
    shared all-padding segment), longer ones split at ceil(m/2).  Both
    halves are deduped together through one np.unique over the (2*S, hseg)
    rows.  Splitting beats deduping full paths because fat-tree first/last
    hops are host-specific: halves shed one host-edge each, so they repeat
    across far more subflows (k=8 / 100k flows: ~58k unique segments vs
    ~350k unique full paths).  Stage-1 entries whose segment is the
    all-padding row are dropped — its rate total only ever lands in the
    scratch slot and its gather row composes the identity, so the entries
    are dead weight (intra-DC paths make them ~1/3 of the total on the
    fat tree).

    `min_compress=r` returns None unless the flat entry count is at least
    r times the compressed one (the auto-attach policy).  `pad_segments_to`
    pads the segment axis with empty all-scratch rows and `pad_entries_to`
    pads stage 1 with sentinel entries (they read the appended 0.0 value
    and sum into the guaranteed-zero final slot) so per-shard tables share
    one (U, E1) and stack into a shard_map operand — empty segments sum to
    0 rate and scatter only into the scratch slot, harmless.
    Host-side only (numpy): call with concrete routes.
    """
    r = np.asarray(routes)
    if r.ndim == 2:
        r = r[:, None, :]
    n, p, h = r.shape
    n_sub = n * p
    hseg = max(1, (h + 1) // 2)
    flat = r.reshape(n_sub, h)
    real = flat >= 0
    m = real.sum(axis=1)
    # prefix hop count: the whole path when it fits, else ceil(m/2)
    c = np.where(m <= hseg, m, (m + 1) // 2)
    rank = np.cumsum(real, axis=1) - 1    # each real hop's index among reals
    pre = np.full((n_sub, hseg), -1, np.int32)
    suf = np.full((n_sub, hseg), -1, np.int32)
    in_pre = real & (rank < c[:, None])
    rows, cols = np.nonzero(in_pre)
    pre[rows, rank[rows, cols]] = flat[rows, cols]
    rows, cols = np.nonzero(real & ~in_pre)
    suf[rows, rank[rows, cols] - c[rows]] = flat[rows, cols]
    seg, inv = np.unique(np.concatenate([pre, suf]), axis=0,
                         return_inverse=True)
    inv = inv.reshape(-1)
    u = seg.shape[0]
    pre_id = inv[:n_sub].astype(np.int32)
    suf_id = inv[n_sub:].astype(np.int32)
    # stage 1: each subflow contributes its rate to BOTH halves' segments,
    # except entries for the all-padding segment (scratch-only — dropped)
    e_sub = np.tile(np.arange(n_sub, dtype=np.int32), 2)
    e_seg = np.concatenate([pre_id, suf_id])
    pad_row = np.nonzero((seg < 0).all(axis=1))[0]
    if pad_row.size:
        live = e_seg != pad_row[0]
        e_sub, e_seg = e_sub[live], e_seg[live]
    if min_compress is not None and \
            n_sub * h < min_compress * (e_seg.shape[0] + u * hseg):
        return None
    n_seg = u if pad_segments_to is None else int(pad_segments_to)
    if n_seg < u:
        raise ValueError(f"pad_segments_to={n_seg} < {u} unique segments")
    seg_idx = np.where(seg >= 0, seg, n_links).astype(np.int32)
    if n_seg > u:
        seg_idx = np.concatenate(
            [seg_idx, np.full((n_seg - u, hseg), n_links, np.int32)])
    if pad_entries_to is not None:
        extra = int(pad_entries_to) - e_seg.shape[0]
        if extra < 0:
            raise ValueError(f"pad_entries_to={pad_entries_to} < "
                             f"{e_seg.shape[0]} live entries")
        e_sub = np.concatenate([e_sub, np.full(extra, n_sub, np.int32)])
        e_seg = np.concatenate([e_seg, np.full(extra, n_seg, np.int32)])
    order = np.argsort(e_seg, kind="stable")
    # sentinel subflow id n_sub reads an appended 0.0; sentinel segment
    # id n_seg lands past every real segment's ptr range
    seg_gather, seg_ptr = _blocked_csr(
        e_seg[order], e_sub[order], n_seg, n_seg, n_sub, block)
    # stage 2: each (segment, hop) entry carries that segment's stage-1
    # rate into its link; pad hops already point at the scratch slot
    e_lnk = seg_idx.reshape(-1)
    e_sid = np.repeat(np.arange(n_seg, dtype=np.int32), hseg)
    order = np.argsort(e_lnk, kind="stable")
    # sentinel segment id n_seg reads the (U+1,)-rate vector's final slot,
    # which stage 1 guarantees to be 0.0
    lcsr_gather, llink_ptr = _blocked_csr(
        e_lnk[order], e_sid[order], n_links, n_links, n_seg, block)
    return PathTable(pre_id=jnp.asarray(pre_id.reshape(n, p)),
                     suf_id=jnp.asarray(suf_id.reshape(n, p)),
                     seg_idx=jnp.asarray(seg_idx),
                     seg_gather=jnp.asarray(seg_gather),
                     seg_ptr=jnp.asarray(seg_ptr),
                     lcsr_gather=jnp.asarray(lcsr_gather),
                     llink_ptr=jnp.asarray(llink_ptr))


def compute_layout(routes: jnp.ndarray, n_links: int, *,
                   block: int = CSR_BLOCK, trim: bool = False,
                   path_table="auto") -> RouteLayout:
    """Compile the route tensor into a RouteLayout.

    jit-compatible with `trim=False` (repro.fleetsim.shard builds per-shard
    layouts inside shard_map).  `trim=True` drops the -1 padding entries
    from the sorted view before block-rounding — cheaper when the route
    tensor is mostly padding (e.g. single-path flows in a wide multipath
    net) — but needs concrete routes (host-side only), and layouts with
    different trimmed sizes cannot be stacked into one sweep grid.

    `path_table` controls the compressed unique-path view: "auto" (the
    default) attaches one when the routes are concrete AND the
    factorization compresses by at least PT_MIN_COMPRESS (inside jit, or
    on dumbbell-shallow routes, the layout stays flat); True forces the
    build (concrete routes required); False skips it; a prebuilt
    `PathTable` is attached as-is (the sharded pad-to-common-U path).
    """
    r = routes if routes.ndim == 3 else routes[:, None, :]
    n, p, h = r.shape
    n_sub = n * p
    pad_idx = jnp.where(r >= 0, r, n_links).astype(jnp.int32)
    hop_mask = r >= 0
    path_mask = jnp.any(hop_mask, axis=2)

    flat_link = pad_idx.reshape(-1)
    flat_sub = (jnp.arange(n_sub * h, dtype=jnp.int32) // h)
    order = jnp.argsort(flat_link, stable=True)
    sort_link = flat_link[order]
    sort_sub = flat_sub[order]
    keep = flat_link.shape[0]
    if trim:
        n_real = int(jnp.sum(hop_mask))          # host-side only
        keep = n_real
        sort_link = sort_link[:keep]
        sort_sub = sort_sub[:keep]
    n_chunks = max(1, -(-keep // block))
    pad_to = n_chunks * block
    sort_link = jnp.concatenate(
        [sort_link, jnp.full(pad_to - keep, n_links, jnp.int32)])
    sort_sub = jnp.concatenate(
        [sort_sub, jnp.full(pad_to - keep, n_sub, jnp.int32)])
    link_ptr = jnp.searchsorted(
        sort_link, jnp.arange(n_links + 2, dtype=jnp.int32)).astype(jnp.int32)
    csr_gather = sort_sub.reshape(n_chunks, block)
    concrete = not isinstance(routes, jax.core.Tracer)
    if path_table is None or path_table is False:
        pt = None
    elif isinstance(path_table, PathTable):
        pt = path_table
    elif path_table is True:
        if not concrete:
            raise ValueError("path_table=True needs concrete routes "
                             "(host-side compute_layout call)")
        pt = compute_path_table(routes, n_links, block=block)
    elif path_table == "auto":
        pt = compute_path_table(routes, n_links, block=block,
                                min_compress=PT_MIN_COMPRESS) \
            if concrete else None
    else:
        raise ValueError(f"path_table={path_table!r}: expected 'auto', "
                         "True, False/None, or a PathTable")
    return RouteLayout(hop_idx=hop_major_idx(routes, n_links),
                       path_mask=path_mask, sort_sub=sort_sub,
                       sort_link=sort_link, link_ptr=link_ptr,
                       csr_gather=csr_gather, path_table=pt)


def with_layout(net: FluidNet, **kw) -> FluidNet:
    """Return `net` with a freshly compiled layout attached (recompile after
    any change to `routes`; stale layouts silently misroute load)."""
    return net._replace(layout=compute_layout(net.routes, net.n_links, **kw))


def layout_to_arrays(lay: RouteLayout, prefix: str = "lay_") -> dict:
    """RouteLayout -> {name: np.ndarray}, ready for an allow_pickle=False
    `np.savez`.  The optional nested PathTable's fields ride under
    `<prefix>pt_` (absent keys mean the layout was flat)."""
    out = {prefix + f: np.asarray(getattr(lay, f))
           for f in RouteLayout._fields if f != "path_table"}
    if lay.path_table is not None:
        out.update({prefix + "pt_" + f: np.asarray(getattr(lay.path_table, f))
                    for f in PathTable._fields})
    return out


def layout_from_arrays(arrays, prefix: str = "lay_") -> \
        Optional[RouteLayout]:
    """Inverse of `layout_to_arrays`; `arrays` is any mapping (e.g. an
    open NpzFile).  Returns None when no layout was serialized — the
    round trip preserves "no layout" as well as flat vs PathTable'd."""
    if prefix + "hop_idx" not in arrays:
        return None
    pt = None
    if prefix + "pt_pre_id" in arrays:
        pt = PathTable(**{f: jnp.asarray(arrays[prefix + "pt_" + f])
                          for f in PathTable._fields})
    return RouteLayout(
        **{f: jnp.asarray(arrays[prefix + f])
           for f in RouteLayout._fields if f != "path_table"},
        path_table=pt)


def path_mask(net: FluidNet) -> jnp.ndarray:
    """(n_flows, n_paths) bool: True where the path slot holds a real path."""
    if net.layout is not None:
        return net.layout.path_mask
    return jnp.any(_routes3(net) >= 0, axis=2)


def uniform_split(net: FluidNet) -> jnp.ndarray:
    """(n_flows, n_paths) equal weights over each flow's valid paths."""
    m = path_mask(net).astype(jnp.float32)
    return m / jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)


def normalize_split(w: jnp.ndarray, mask: jnp.ndarray,
                    w_floor: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Project weights back onto the simplex over valid paths.

    `w_floor` (per-flow, fraction of the uniform weight) keeps a probe
    trickle on every valid path so a repathed/zeroed path can recover —
    the fluid analogue of UnoLB keeping subflows alive on proven paths
    while occasionally re-testing the rest.
    """
    m = mask.astype(w.dtype)
    w = jnp.maximum(w, 0.0) * m
    if w_floor is not None:
        n_valid = jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
        w = jnp.maximum(w, (w_floor[:, None] / n_valid) * m)
    s = jnp.sum(w, axis=1, keepdims=True)
    uni = m / jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
    return jnp.where(s > _EPS, w / jnp.maximum(s, _EPS), uni)


def _split_or_uniform(net: FluidNet, split) -> jnp.ndarray:
    return uniform_split(net) if split is None else split


# ------------------------------------------------------- flow -> link scatter

def _offered_load_reference(net: FluidNet, rates, split) -> jnp.ndarray:
    """Original ravel'd scatter-add (the pad slot absorbs -1 hops)."""
    hop_mask = (_routes3(net) >= 0).astype(rates.dtype)
    per_hop = (rates[:, None] * split)[:, :, None] * hop_mask
    buf = jnp.zeros(net.n_links + 1, rates.dtype)
    buf = buf.at[_pad_idx(net).ravel()].add(per_hop.ravel())
    return buf


def _offered_load_segment(net: FluidNet, rates, split) -> jnp.ndarray:
    """jax.ops.segment_sum over the by-link-sorted layout."""
    lay = net.layout
    vals = _take_subflows(rates, split, lay.sort_sub)
    return jax.ops.segment_sum(vals, lay.sort_link,
                               num_segments=net.n_links + 1,
                               indices_are_sorted=True)


def _blocked_segment_sum(entries: jnp.ndarray,
                         ptr: jnp.ndarray) -> jnp.ndarray:
    """(len(ptr) - 1,) segment totals of the sorted `entries`.

    `entries` is the (n_chunks, block) row-per-chunk matrix of sorted
    entry values, gathered through a `_blocked_csr` gather matrix (block
    padding reads 0.0); `ptr` holds each output segment's CSR offsets in
    the underlying sorted order.  Entries are chunk-contiguous and
    prefix-summed along the fast block axis (XLA's native cumsum on
    the contiguous minor axis beats a Hillis-Steele doubling pass here —
    the doubling's log2(block) concatenate copies cost more than they
    save); each segment total is then assembled from CHUNK-LOCAL pieces —
    the partial head/tail chunks by differencing the local prefix, the
    interior chunks by a scatter-add of whole-chunk totals
    (n_chunks = n_entries / block values, block x fewer than a per-entry
    scatter).

    Differencing one *global* running prefix instead would be cheaper
    still, but its absolute error is ulp(grand total) per segment — at 1M
    flows that is ~10% relative error on a lightly loaded uplink.  All
    pieces here are bounded by the segment's own magnitude (or one
    chunk's), so per-segment relative error stays at float32 rounding
    scale.
    """
    n_chunks, block = entries.shape
    cs = jnp.cumsum(entries, axis=1)              # chunk-local prefixes
    chunk_tot = cs[:, -1]

    a = ptr[:-1]                                  # segment starts
    b = ptr[1:]                                   # segment ends (exclusive)
    ca, ra = a // block, a % block
    cb, rb = (b - 1) // block, (b - 1) % block    # last entry (b > a only)
    # local prefix of entries < position: 0 at a chunk's first slot
    head = jnp.where(ra > 0, cs[ca, ra - 1], 0.0)   # before the segment
    tail = cs[cb, rb]                               # through its last entry
    same = ca == cb
    out = jnp.where(same, tail - head,
                    (chunk_tot[ca] - head) + tail)
    # interior chunks (strictly between a segment's first and last chunk)
    # contribute whole chunk_tots via a tiny scatter over n_chunks values
    first = jnp.arange(n_chunks, dtype=ptr.dtype) * block
    owner = jnp.searchsorted(ptr, first, side="right") - 1
    owner = jnp.clip(owner, 0, ptr.shape[0] - 2)
    interior = (jnp.arange(n_chunks) > ca[owner]) & \
        (jnp.arange(n_chunks) < cb[owner])
    out = out.at[owner].add(jnp.where(interior, chunk_tot, 0.0),
                            indices_are_sorted=True)
    return jnp.where(b > a, out, 0.0)


def _take_subflows(rates, split, ids) -> jnp.ndarray:
    """rates[f] * split[f, j] at flow-major subflow ids (f * n_paths + j),
    shaped like `ids`; the block-pad sentinel id n_flows * n_paths reads
    0.0.  Indexes the (n_flows, n_paths) matrix directly: flattening it
    is a relayout whose TPU compile time grows with n_flows (minutes at
    1M flows)."""
    n, p = split.shape
    f, j = ids // p, ids % p
    live = f < n
    f = jnp.where(live, f, 0)
    return jnp.where(live, rates[f] * split[f, j], 0.0)


def _offered_load_csr(net: FluidNet, rates, split) -> jnp.ndarray:
    """Blocked cumulative-sum segment reduction over the flat sorted layout
    (see `_blocked_segment_sum`); returns the (n_links + 1,) load buffer."""
    lay = net.layout
    return _blocked_segment_sum(_take_subflows(rates, split, lay.csr_gather),
                                lay.link_ptr)


def _pt_seg_rates(pt: PathTable, rates, split) -> jnp.ndarray:
    """Stage 1: (U + 1,) total subflow rate traversing each unique segment
    (every subflow contributes to BOTH its prefix and suffix segment).
    The final slot is the stage-1 block-pad sentinel segment and is
    guaranteed 0.0 — stage 2's own pad entries read it."""
    return _blocked_segment_sum(_take_subflows(rates, split, pt.seg_gather),
                                pt.seg_ptr)


def _offered_load_pt(net: FluidNet, rates, split) -> jnp.ndarray:
    """Two-stage PathTable aggregation: segment-sum rates by unique
    segment (O(S) entries, no hop axis), then push the tiny (U, hseg)
    table into links (O(U*hseg) entries) — both through the same
    blocked-CSR reduction the flat backend uses."""
    pt = net.layout.path_table
    seg = _pt_seg_rates(pt, rates, split)
    return _blocked_segment_sum(seg[pt.lcsr_gather], pt.llink_ptr)


def _resolve_backend(net: FluidNet, backend: str) -> str:
    if backend not in LOAD_BACKENDS:
        raise ValueError(f"unknown link-aggregation backend {backend!r}")
    lay = net.layout
    if backend == "auto":
        if lay is None:
            return "reference"
        return "pt" if lay.path_table is not None else "csr"
    if backend in ("segment", "csr") and lay is None:
        raise ValueError(f"backend {backend!r} needs a RouteLayout "
                         "(links.with_layout)")
    if backend in ("pt", "pt_pallas") and \
            (lay is None or lay.path_table is None):
        raise ValueError(f"backend {backend!r} needs a PathTable "
                         "(links.with_layout(net, path_table=True))")
    return backend


def halo_exchange(buf: jnp.ndarray, n_links: int, axis_name: str,
                  halo: Optional[int],
                  nbr: Optional[jnp.ndarray] = None,
                  n_shards: Optional[int] = None) -> jnp.ndarray:
    """Cross-shard reduction of a partial (n_links + 1,) link buffer.

    `halo=None` psums the whole buffer (every link potentially shared — the
    PR-3 behavior).  `halo=B` psums only the LAST `B` real links: under a
    locality shard plan (repro.scenarios.plan_shards) those are exactly the
    boundary links touched by more than one shard, everything below them is
    shard-private and already globally correct, and the scratch slot is
    never read.  `halo=0` means no link is shared — no collective at all.

    `nbr` switches the boundary reduction from the all-to-all psum to a
    ppermute NEIGHBOR exchange — legal when every boundary link is touched
    by exactly one RING-ADJACENT shard pair (a DC-major plan on a ring /
    full-mesh multi-DC topology; repro.fleetsim.shard.neighbor_halo builds
    the operand and checks legality).  `nbr` is this shard's (2, P) slice
    of the stacked (n_shards, 2, P) index table: row 0 lists the boundary
    links shared with the RIGHT neighbor (pair group p on shard p), row 1
    those shared with the LEFT (group p-1), both padded with `n_links`
    (the scratch slot).  Group p's positions agree between shard p's row 0
    and shard p+1's row 1 — both are built from one global group list — so
    each shard sends two (P,) buffers and adds exactly its partner's
    partials.  Every touched link then carries the full two-shard sum
    (bit-equal to the psum: the other shards' psum contributions are exact
    +0.0), links of OTHER pair groups stay stale, and no local flow reads
    them — the same staleness contract as the psum tail.  Requires
    `n_shards` (static) for the permutation tables.
    """
    if nbr is not None:
        if n_shards is None:
            raise ValueError("neighbor halo exchange needs n_shards")
        idx_r, idx_l = nbr[0], nbr[1]
        to_left = [(p, (p - 1) % n_shards) for p in range(n_shards)]
        to_right = [(p, (p + 1) % n_shards) for p in range(n_shards)]
        from_right = jax.lax.ppermute(buf[idx_l], axis_name, to_left)
        from_left = jax.lax.ppermute(buf[idx_r], axis_name, to_right)
        return buf.at[idx_r].add(from_right).at[idx_l].add(from_left)
    if halo is None:
        return jax.lax.psum(buf, axis_name)
    if halo == 0:
        return buf
    lo = n_links - halo
    shared = jax.lax.psum(jax.lax.slice_in_dim(buf, lo, n_links), axis_name)
    return jnp.concatenate([buf[:lo], shared, buf[n_links:]])


@jax.named_scope("fleetsim.offered_load")
def offered_load(net: FluidNet, rates: jnp.ndarray,
                 split: Optional[jnp.ndarray] = None, *,
                 axis_name: Optional[str] = None,
                 backend: str = "auto",
                 halo: Optional[int] = None,
                 block: Optional[int] = None,
                 nbr: Optional[jnp.ndarray] = None,
                 n_shards: Optional[int] = None) -> jnp.ndarray:
    """(n_links,) aggregate arrival rate from per-flow send rates.

    With a split matrix, flow i contributes rates[i] * split[i, p] to every
    hop of its p-th path.  All backends agree on the returned real links;
    the internal pad slot is backend-specific (the reference scatter masks
    -1 hops to zero, so only IT conserves total scatter mass across
    links + pad slot — the layout/Pallas paths park the subflow's rate
    there).  `axis_name` reduces the per-shard partial loads across a
    sharded flow axis (repro.fleetsim.shard): the full buffer when
    `halo=None`, only the trailing `halo` boundary links otherwise (see
    `halo_exchange`; `nbr`/`n_shards` switch the boundary reduction to
    the ppermute neighbor exchange).  On a locality-sharded run the
    returned loads are
    globally correct ONLY on this shard's own links plus the boundary
    tail — exactly the links its flows can read.  `backend` picks the
    aggregation implementation (see module docstring); "auto" uses the
    PathTable pipeline when the layout carries one, else the blocked-CSR
    path whenever a layout is attached.  `block` overrides the Pallas
    subflow-block size (None picks it from the route size); the Pallas
    backends compile on a TPU and interpret elsewhere
    (repro.kernels.interpret_mode).
    """
    split = _split_or_uniform(net, split)
    backend = _resolve_backend(net, backend)
    if backend == "pallas":
        from repro.kernels import fleet_pallas
        buf = fleet_pallas.link_scatter(
            _hop_idx(net), rates[:, None] * split, net.n_links, block=block)
    elif backend == "pt_pallas":
        from repro.kernels import fleet_pallas
        pt = net.layout.path_table
        buf = fleet_pallas.path_table_scatter(
            pt.pre_id, pt.suf_id, pt.seg_idx, rates[:, None] * split,
            net.n_links, block=block)
    elif backend == "pt":
        buf = _offered_load_pt(net, rates, split)
    elif backend == "segment":
        buf = _offered_load_segment(net, rates, split)
    elif backend == "csr":
        buf = _offered_load_csr(net, rates, split)
    else:
        buf = _offered_load_reference(net, rates, split)
    if axis_name is not None:
        buf = halo_exchange(buf, net.n_links, axis_name, halo,
                            nbr=nbr, n_shards=n_shards)
    return buf[:net.n_links]


# ------------------------------------------------------- link -> flow gathers
# (one hop-major gather + hop-axis reduce each, through `hop_reduce`;
# `link_epoch` gathers all of an epoch's rows at once, `_hop_reduce_many`)
# Per-link rows, padded with the identity of their hop reduction: padding
# hops read the last slot.

def _scale_row(net: FluidNet, load: jnp.ndarray) -> jnp.ndarray:
    """min(1, cap/load) per link; pad slot 1.0 (no constraint)."""
    s = jnp.minimum(1.0, net.cap / jnp.maximum(load, _EPS))
    return jnp.concatenate([s, jnp.ones(1, s.dtype)])


def _survival_row(p: jnp.ndarray) -> jnp.ndarray:
    """1 - p per link; pad slot 1.0 (the hop product's identity)."""
    return jnp.concatenate([1.0 - p, jnp.ones(1, p.dtype)])


def _delay_row(net: FluidNet, q_phys: jnp.ndarray) -> jnp.ndarray:
    """q/cap per link; pad slot 0.0 (the hop sum's identity)."""
    return jnp.concatenate([q_phys / jnp.maximum(net.cap, _EPS),
                            jnp.zeros(1, q_phys.dtype)])


def subflow_scale(net: FluidNet, load: jnp.ndarray) -> jnp.ndarray:
    """(n_flows, n_paths) goodput/offered ratio: min over hops of cap/load.

    FIFO fluid approximation — an overloaded link serves flows
    proportionally to their arrival rates.  Padding paths report 1.0
    (harmless: their split weight is 0).
    """
    return hop_reduce(net, _scale_row(net, load), jnp.min)


def bottleneck_scale(net: FluidNet, load: jnp.ndarray,
                     split: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(n_flows,) goodput/offered ratio, split-weighted across paths."""
    split = _split_or_uniform(net, split)
    return jnp.sum(split * subflow_scale(net, load), axis=1)


def step_queues(net: FluidNet, q_phys: jnp.ndarray, q_phantom: jnp.ndarray,
                load: jnp.ndarray):
    """One forward-Euler epoch of both queue families."""
    q_phys = jnp.clip(q_phys + (load - net.cap) * net.dt, 0.0, net.qcap)
    q_phantom = jnp.clip(q_phantom + (load - net.drain) * net.dt,
                         0.0, net.vcap)
    return q_phys, q_phantom


def drop_prob(net: FluidNet, q_phys_prev: jnp.ndarray,
              load: jnp.ndarray) -> jnp.ndarray:
    """(n_links,) per-byte drop probability from physical-queue overflow.

    The pre-clip excess of `step_queues` — bytes the queue could not
    absorb this epoch — as a fraction of the bytes that arrived:
    max(q + (load - cap) * dt - qcap, 0) / (load * dt), clipped to [0, 1].
    This is the loss signal the reliability axis composes along paths
    (repro.fleetsim.reliability); it is exactly 0.0 whenever the queue
    stays within capacity.  At saturation (full queue, load > cap) it
    approaches 1 - cap/load — consistent with the FIFO goodput scale.
    """
    over = q_phys_prev + (load - net.cap) * net.dt - net.qcap
    return jnp.clip(jnp.maximum(over, 0.0) /
                    jnp.maximum(load * net.dt, _EPS), 0.0, 1.0)


def subflow_loss_frac(net: FluidNet, p_drop: jnp.ndarray) -> jnp.ndarray:
    """(n_flows, n_paths) loss fraction: 1 - prod over hops of (1 - p).

    Same hop composition as `subflow_mark_frac`, on the overflow drop
    probabilities instead of the RED marks."""
    return 1.0 - hop_reduce(net, _survival_row(p_drop), jnp.prod)


def _pt_gathers(net: FluidNet, load, p_link, q_phys):
    """The three link->flow gathers through the PathTable: each reduction
    (min of cap/load, prod of 1-p, sum of q/cap) runs once per UNIQUE
    segment over the (U, hseg) table, then two (n, p) takes compose the
    prefix and suffix halves per subflow.  min composes exactly under the
    split; prod/sum merely regroup, staying within the backends' shared
    ~1e-6 float tolerance.  Pad hops read the appended identity slot
    (1.0 / 1.0 / 0.0 — valid because scale <= 1)."""
    pt = net.layout.path_table
    s = _scale_row(net, load)
    clean = _survival_row(p_link)
    d = _delay_row(net, q_phys)
    seg_scale = jnp.min(s[pt.seg_idx], axis=1)       # (U,)
    seg_clean = jnp.prod(clean[pt.seg_idx], axis=1)
    seg_delay = jnp.sum(d[pt.seg_idx], axis=1)
    sub_scale = jnp.minimum(seg_scale[pt.pre_id], seg_scale[pt.suf_id])
    sub_frac = 1.0 - seg_clean[pt.pre_id] * seg_clean[pt.suf_id]
    sub_delay = seg_delay[pt.pre_id] + seg_delay[pt.suf_id]
    return sub_scale, sub_frac, sub_delay


def _pt_loss_frac(net: FluidNet, p_drop: jnp.ndarray) -> jnp.ndarray:
    """`subflow_loss_frac` through the PathTable: survival products per
    unique segment, composed per subflow across the prefix/suffix split."""
    pt = net.layout.path_table
    seg_keep = jnp.prod(_survival_row(p_drop)[pt.seg_idx], axis=1)
    return 1.0 - seg_keep[pt.pre_id] * seg_keep[pt.suf_id]


def mark_prob(net: FluidNet, q_phys: jnp.ndarray,
              q_phantom: jnp.ndarray) -> jnp.ndarray:
    """(n_links,) expected RED mark probability on the marking queue."""
    q = jnp.where(net.use_phantom, q_phantom, q_phys)
    return jnp.clip((q - net.ecn_lo) /
                    jnp.maximum(net.ecn_hi - net.ecn_lo, _EPS), 0.0, 1.0)


def subflow_mark_frac(net: FluidNet, p_link: jnp.ndarray) -> jnp.ndarray:
    """(n_flows, n_paths) mark fraction: 1 - prod over hops of (1 - p)."""
    return 1.0 - hop_reduce(net, _survival_row(p_link), jnp.prod)


def path_mark_frac(net: FluidNet, p_link: jnp.ndarray,
                   split: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(n_flows,) mark fraction of the flow's bytes, split-weighted."""
    split = _split_or_uniform(net, split)
    return jnp.sum(split * subflow_mark_frac(net, p_link), axis=1)


def subflow_delay(net: FluidNet, q_phys: jnp.ndarray) -> jnp.ndarray:
    """(n_flows, n_paths) relative queueing delay: sum of q/cap (ns).

    The capacity floor keeps a faulted (cap == 0) link's delay finite —
    huge, which correctly saturates the delay-gated reactions, but never
    NaN/Inf in the carry (repro.fleetsim.faults)."""
    return hop_reduce(net, _delay_row(net, q_phys), jnp.sum)


def path_delay(net: FluidNet, q_phys: jnp.ndarray,
               split: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(n_flows,) relative queueing delay, split-weighted across paths."""
    split = _split_or_uniform(net, split)
    return jnp.sum(split * subflow_delay(net, q_phys), axis=1)


def link_epoch(net: FluidNet, rates: jnp.ndarray, split: jnp.ndarray,
               q_phys: jnp.ndarray, q_phantom: jnp.ndarray, *,
               axis_name: Optional[str] = None,
               backend: str = "auto",
               halo: Optional[int] = None,
               block: Optional[int] = None,
               with_loss: bool = False,
               nbr: Optional[jnp.ndarray] = None,
               n_shards: Optional[int] = None) -> LinkEpoch:
    """One epoch of link physics in one call: offered load -> queue step ->
    mark probabilities -> the link->flow hop reductions.

    Every hop reduction here reads only values known once the load is:
    cap/load (min), 1 - p_link (prod), q/cap (sum), and the survivals
    1 - p of `p_loss` and of the composed `p_drop` (prod).  On the flat
    backends ("reference", "segment", "csr") their per-link rows stack
    into one (K, n_links + 1) table that ONE take through `hop_idx`
    gathers (`_hop_reduce_many`, under the "hop_gather" scope), K = 3 to
    5 as the trace carries the loss rows; the result equals the separate
    `subflow_*` gathers bit for bit.  (`faults.degrade_split` gathers
    apart: its split feeds the offered load this pass follows.)  With
    `backend="pallas"` the three reductions run as one fused kernel pass
    over the route tensor (repro.kernels.fleet_pallas.link_gathers) and
    the loss rows share one take, and with the
    PathTable backends ("pt" / "pt_pallas", also what "auto" picks when
    the layout carries a table) each gather reduces once per UNIQUE path
    segment before two per-subflow takes compose the halves — including
    the `p_loss` thinning and the `with_loss` composition.  `halo` restricts
    the sharded reduction to the trailing boundary links (see
    `offered_load`); queue/mark state on links outside this shard's reach
    is then stale, but no local flow reads it.

    `with_loss=True` (a trace-time flag — the default trace pays zero
    overhead) additionally computes the queue-overflow drop probabilities
    from the PRE-step queues and composes them per subflow
    (`p_drop`/`sub_loss`) for the reliability axis.  Under sharding this
    needs no extra exchange: p_drop reads the carried queues and post-halo
    loads, both already correct on every link a local flow touches.

    A net with `p_loss` (configured random loss) additionally thins
    `sub_scale` by each subflow's survival through its lossy hops —
    bytes dropped at random never reach the receiver even on
    under-capacity links, unlike overflow loss which the FIFO cap/load
    scale already excludes — and `with_loss` folds the random drops into
    the composed `p_drop`/`sub_loss` loss signal.
    """
    q_prev = q_phys
    rb = _resolve_backend(net, backend)
    load = offered_load(net, rates, split, axis_name=axis_name,
                        backend=rb, halo=halo, block=block,
                        nbr=nbr, n_shards=n_shards)
    with jax.named_scope("fleetsim.link_gathers"):
        q_phys, q_phantom = step_queues(net, q_phys, q_phantom, load)
        p_link = mark_prob(net, q_phys, q_phantom)
        p_drop = None
        if with_loss:
            p_drop = drop_prob(net, q_prev, load)
            if net.p_loss is not None:
                p_drop = 1.0 - (1.0 - p_drop) * (1.0 - net.p_loss)
        # the loss rows: the random-loss thinning, then the composed loss
        lossy = [p for p in (net.p_loss, p_drop) if p is not None]
        if rb == "pallas":
            from repro.kernels import fleet_pallas
            sub_scale, sub_frac, sub_delay = fleet_pallas.link_gathers(
                _hop_idx(net),
                jnp.minimum(1.0, net.cap / jnp.maximum(load, _EPS)),
                1.0 - p_link, q_phys / jnp.maximum(net.cap, _EPS),
                net.routes.shape[0], block=block)
            keeps = _hop_reduce_many(net, [_survival_row(p) for p in lossy],
                                     [jnp.prod] * len(lossy))
        elif rb == "pt_pallas":
            from repro.kernels import fleet_pallas
            pt = net.layout.path_table
            sub_scale, sub_frac, sub_delay = \
                fleet_pallas.path_table_gathers(
                    pt.pre_id, pt.suf_id, pt.seg_idx,
                    jnp.minimum(1.0, net.cap / jnp.maximum(load, _EPS)),
                    1.0 - p_link, q_phys / jnp.maximum(net.cap, _EPS),
                    block=block)
        elif rb == "pt":
            sub_scale, sub_frac, sub_delay = _pt_gathers(net, load, p_link,
                                                         q_phys)
        else:
            sub_scale, keep, sub_delay, *keeps = _hop_reduce_many(
                net, [_scale_row(net, load), _survival_row(p_link),
                      _delay_row(net, q_phys)]
                + [_survival_row(p) for p in lossy],
                [jnp.min, jnp.prod, jnp.sum] + [jnp.prod] * len(lossy))
            sub_frac = 1.0 - keep
        if rb in ("pt", "pt_pallas"):
            loss_fracs = [_pt_loss_frac(net, p) for p in lossy]
        else:
            loss_fracs = [1.0 - k for k in keeps]
        if net.p_loss is not None:
            sub_scale = sub_scale * (1.0 - loss_fracs[0])
        sub_loss = loss_fracs[-1] if with_loss else None
    return LinkEpoch(load=load, q_phys=q_phys, q_phantom=q_phantom,
                     p_link=p_link, sub_scale=sub_scale, sub_frac=sub_frac,
                     sub_delay=sub_delay, p_drop=p_drop, sub_loss=sub_loss)


# -------------------------------------------------------------------- builders

def dumbbell(n_intra: int, n_inter: int, *, rate: float = RATE_100G,
             intra_rtt: float = 14 * US, inter_rtt: float = 2 * MS,
             qcap: float = 1 * MIB, n_wan: int = 8, n_bottleneck: int = 1,
             phantom: bool = True, drain_frac: float = 0.9,
             cap_bdps: float = 1.0, min_frac: float = 0.05,
             max_frac: float = 0.35, red_lo_frac: float = 0.25,
             red_hi_frac: float = 0.75, epoch_period_frac: float = 1.0,
             multipath: bool = False):
    """Fluid mirror of netsim.topology.Dumbbell (+ attach_phantoms defaults).

    Thin wrapper over the shared scenario layer: builds
    `repro.scenarios.dumbbell_scenario` and compiles it with
    `repro.scenarios.fleet_arrays` — netsim and fleetsim construct the same
    dumbbell from one spec.  The returned net carries a compiled
    RouteLayout.

    Flow -> downlink convention (standardized by the scenario layer, shared
    with the netsim compiler): flows are numbered globally with intra flows
    first, then inter flows, and flow i sends to downlink i % n_bottleneck.

    `multipath=False` (default): the n_wan border links appear as ONE
    aggregated WAN pipe (packet-sprayed inter flows see their sum) and every
    flow has a single path.  `multipath=True`: the WAN stays n_wan separate
    links and each inter flow gets one path per WAN link (UnoLB subflows).

    Returns (FluidNet, bdp (n_flows,), rtt (n_flows,)); routes are
    (n_flows, n_paths, 2) with n_paths == 1 unless `multipath`.
    """
    from repro.scenarios import dumbbell_scenario, fleet_arrays
    spec = dumbbell_scenario(
        n_intra, n_inter, rate=rate, intra_rtt=intra_rtt,
        inter_rtt=inter_rtt, qcap=qcap, n_wan=n_wan,
        n_bottleneck=n_bottleneck, phantom=phantom, drain_frac=drain_frac,
        cap_bdps=cap_bdps, min_frac=min_frac, max_frac=max_frac,
        red_lo_frac=red_lo_frac, red_hi_frac=red_hi_frac,
        epoch_period_frac=epoch_period_frac, multipath=multipath)
    net, bdp, rtt, _ = fleet_arrays(spec)
    return net, bdp, rtt
