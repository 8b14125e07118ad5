"""Dynamic EC + NACK loss-recovery state machine, vectorized per flow.

The paper's inter-DC reliability layer (§4.2: UnoRC erasure coding plus
NACK-driven retransmission) previously existed in the fluid model only as a
static k/(k+r) goodput tax; every recovery *dynamic* — retransmit traffic
re-congesting links, recovery-induced rate dips, parity amortizing tail
loss — lived solely in the dozens-of-flows packet simulator
(repro.netsim.protocol).  This module makes those dynamics sweepable at
fleet scale: pure (n_flows,) array math that runs inside the jitted
`lax.scan` step (repro.fleetsim.cc.make_step), with the packet simulator's
EC+NACK machinery as the cross-validation oracle
(repro.fleetsim.validate.compare_recovery_steady_state).

Loss signal.  Per epoch, each link's drop probability is the fraction of
arriving bytes its physical queue could not absorb:

    p_drop = max(q + (load - cap) * dt - qcap, 0) / (load * dt)

(the pre-clip overflow of links.step_queues).  A subflow's loss fraction
composes over hops exactly like the mark fraction — 1 - prod(1 - p_drop) —
and a flow's loss fraction `q` is the split-weighted sum over its paths
(links.link_epoch with `with_loss=True`).  At a saturated link with a full
queue this reproduces 1 - cap/load, consistent with the FIFO service
fraction the goodput scale already models.

EC recovery split.  A flow's wire stream is framed into blocks of
k data + r parity packets (MDS: any k of n = k+r decode).  With per-packet
loss prob q, losses per block X ~ Binomial(n, q); a block with X <= r
decodes locally (zero retransmits), X > r triggers the NACK path for the
X - r-ish missing data.  Exactly (in expectation, per wire byte sent):

    recovered  = E[X * 1(X <= r)] * k / n^2      (parity absorbs the loss)
    nack_bytes = E[X * 1(X >  r)] * k / n^2      (data needing retransmit)

with the complement identity E[X * 1(X > r)] = n*q - sum_{i=1..r} i*P(X=i)
needing only r+1 pmf terms — the binomial coefficients are per-flow
constants precomputed in `make_rel_params` (coef[:, i] = C(n, i) for
i <= r, else 0), so the per-epoch cost is one (n_flows, MAX_R+1)
elementwise block.  The two terms sum to q * k/n (all lost data), and both
are EXACTLY 0.0 at q == 0 (0^i powers), which is what makes the
no-loss trace bit-identical to the static-EC path.

NACK state machine (per flow, modeled on the packet receiver's block
timers + the SmartAckNack batching/debounce idiom):

    pending  bytes lost beyond parity, detected at the receiver but not
             yet NACKed (cumulative-ACK batching: NACK opportunities come
             only every `nack_period` epochs — the ACK-batch clock);
    backlog  bytes NACKed, awaiting retransmission at the sender;
    ack_cd   countdown to the next cumulative-ACK/NACK batch;
    hold     debounce holdoff: after a NACK fires, no further NACK for
             `nack_hold` epochs (the packet receiver's exponential
             block-timer backoff, linearized).

A NACK fires when the batch clock ticks, the holdoff has expired, and
pending holds at least one packet's worth of lost data (`nack_quantum`,
the per-block discreteness the expectation smears out: the packet
receiver NACKs when a BLOCK fails with >= 1 whole packet beyond parity,
so sub-packet expected pending must not fire — without the quantum a
vanishing loss rate still fires every tick and cuts cwnd forever):
pending drains into backlog and the holdoff rearms.  The
sender's `loss_md` window cut is additionally rate-limited to AT MOST ONE
PER FLOW RTT (the `md_cd` countdown) — mirroring the packet sender
(netsim protocol.Flow), where on_nack/_rto_check invoke
cc.on_loss_signal at most once per RTT because a NACK storm is one
congestion event, not hundreds.  Without that gate, persistent random
loss fires the batch clock every nack_period (~RTT/4) and the compounded
cuts collapse throughput far below the packet truth.  The sender
retransmits from backlog at min(backlog / rtt, rtx_cap * rate) — this
rate is REAL WIRE TRAFFIC: it re-enters `offered_load` and can itself be
lost (lost retransmits re-enter `pending`), which is the
retransmit-storm feedback loop the static tax could not express.

What stays netsim-only: packet reordering, per-block discreteness (the
fluid expectation recovers fractional packets), the exponential NACK
backoff schedule (linearized to one holdoff here), and RTO-driven
head-of-line stalls.  See ROADMAP.md's fidelity-limit list.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_EPS = 1e-9
MAX_R = 16        # parity window cap: coef tables carry MAX_R + 1 pmf terms


class RelParams(NamedTuple):
    """Per-flow reliability constants.

    All (n_flows,) float32/int32/bool except `coef`
    ((n_flows, MAX_R + 1) float32): coef[:, i] = C(k+r, i) for i <= r,
    0.0 beyond — the only pmf terms the recovery split needs.  Flows with
    `enabled == False` (intra-DC: EC/NACK never runs there, paper §4.2)
    keep ec_eff as their static goodput factor and bypass the state
    machine entirely.
    """
    enabled: jnp.ndarray        # bool: EC+NACK active on this flow
    ec_k: jnp.ndarray           # data packets per block
    ec_r: jnp.ndarray           # parity packets per block
    ec_eff: jnp.ndarray         # goodput efficiency k/(k+r); 1.0 = no EC
    nack_period: jnp.ndarray    # int32 epochs between NACK batch ticks
    nack_hold: jnp.ndarray      # int32 debounce epochs after a NACK fires
    loss_md: jnp.ndarray        # cwnd factor applied when a NACK fires
    rtx_cap: jnp.ndarray        # retransmit rate cap, multiple of CC rate
    nack_quantum: jnp.ndarray   # min pending bytes for a NACK (~1 packet)
    coef: jnp.ndarray           # (n_flows, MAX_R + 1) masked C(n, i)
    # --- adaptive EC-strength ladder (all None = static EC, the default).
    # The ladder arrays are SHARED across flows ((L,) / (L, MAX_R + 1)),
    # indexed per flow by RelState.rung; `adapt_on` masks the controller
    # per flow.  Shapes are rung-indexed, not flow-indexed, so a vmapped
    # grid can carry per-cell ladders without blowing up the flow axis.
    adapt_on: Optional[jnp.ndarray] = None      # bool (n_flows,)
    ladder_k: Optional[jnp.ndarray] = None      # (L,) data pkts per rung
    ladder_r: Optional[jnp.ndarray] = None      # (L,) parity pkts per rung
    ladder_eff: Optional[jnp.ndarray] = None    # (L,) k/(k+r) per rung
    ladder_coef: Optional[jnp.ndarray] = None   # (L, MAX_R + 1) pmf coefs
    ladder_up: Optional[jnp.ndarray] = None     # (L,) loss EWMA to step up
    ladder_down: Optional[jnp.ndarray] = None   # (L,) loss EWMA to step down


class RelState(NamedTuple):
    """Per-flow recovery state in the scan carry, all (n_flows,).

    `pending`/`backlog`/`ack_cd`/`hold` are the state machine proper; the
    rest are observables (EWMAs + cumulative byte/event counters) the
    recovery sweep reads off the final state."""
    pending: jnp.ndarray        # lost bytes awaiting a NACK batch
    backlog: jnp.ndarray        # NACKed bytes awaiting retransmission
    ack_cd: jnp.ndarray         # int32: epochs to the next NACK batch tick
    hold: jnp.ndarray           # int32: debounce epochs remaining
    md_cd: jnp.ndarray          # ns until the next loss_md cut may fire
    rtx_ewma: jnp.ndarray       # EWMA retransmit rate (bytes/ns)
    lat_ewma: jnp.ndarray       # EWMA recovery latency estimate (ns)
    nacks: jnp.ndarray          # cumulative NACK events
    rec_bytes: jnp.ndarray      # cumulative parity-recovered data bytes
    rtx_bytes: jnp.ndarray      # cumulative retransmitted bytes
    wire_bytes: jnp.ndarray     # cumulative wire bytes sent
    lost_bytes: jnp.ndarray     # cumulative wire bytes dropped en route
    rung: jnp.ndarray           # int32 current ladder rung (0 = base EC)
    loss_ewma: jnp.ndarray      # controller's smoothed loss fraction
    adapt_cd: jnp.ndarray       # ns until the next rung move may fire


def make_rel_params(n_flows: int, *, ec: Tuple[int, int] = (8, 2),
                    nack_period: int = 1, nack_hold: int = 0,
                    loss_md: float = 0.5, rtx_cap: float = 1.0,
                    nack_quantum: float = 4096.0,
                    enabled=None, ladder=None, ladder_up=None,
                    ladder_down=None) -> RelParams:
    """Broadcast scalar reliability knobs to (n_flows,) arrays.

    `ec=(k, r)` sets the block geometry (r <= MAX_R; r == 0 means every
    loss takes the NACK path).  `nack_period`/`nack_hold` are in epochs —
    the scenario compiler derives them from time-valued RelSpec knobs.
    `nack_quantum` is the packet-discreteness floor on pending bytes
    before a NACK may fire (~1 MTU, see module docstring).
    `enabled` masks the state machine per flow (default: all on);
    disabled flows keep ec_eff = 1.0 and zero recovery dynamics.

    `ladder=((k0, r0), (k1, r1), ...)` turns on the adaptive EC-strength
    controller: flows start at rung 0 (which REPLACES `ec` as the base
    geometry) and step up/down the ladder on a smoothed loss signal (see
    `rel_epoch`).  `ladder_up[i]` is the loss-EWMA above which rung i
    escalates to i+1; `ladder_down[i]` the EWMA below which it relaxes to
    i-1.  Defaults place the up-threshold at half the per-packet loss a
    rung's parity absorbs in expectation (0.5 * (r+1)/n) and the
    down-threshold at half the PREVIOUS rung's up-threshold, giving a
    hysteresis band that prevents chatter at a steady loss rate.
    """
    k, r = int(ec[0]), int(ec[1])
    rungs = None
    if ladder is not None:
        rungs = [(int(kk), int(rr)) for kk, rr in ladder]
        if not rungs:
            raise ValueError("ladder needs at least one (k, r) rung")
        k, r = rungs[0]
    if k < 1 or r < 0 or r > MAX_R:
        raise ValueError(f"ec=({k}, {r}) needs k >= 1 and 0 <= r <= "
                         f"{MAX_R}")
    ones = jnp.ones(n_flows, jnp.float32)
    if enabled is None:
        enabled = jnp.ones(n_flows, bool)
    enabled = jnp.asarray(enabled, bool)
    en = enabled.astype(jnp.float32)
    lad = dict(adapt_on=None, ladder_k=None, ladder_r=None,
               ladder_eff=None, ladder_coef=None, ladder_up=None,
               ladder_down=None)
    if rungs is not None:
        for kk, rr in rungs:
            if kk < 1 or rr < 0 or rr > MAX_R:
                raise ValueError(f"ladder rung ({kk}, {rr}) needs k >= 1 "
                                 f"and 0 <= r <= {MAX_R}")
        ks = jnp.asarray([kk for kk, _ in rungs], jnp.float32)
        rs = jnp.asarray([rr for _, rr in rungs], jnp.float32)
        ns = ks + rs
        if ladder_up is None:
            up = 0.5 * (rs + 1.0) / ns      # top rung's value never fires
        else:
            up = jnp.asarray(ladder_up, jnp.float32)
        if ladder_down is None:
            down = jnp.concatenate([jnp.zeros(1, jnp.float32),
                                    0.5 * up[:-1]])
        else:
            down = jnp.asarray(ladder_down, jnp.float32)
        if up.shape != ks.shape or down.shape != ks.shape:
            raise ValueError("ladder_up/ladder_down must match the ladder "
                             "length")
        lad = dict(
            adapt_on=enabled,
            ladder_k=ks, ladder_r=rs, ladder_eff=ks / ns,
            ladder_coef=jnp.stack([binom_coef_row(kk, rr)
                                   for kk, rr in rungs]),
            ladder_up=up, ladder_down=down)
    return RelParams(
        enabled=enabled,
        ec_k=jnp.where(enabled, float(k), 1.0),
        ec_r=jnp.where(enabled, float(r), 0.0),
        ec_eff=jnp.where(enabled, k / (k + r), 1.0),
        nack_period=jnp.full(n_flows, max(int(nack_period), 1), jnp.int32),
        nack_hold=jnp.full(n_flows, max(int(nack_hold), 0), jnp.int32),
        loss_md=loss_md * ones, rtx_cap=rtx_cap * ones,
        nack_quantum=nack_quantum * ones,
        coef=en[:, None] * binom_coef_row(k, r)[None, :],
        **lad)


def binom_coef_row(k: int, r: int) -> jnp.ndarray:
    """(MAX_R + 1,) float32: C(k+r, i) for i <= r, 0.0 past the window."""
    n = k + r
    row = [float(math.comb(n, i)) if i <= r else 0.0
           for i in range(MAX_R + 1)]
    return jnp.asarray(row, jnp.float32)


_LADDER_SHARED = ("ladder_k", "ladder_r", "ladder_eff", "ladder_coef",
                  "ladder_up", "ladder_down")


def stack_rel_params(rows: list) -> RelParams:
    """Concatenate per-group RelParams along the flow axis (compiler use).

    Ladder arrays are rung-indexed (shared), not flow-indexed: they pass
    through unconcatenated, and all groups that carry one must carry the
    SAME one (per-group ladders would need per-flow rung tables — not
    modeled).  Groups without a ladder get `adapt_on = False` fill, so
    they stay on their static geometry."""
    out = {}
    for f in RelParams._fields:
        vals = [getattr(r, f) for r in rows]
        if f in _LADDER_SHARED:
            present = [v for v in vals if v is not None]
            if not present:
                out[f] = None
                continue
            ref = present[0]
            for v in present[1:]:
                if v.shape != ref.shape or not bool(jnp.all(v == ref)):
                    raise ValueError(
                        "stack_rel_params: groups carry differing EC "
                        "ladders; the ladder is shared across the fleet")
            out[f] = ref
        elif f == "adapt_on":
            if all(v is None for v in vals):
                out[f] = None
            else:
                out[f] = jnp.concatenate(
                    [v if v is not None
                     else jnp.zeros(r.enabled.shape[0], bool)
                     for v, r in zip(vals, rows)])
        else:
            out[f] = jnp.concatenate(vals)
    return RelParams(**out)


def init_rel_state(rel: RelParams) -> RelState:
    """Clean recovery state: empty pools, batch clock at a full period."""
    z = jnp.zeros_like(rel.loss_md)
    return RelState(pending=z, backlog=z, ack_cd=rel.nack_period,
                    hold=jnp.zeros_like(rel.nack_hold), md_cd=z,
                    rtx_ewma=z, lat_ewma=z, nacks=z, rec_bytes=z,
                    rtx_bytes=z, wire_bytes=z, lost_bytes=z,
                    rung=jnp.zeros_like(rel.nack_period), loss_ewma=z,
                    adapt_cd=z)


def _effective_geometry(rel: RelParams, st: Optional[RelState]):
    """(ec_k, ec_r, coef) with the ladder rung folded in, if any.

    Without a ladder (or without state, e.g. compile-time queries) this is
    just the static per-flow geometry.  With one, flows under the
    controller (`adapt_on`) read rung `st.rung` of the shared tables."""
    ec_k, ec_r, coef = rel.ec_k, rel.ec_r, rel.coef
    if st is not None and rel.ladder_k is not None:
        on = rel.adapt_on
        ec_k = jnp.where(on, rel.ladder_k[st.rung], ec_k)
        ec_r = jnp.where(on, rel.ladder_r[st.rung], ec_r)
        coef = jnp.where(on[:, None], rel.ladder_coef[st.rung], coef)
    return ec_k, ec_r, coef


@jax.named_scope("fleetsim.reliability")
def effective_eff(rel: RelParams, st: Optional[RelState]) -> jnp.ndarray:
    """Current goodput efficiency k/(k+r), ladder rung folded in."""
    if st is None or rel.ladder_eff is None:
        return rel.ec_eff
    return jnp.where(rel.adapt_on, rel.ladder_eff[st.rung], rel.ec_eff)


def recovery_split(rel: RelParams, q: jnp.ndarray,
                   st: Optional[RelState] = None):
    """(recovered_frac, nack_frac) of a flow's wire bytes at loss prob `q`.

    Both are expected DATA bytes per wire byte sent (see module docstring):
    `recovered_frac` decodes locally from parity, `nack_frac` needs the
    NACK/retransmit path.  They sum to q * k/n (every lost data byte is
    one or the other) and are exactly 0.0 at q == 0.  Disabled flows
    report (0, 0): their losses are unrecovered, as before this module.
    Pass `st` to evaluate at the flow's CURRENT adaptive-EC rung.
    """
    ec_k, ec_r, coef = _effective_geometry(rel, st)
    q = jnp.clip(q, 0.0, 1.0)[:, None]
    n = (ec_k + ec_r)[:, None]
    i = jnp.arange(MAX_R + 1, dtype=jnp.float32)[None, :]
    # pmf terms i = 0..r only (coef is 0 beyond r); q^i and (1-q)^(n-i)
    # via pow keep the q == 0 column exactly {1, 0, 0, ...}.  The exponent
    # clamp guards the masked i > n columns: pow(0, negative) is inf, and
    # 0 * inf would poison the row with NaN at q == 1.
    p_i = coef * jnp.power(q, i) * \
        jnp.power(1.0 - q, jnp.maximum(n - i, 0.0))
    rec_window = jnp.sum(i * p_i, axis=1)        # E[X * 1(X <= r)]
    q1, n1 = q[:, 0], n[:, 0]
    nack_window = jnp.maximum(n1 * q1 - rec_window, 0.0)
    scale = jnp.where(rel.enabled, ec_k / jnp.maximum(n1 * n1, 1.0),
                      0.0)
    return rec_window * scale, nack_window * scale


@jax.named_scope("fleetsim.reliability")
def rtx_rate(rel: RelParams, st: RelState, rate: jnp.ndarray,
             rtt: jnp.ndarray) -> jnp.ndarray:
    """Retransmit send rate (bytes/ns) drained from the NACK backlog.

    Paced at one backlog per RTT, capped at `rtx_cap` times the CC rate —
    an OFF/zero-rate flow retransmits nothing.  Exactly 0.0 while the
    backlog is empty (the no-loss fast-trace identity)."""
    return jnp.minimum(st.backlog / jnp.maximum(rtt, 1.0),
                       rel.rtx_cap * rate)


@jax.named_scope("fleetsim.reliability")
def rel_epoch(rel: RelParams, st: RelState, rate: jnp.ndarray,
              rtx: jnp.ndarray, wire: jnp.ndarray, loss_frac: jnp.ndarray,
              dt, rtt: jnp.ndarray):
    """One epoch of the recovery state machine.

    `rate` is the CC (EC-framed) send rate, `rtx` this epoch's retransmit
    rate (computed from the carried backlog BEFORE the link step, since it
    congests links), `wire = rate + rtx`, `loss_frac` the flow's composed
    drop fraction from the link overflow signal.  Returns
    (RelState', cut, recovered_rate) where `cut` is the loss_md
    window-cut mask — NACK fire AND at least one flow RTT since the last
    cut (the packet sender's once-per-RTT on_loss_signal rate limit) —
    and `recovered_rate` the parity-recovered data rate to credit to
    goodput.

    Adaptive EC controller (ladder configured): the loss fraction feeds a
    flow-RTT-clock EWMA; when it crosses the current rung's `ladder_up`
    threshold the flow escalates one rung (more parity), below
    `ladder_down` it relaxes one.  Moves are rate-limited to one per flow
    RTT (`adapt_cd`) and the up/down hysteresis band prevents chatter —
    the ROADMAP's "loss-EWMA -> EC-strength controller" item.
    """
    g = jnp.minimum(dt / rtt, 1.0)
    q = jnp.clip(loss_frac, 0.0, 1.0)
    rec_frac, nack_frac = recovery_split(rel, q, st)
    recovered_rate = rate * rec_frac
    # bytes entering the NACK path this epoch: fresh unrecoverable losses
    # plus lost retransmits (plain data, no EC framing on the retx stream)
    lost_new = rate * nack_frac * dt + rtx * q * dt
    pending = st.pending + lost_new

    tick = st.ack_cd <= 1
    fire = tick & (st.hold <= 0) & (pending >= rel.nack_quantum) \
        & rel.enabled
    backlog = jnp.maximum(st.backlog - rtx * dt, 0.0) + \
        jnp.where(fire, pending, 0.0)
    pending = jnp.where(fire, 0.0, pending)
    hold = jnp.where(fire, rel.nack_hold,
                     jnp.maximum(st.hold - 1, 0))
    ack_cd = jnp.where(tick, rel.nack_period, st.ack_cd - 1)
    # one multiplicative cut per RTT, however many NACK batches fire
    cut = fire & (st.md_cd <= 0.0)
    md_cd = jnp.where(cut, rtt, jnp.maximum(st.md_cd - dt, 0.0))

    # adaptive EC-strength controller (no-op without a ladder: the carry
    # fields pass through untouched and the trace is unchanged)
    if rel.ladder_k is None:
        rung, loss_ewma, adapt_cd = st.rung, st.loss_ewma, st.adapt_cd
    else:
        n_rungs = rel.ladder_k.shape[0]
        loss_ewma = st.loss_ewma + \
            jnp.minimum(dt / rtt, 1.0) * (q - st.loss_ewma)
        cd = jnp.maximum(st.adapt_cd - dt, 0.0)
        can = rel.adapt_on & rel.enabled & (cd <= 0.0)
        step_up = can & (loss_ewma > rel.ladder_up[st.rung]) \
            & (st.rung < n_rungs - 1)
        step_dn = can & (loss_ewma < rel.ladder_down[st.rung]) \
            & (st.rung > 0)
        rung = st.rung + step_up.astype(jnp.int32) \
            - step_dn.astype(jnp.int32)
        adapt_cd = jnp.where(step_up | step_dn, rtt, cd)

    # observables: EWMAs on the flow-RTT clock + cumulative counters.
    # Latency estimate: parity recovery completes within ~1 block RTT;
    # NACKed data waits half a batch period + holdoff in expectation,
    # then a retransmit round trip.
    lat_nack = 1.5 * rtt + 0.5 * (rel.nack_period + rel.nack_hold) * dt
    vol = recovered_rate + rtx
    inst_lat = (recovered_rate * rtt + rtx * lat_nack) / \
        jnp.maximum(vol, _EPS)
    lat_ewma = jnp.where(vol > 0.0,
                         st.lat_ewma + g * (inst_lat - st.lat_ewma),
                         st.lat_ewma)
    new = RelState(
        pending=pending, backlog=backlog, ack_cd=ack_cd, hold=hold,
        md_cd=md_cd,
        rtx_ewma=st.rtx_ewma + g * (rtx - st.rtx_ewma),
        lat_ewma=lat_ewma,
        nacks=st.nacks + fire.astype(jnp.float32),
        rec_bytes=st.rec_bytes + recovered_rate * dt,
        rtx_bytes=st.rtx_bytes + rtx * dt,
        wire_bytes=st.wire_bytes + wire * dt,
        lost_bytes=st.lost_bytes + wire * q * dt,
        rung=rung, loss_ewma=loss_ewma, adapt_cd=adapt_cd)
    return new, cut, recovered_rate
