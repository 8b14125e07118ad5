"""Vectorized congestion-control state machines on a fixed epoch clock.

One `step` = one epoch (intra-DC-RTT-derived period, the paper's single
granularity).  Per epoch, for all flows at once:

  send rates (split across paths) -> per-link offered load -> queue
  occupancies (physical + phantom) -> expected ECN mark fractions (per
  subflow and split-weighted per flow) -> window accumulators -> the
  scheme's window reaction (Alg 1 for UnoCC; per-own-RTT reactions for the
  DCTCP / Gemini baselines) -> Quick-Adapt (UnoCC only) -> the `lb` axis
  (UnoLB-style adaptive subflow weights) -> open-loop churn transitions.

The MD arithmetic is imported from repro.core.unocc — the scalar per-flow
controller and this fleet model share the formulas, they differ only in
plumbing.  Everything here is jit-compiled via `jax.lax.scan` and carries
pure (n_flows,)/(n_links,)/(n_flows, n_paths) arrays, so 10k flows x 100k
epochs run in seconds and whole scenarios `vmap` across parameter grids
(repro.fleetsim.sweeps).

The `lb` axis (LbParams; fluid analogue of netsim.routing.UnoLBRouter /
Algorithm 2): each flow's split weights shift multiplicatively toward
less-marked paths (w *= exp(-eta * path_mark_frac), renormalized), and a
path whose lagged mark fraction stays above `repath_thresh` for
`repath_patience` consecutive epochs is repathed REPS/PLB-style — its
weight is redistributed to the other paths (a floor weight keeps probing
it so it can recover).  Static-EC overhead mode scales *useful* goodput by
k/(k+r) while the wire rate (what congests links) is unscaled.

Open-loop churn (ChurnParams): per-flow on/off masks with geometric
per-epoch transitions (exponential holding times in the fluid limit),
deterministically seeded via the PRNG key in FleetState.  An OFF flow
sends nothing and its controller state is frozen; turning ON restarts it
like a fresh flow (cwnd = BDP, clean accumulators) — this makes
app-limited senders and approximate FCT questions expressible.

The reliability axis (RelParams/RelState, repro.fleetsim.reliability;
fluid analogue of netsim's EC framing + SmartAckNack receivers): when a
scenario carries `rel`, each epoch derives a per-flow loss fraction from
queue overflow (links.drop_prob composed along the flow's paths), splits
it into parity-recovered vs NACK-bound payload via the dynamic-EC window
pmf, runs the batched-NACK/debounce counters, and feeds the retransmit
backlog back into the wire rate — so `offered_load` sees retransmissions
as real traffic and a NACK batch fires a loss-driven multiplicative
decrease (`loss_md`).  Goodput then uses the dynamic split instead of the
static `lb.ec_eff` tax: payload delivered + payload recovered from parity
+ retransmitted payload (retransmissions carry data only, no parity).
With `rel=None` the whole machine vanishes at trace time — the compiled
step is the same program as before the axis existed.

Fluid-model fidelity limits (vs repro.netsim, recorded in ROADMAP.md):
marking is the RED expectation (no per-packet randomness), feedback is a
first-order lag rather than an exact delay line, queues see *offered* load
(upstream bottlenecks do not thin downstream arrivals), the scalar
controller's fast increase is windowed (clean-window streak on the epoch
clock) rather than per-ACK, churned
flows restart instantaneously (no slow-start ramp) with exponential rather
than empirical size/holding distributions, and repathing moves rate weight
without packet reordering.  The reliability axis captures expected loss
rates, parity-window recovery fractions, NACK batching cadence and
retransmit-load feedback, but not per-packet effects: packet reordering,
selective-repeat hole tracking, receiver block timers / exponential
backoff, or loss burstiness beyond the per-epoch expectation (netsim
remains the oracle for those — fleetsim.validate cross-checks the rates).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.unocc import gentle_md_scale, md_ecn_gain, md_factor
from repro.fleetsim import faults as F
from repro.fleetsim import links as L
from repro.fleetsim import reliability as R
from repro.fleetsim.state import (ChurnParams, FleetParams, FleetState,
                                  LbParams, init_state)

SCHEMES = ("uno", "gemini", "dctcp")
_FRAC_EPS = 1e-6
# state NOT selected per flow by the churn merge: shared link occupancies,
# the PRNG key, the replicated fault carry, and the active mask itself
# (set explicitly each epoch)
_NON_FLOW_FIELDS = ("q_phys", "q_phantom", "key", "active", "fault")


def _merge_flow_state(cond: jnp.ndarray, a: FleetState,
                      b: FleetState) -> FleetState:
    """Per-flow fields from `a` where `cond` (a (n_flows,) bool) else `b`;
    link-level fields and the PRNG key pass through from `a`.

    Iterating FleetState._fields makes the churn freeze/restart exhaustive
    by construction — a field added to FleetState is covered automatically
    instead of silently escaping a hand-written list.
    """
    out = {}
    for f in FleetState._fields:
        av = getattr(a, f)
        if f in _NON_FLOW_FIELDS or av is None:
            out[f] = av
            continue
        if hasattr(av, "_fields"):  # nested per-flow pytree (RelState)
            out[f] = jax.tree.map(
                lambda x, y: jnp.where(cond, x, y), av, getattr(b, f))
            continue
        c = cond if av.ndim == 1 else cond[:, None]
        out[f] = jnp.where(c, av, getattr(b, f))
    return FleetState(**out)


def update_split(split: jnp.ndarray, path_frac: jnp.ndarray,
                 bad_count: jnp.ndarray, mask: jnp.ndarray, lb: LbParams):
    """One epoch of the UnoLB-style weight adaptation.

    Returns (split', bad_count').  Multiplicative weights on the lagged
    per-path mark fractions shift rate toward cleaner paths; a path that
    stays above `repath_thresh` for `repath_patience` epochs is zeroed
    (repath) and its weight redistributes through renormalization, with
    `w_floor` keeping a probe trickle on every valid path.
    """
    bad = mask & (path_frac > lb.repath_thresh[:, None])
    bad_count = jnp.where(bad, bad_count + 1, 0)
    repath = bad_count >= lb.repath_patience[:, None]
    w = split * jnp.exp(-lb.eta[:, None] * path_frac)
    w = jnp.where(repath, 0.0, w)
    bad_count = jnp.where(repath, 0, bad_count)
    return L.normalize_split(w, mask, lb.w_floor), bad_count


def make_step(net: L.FluidNet, params: FleetParams, scheme: str = "uno",
              is_inter: Optional[jnp.ndarray] = None,
              lb: Optional[LbParams] = None,
              churn: Optional[ChurnParams] = None,
              rel: Optional[R.RelParams] = None,
              fault: Optional[F.FaultSchedule] = None, *,
              axis_name: Optional[str] = None, backend: str = "auto",
              halo: Optional[int] = None, block: Optional[int] = None,
              churn_map: Optional[jnp.ndarray] = None,
              churn_n: Optional[int] = None,
              nbr: Optional[jnp.ndarray] = None,
              n_shards: Optional[int] = None):
    """Build the per-epoch transition: state -> (state', goodput).

    `lb=None` freezes the split at its initial value (static spraying) and
    reports raw goodput; `churn=None` keeps every flow backlogged;
    `rel=None` skips the loss/recovery machine entirely (no loss arrays are
    even computed — the trace is identical to the pre-reliability step);
    `fault=None` likewise skips fault injection.  With a `fault` schedule
    (repro.fleetsim.faults), each epoch modulates link capacity (downs /
    brownouts / flaps) and loss probability (Gilbert-Elliott bursts) and
    drains the epoch's send split from dead paths — the STORED split is
    untouched when `lb` is off, so repairs resume pre-fault weights.
    With `rel` set, the wire rate is cwnd-rate + retransmit rate, the loss
    fraction from links.drop_prob drives reliability.rel_epoch, a NACK
    batch applies `rel.loss_md`, and goodput uses the dynamic EC split —
    `rel.ec_eff` supersedes `lb.ec_eff` (the compiler folds the static
    efficiency of non-reliability flows into `rel.ec_eff`).
    `axis_name` names a shard_map mesh axis the flow dimension is sharded
    over (per-epoch reduction of the partial link loads — repro.fleetsim
    .shard); `halo` shrinks that reduction to the trailing boundary links
    of a locality-relabeled link id space (links.halo_exchange), and
    `nbr`/`n_shards` swap the boundary psum for the ppermute neighbor
    exchange when the plan proved every boundary link adjacent-pair-only;
    `backend` picks the link-aggregation implementation (repro.fleetsim
    .links.LOAD_BACKENDS); `block` overrides the Pallas backends'
    flow-block size (None picks it from n_flows).

    `churn_map`/`churn_n` make churn exact under flow sharding: each shard
    draws the SAME global (churn_n,) uniform vector (the PRNG key is
    replicated) and gathers its local rows by their global flow ids, so a
    sharded run flips exactly the flows the single-device run flips
    regardless of how the plan permuted them.
    """
    if scheme not in SCHEMES:
        raise ValueError(f"unknown fleetsim scheme {scheme!r}")
    if churn_map is not None and churn_n is None:
        raise ValueError("churn_map needs churn_n (the global flow count)")
    if is_inter is None:
        is_inter = jnp.zeros_like(params.bdp, bool)
    pmask = L.path_mask(net)
    single = net.n_paths == 1
    # restart target for OFF->ON churn transitions: a fresh flow exactly as
    # init_state would start it (line-rate cwnd, clean accumulators,
    # uniform split); constant, so hoisted out of the scanned step
    fresh = None
    if churn is not None:
        fresh = init_state(params, net.n_links, n_paths=net.n_paths,
                           split0=L.uniform_split(net), rel=rel)

    @jax.named_scope("fleetsim.cc")
    def step(state: FleetState, _):
        p = params
        act = state.active
        actf = act.astype(jnp.float32)
        # ---- fault injection: this epoch's effective net ----------------
        # cap/drain scaled by scheduled downs/brownouts/flaps, GE burst
        # loss composed into p_loss; the degraded SEND split shifts rate
        # off dead paths for this epoch only (state.split is persistent)
        net_e, fault_new = net, state.fault
        split = state.split
        if fault is not None:
            cap_scale, p_extra, fault_new = F.fault_modulation(
                fault, state.fault, net.n_links)
            net_e = F.apply_modulation(net, cap_scale, p_extra)
            if cap_scale is not None and not single:
                split = F.degrade_split(net, split, cap_scale, pmask)
        # ---- network: loads, queues, marks, delays ----------------------
        rate = actf * state.cwnd / p.rtt
        if rel is None:
            wire = rate
        else:   # retransmit backlog drains onto the wire as real traffic
            rtx = R.rtx_rate(rel, state.rel, rate, p.rtt)
            wire = rate + rtx
        le = L.link_epoch(net_e, wire, split, state.q_phys, state.q_phantom,
                          axis_name=axis_name, backend=backend, halo=halo,
                          block=block, with_loss=rel is not None,
                          nbr=nbr, n_shards=n_shards)
        q_phys, q_phantom = le.q_phys, le.q_phantom
        sub_frac = le.sub_frac
        if single:   # split-weighted sums collapse to one product per flow
            s1 = split[:, 0]
            sc = s1 * le.sub_scale[:, 0]
            inst_frac = s1 * sub_frac[:, 0]
            inst_delay = s1 * le.sub_delay[:, 0]
        else:
            sc = jnp.sum(split * le.sub_scale, axis=1)
            inst_frac = jnp.sum(split * sub_frac, axis=1)
            inst_delay = jnp.sum(split * le.sub_delay, axis=1)
        goodput = wire * sc
        rel_new, nack_fire, recovered = state.rel, None, None
        if rel is not None:
            if single:
                lf = s1 * le.sub_loss[:, 0]
            else:
                lf = jnp.sum(split * le.sub_loss, axis=1)
            rel_new, nack_fire, recovered = R.rel_epoch(
                rel, state.rel, rate, rtx, wire, lf, net.dt, p.rtt)
        # Feedback lag: a sender observes congestion one flow-RTT late (marks
        # ride the data+ACK round trip).  First-order filter with time
        # constant = flow RTT — exact for intra flows (rtt == dt), and for
        # long-RTT flows it reproduces the overshoot the packet simulator
        # shows (growth continues while marks are in flight), without
        # carrying an explicit per-link delay line.
        fb = jnp.minimum(net.dt / p.rtt, 1.0)
        frac = state.obs_frac + fb * (inst_frac - state.obs_frac)
        delay = state.obs_delay + fb * (inst_delay - state.obs_delay)
        # the lagged per-path marks only feed the lb weight update — skip
        # the (n_flows, n_paths) filter entirely under static spraying
        path_frac = state.path_frac if lb is None else \
            state.path_frac + fb[:, None] * (sub_frac - state.path_frac)
        acked = goodput * net.dt

        # ---- window accumulators ----------------------------------------
        win_acked = state.win_acked + acked
        win_marked = state.win_marked + frac * acked
        # delay extrema feed scheme-specific reactions: win_dmin gates Uno's
        # gentle MD, win_dmax drives Gemini's WAN backoff — maintain only
        # what the scheme reads
        win_dmin = jnp.minimum(state.win_delay_min, delay) \
            if scheme == "uno" else state.win_delay_min
        win_dmax = jnp.maximum(state.win_delay_max, delay) \
            if scheme == "gemini" else state.win_delay_max
        fire = state.cc_countdown <= 1
        can_md = state.skip <= 0
        wfrac = win_marked / jnp.maximum(win_acked, 1.0)
        marked = wfrac > _FRAC_EPS

        # ---- additive increase (continuous, on unmarked bytes) ----------
        ai_gain = p.mtu if scheme == "dctcp" else p.alpha
        inc = ai_gain * acked * (1.0 - frac) / \
            jnp.maximum(state.cwnd, 1.0)
        if scheme == "uno":
            # Fast increase (UnoCC / SMaRTT lineage, core.unocc OnAck):
            # after >= 3 fully clean windows while well below the last
            # congested cwnd, grow by the unmarked acked bytes themselves
            # (doubling per RTT) until the first mark arrives.  Without it
            # the fluid flow recovers from a deep (QA or loss-signal)
            # collapse at alpha-AI pace, O(BDP/alpha) RTTs slower than the
            # packet sender — the dominant infidelity under loss-driven
            # cuts on mark-free paths.  FI keys off the INSTANTANEOUS mark
            # fraction (the per-ACK ECN bit, which ends crisply when the
            # phantom queue empties), not the lagged `frac`: the lag
            # filter's exponential tail would keep "marked" true for many
            # epochs after congestion clears, chasing fi_ceiling down to
            # the collapsed cwnd and locking FI out permanently.
            m_fi = inst_frac > _FRAC_EPS
            fi_on = state.fi_active & ~m_fi
            inc = jnp.where(fi_on, jnp.maximum(inc, acked * (1.0 - frac)),
                            inc)
        cwnd = state.cwnd + inc

        # ---- window reaction --------------------------------------------
        ecn_ewma = jnp.where(
            fire, (1.0 - p.ewma_g) * state.ecn_ewma + p.ewma_g * wfrac,
            state.ecn_ewma)
        md_scale = state.md_scale
        if scheme == "uno":                          # Alg 1 OnEpoch
            gentle = jnp.where(
                win_dmin < p.delay_thresh,
                gentle_md_scale(state.md_scale, p.gentle_scale,
                                p.gentle_floor, maximum=jnp.maximum),
                1.0)
            md_scale = jnp.where(fire & marked & can_md, gentle,
                                 jnp.where(fire & ~marked, 1.0,
                                           state.md_scale))
            factor = md_factor(ecn_ewma, md_scale, p.k_md, p.bdp, p.md_cap,
                               minimum=jnp.minimum)
            cwnd = jnp.where(fire & marked & can_md,
                             jnp.maximum(cwnd * factor, p.min_cwnd), cwnd)
        elif scheme == "gemini":                     # per-own-RTT reaction
            md = jnp.where(marked,
                           ecn_ewma * md_ecn_gain(p.k_md, p.bdp), 0.0)
            wan_md = jnp.where(
                is_inter & (win_dmax > p.delay_thresh),
                0.5 * jnp.minimum(win_dmax / p.rtt, 1.0), 0.0)
            md = jnp.minimum(jnp.maximum(md, wan_md), p.md_cap)
            cwnd = jnp.where(fire & (md > 0.0),
                             jnp.maximum(cwnd * (1.0 - md), p.min_cwnd),
                             cwnd)
        else:                                        # dctcp: cwnd *= 1 - E/2
            cwnd = jnp.where(fire & marked,
                             jnp.maximum(cwnd * (1.0 - 0.5 * ecn_ewma),
                                         p.min_cwnd),
                             cwnd)

        win_acked = jnp.where(fire, 0.0, win_acked)
        win_marked = jnp.where(fire, 0.0, win_marked)
        if scheme == "uno":
            win_dmin = jnp.where(fire, jnp.inf, win_dmin)
        if scheme == "gemini":
            win_dmax = jnp.where(fire, 0.0, win_dmax)
        cc_countdown = jnp.where(fire, p.cc_period, state.cc_countdown - 1)

        # ---- fast-increase bookkeeping (UnoCC only) ---------------------
        fi_clean = state.fi_clean
        fi_active = state.fi_active
        fi_ceiling = state.fi_ceiling
        if scheme == "uno":
            fi_active = fi_on        # marks mid-window already disengaged
            # window close (core.unocc._end_epoch): a clean window extends
            # the streak and may engage FI — only well below the last cwnd
            # that saw congestion (re-probing at the old ceiling just
            # oscillates against the phantom marks); a marked window resets
            # the streak and pins the ceiling at the congested cwnd.
            fi_clean = jnp.where(fire, jnp.where(m_fi, 0,
                                                 state.fi_clean + 1),
                                 state.fi_clean)
            engage = (fi_clean >= 3) & (cwnd < 0.7 * fi_ceiling)
            fi_active = jnp.where(fire, ~m_fi & (fi_active | engage),
                                  fi_active)
            fi_ceiling = jnp.where(fire & m_fi,
                                   jnp.maximum(cwnd, 4.0 * p.min_cwnd),
                                   state.fi_ceiling)

        # ---- Quick-Adapt (UnoCC only; Alg 1 OnQA) -----------------------
        qa_acked = state.qa_acked + acked
        qa_prev = state.qa_prev_acked
        qa_deficits = state.qa_deficits
        skip = jnp.maximum(state.skip - 1, 0)
        qa_countdown = state.qa_countdown - 1
        if scheme == "uno":
            tick = state.qa_countdown <= 1
            # fluid flows are backlogged while ON, so the "window exercised"
            # guard (inflight + acked >= beta*cwnd) always holds; the 4-MTU
            # quantization guard still applies.
            deficit = (tick & (state.cwnd >= 4.0 * p.mtu)
                       & (qa_acked < p.beta * state.cwnd))
            trigger = deficit & (state.qa_deficits >= 1) & can_md
            cwnd = jnp.where(
                trigger,
                jnp.maximum(jnp.maximum(qa_acked, qa_prev), p.min_cwnd),
                cwnd)
            qa_deficits = jnp.where(
                tick, jnp.where(deficit & ~trigger, state.qa_deficits + 1, 0),
                state.qa_deficits)
            skip = jnp.where(trigger, 2 * p.qa_period, skip)
            qa_prev = jnp.where(tick, qa_acked, qa_prev)
            qa_acked = jnp.where(tick, 0.0, qa_acked)
            qa_countdown = jnp.where(tick, p.qa_period, qa_countdown)

        # ---- reliability: NACK-driven multiplicative decrease -----------
        # `nack_fire` is already rate-limited to one cut per flow RTT
        # (reliability.rel_epoch md_cd); the post-QA skip additionally
        # suppresses it, as the packet sender's on_loss_signal honours
        # _skip_until.
        if rel is not None:
            cwnd = jnp.where(nack_fire & can_md,
                             jnp.maximum(cwnd * rel.loss_md, p.min_cwnd),
                             cwnd)
        cwnd = jnp.clip(cwnd, p.min_cwnd, p.max_cwnd)

        # ---- lb axis: adaptive subflow weights --------------------------
        # without lb the STORED split stays state.split (a fault-degraded
        # send split must not persist — repair resumes pre-fault weights);
        # with lb the weight update adapts FROM the degraded split, which
        # is what the marks it just produced correspond to
        split_new, bad_count = state.split, state.bad_count
        if lb is not None:
            split_new, bad_count = update_split(split, path_frac, bad_count,
                                                pmask, lb)
            if rel is None:
                goodput = goodput * lb.ec_eff   # parity bytes carry no payload
        if rel is not None:
            # dynamic EC split: delivered payload (parity fraction of the
            # CC stream is overhead, retransmits are pure data) + payload
            # decoded locally from parity.  The efficiency is evaluated at
            # the flow's CURRENT adaptive-EC rung (static rel.ec_eff when
            # no ladder is configured; it also carries the static
            # efficiency for non-reliability flows, superseding lb.ec_eff).
            eff = R.effective_eff(rel, state.rel)
            goodput = goodput * eff + rtx * sc * (1.0 - eff) \
                + recovered

        new = FleetState(
            cwnd=cwnd, ecn_ewma=ecn_ewma, md_scale=md_scale,
            q_phys=q_phys, q_phantom=q_phantom,
            obs_frac=frac, obs_delay=delay,
            win_acked=win_acked, win_marked=win_marked,
            win_delay_min=win_dmin, win_delay_max=win_dmax,
            cc_countdown=cc_countdown,
            qa_acked=qa_acked, qa_prev_acked=qa_prev,
            qa_deficits=qa_deficits, qa_countdown=qa_countdown, skip=skip,
            fi_clean=fi_clean, fi_active=fi_active, fi_ceiling=fi_ceiling,
            split=split_new, path_frac=path_frac, bad_count=bad_count,
            active=act, key=state.key, rel=rel_new, fault=fault_new)

        # ---- churn: freeze OFF flows, restart fresh on OFF->ON ----------
        if churn is not None:
            key, sub = jax.random.split(state.key)
            if churn_map is not None:
                u = jax.random.uniform(sub, (churn_n,))[churn_map]
            else:
                u = jax.random.uniform(sub, p.bdp.shape)
            p_off = jnp.clip(net.dt / jnp.maximum(churn.mean_on, 1.0),
                             0.0, 1.0)
            p_on = jnp.clip(net.dt / jnp.maximum(churn.mean_off, 1.0),
                            0.0, 1.0)
            turn_off = act & churn.churned & (u < p_off)
            turn_on = ~act & churn.churned & (u < p_on)
            new = _merge_flow_state(act, new, state)       # OFF: frozen
            new = _merge_flow_state(~turn_on, new, fresh)  # OFF->ON: fresh
            new = new._replace(active=(act & ~turn_off) | turn_on, key=key)
        return new, goodput

    return step


def _default_state(net: L.FluidNet, params: FleetParams, seed: int = 0,
                   rel=None, fault=None):
    return init_state(params, net.n_links, n_paths=net.n_paths,
                      split0=L.uniform_split(net), seed=seed, rel=rel,
                      fault=fault)


@functools.partial(jax.jit,
                   static_argnames=("scheme", "n_epochs", "record",
                                    "backend", "block"))
def _simulate(net, params, state0, is_inter, lb, churn, scheme, n_epochs,
              record, backend="auto", block=None, rel=None, fault=None):
    step = make_step(net, params, scheme, is_inter, lb=lb, churn=churn,
                     rel=rel, fault=fault, backend=backend, block=block)
    if record:
        return jax.lax.scan(step, state0, None, length=n_epochs)
    final, _ = jax.lax.scan(lambda s, x: (step(s, x)[0], None),
                            state0, None, length=n_epochs)
    return final, None


def simulate(net: L.FluidNet, params: FleetParams, *, n_epochs: int,
             scheme: str = "uno", state0: Optional[FleetState] = None,
             is_inter: Optional[jnp.ndarray] = None,
             lb: Optional[LbParams] = None,
             churn: Optional[ChurnParams] = None,
             rel: Optional[R.RelParams] = None,
             fault: Optional[F.FaultSchedule] = None,
             seed: int = 0, record: bool = False, backend: str = "auto",
             block: Optional[int] = None):
    """Run `n_epochs` epochs; returns (final_state, goodput_trajectory).

    `goodput_trajectory` is (n_epochs, n_flows) bytes/ns when `record`,
    else None.  Jit-compiled; recompiles only on new (scheme, n_epochs,
    record, backend, block, shapes, lb/churn/rel/fault presence).  `seed`
    fixes the churn PRNG; `backend` picks the link-aggregation path
    (links.LOAD_BACKENDS) and `block` the Pallas flow-block size; `rel`
    turns on the loss/recovery machine (reliability.make_rel_params);
    `fault` a compiled fault schedule (faults.make_schedule or the
    scenario compiler).
    """
    with jax.profiler.TraceAnnotation("fleetsim.dispatch"):
        if state0 is None:
            state0 = _default_state(net, params, seed, rel, fault)
        if is_inter is None:
            is_inter = jnp.zeros_like(params.bdp, bool)
        return _simulate(net, params, state0, is_inter, lb, churn, scheme,
                         n_epochs, record, backend, block, rel, fault)


@functools.partial(jax.jit,
                   static_argnames=("scheme", "n_warm", "n_meas", "backend",
                                    "axis_name", "halo", "block", "churn_n",
                                    "unroll", "n_shards"))
def steady_state_core(net, params, state0, is_inter, scheme, n_warm, n_meas,
                      lb=None, churn=None, backend="auto", axis_name=None,
                      halo=None, block=None, churn_map=None, churn_n=None,
                      unroll=1, rel=None, fault=None, nbr=None,
                      n_shards=None):
    """Warm up, then return (final_state, mean goodput over n_meas epochs).

    The measurement pass accumulates a running sum in the carry instead of
    materializing the (n_meas, n_flows) trajectory — this is the vmap-safe
    entry point sweeps fan out over (a stacked trajectory for a whole grid
    would not fit memory).  `axis_name`/`halo`/`churn_map`/`churn_n` are
    set by repro.fleetsim.shard when the flow axis runs under shard_map
    (see make_step).  `unroll` fuses that many epochs into one scan step:
    the loop-carried state stays in registers/cache across the fused
    epochs and the boundary collectives batch per step instead of paying
    per-epoch dispatch — numerics are unchanged (same per-epoch op order,
    just loop restructuring)."""
    step = make_step(net, params, scheme, is_inter, lb=lb, churn=churn,
                     rel=rel, fault=fault, backend=backend,
                     axis_name=axis_name, halo=halo, block=block,
                     churn_map=churn_map, churn_n=churn_n, nbr=nbr,
                     n_shards=n_shards)
    state, _ = jax.lax.scan(lambda s, x: (step(s, x)[0], None),
                            state0, None, length=n_warm, unroll=unroll)

    def acc_step(carry, _):
        s, acc = carry
        s, goodput = step(s, None)
        return (s, acc + goodput), None

    (state, acc), _ = jax.lax.scan(
        acc_step, (state, jnp.zeros_like(params.bdp)), None, length=n_meas,
        unroll=unroll)
    return state, acc / n_meas


def steady_state(net: L.FluidNet, params: FleetParams, *, n_warm: int,
                 n_meas: int, scheme: str = "uno",
                 state0: Optional[FleetState] = None,
                 is_inter: Optional[jnp.ndarray] = None,
                 lb: Optional[LbParams] = None,
                 churn: Optional[ChurnParams] = None,
                 rel: Optional[R.RelParams] = None,
                 fault: Optional[F.FaultSchedule] = None, seed: int = 0,
                 backend: str = "auto", block: Optional[int] = None):
    if state0 is None:
        state0 = _default_state(net, params, seed, rel, fault)
    if is_inter is None:
        is_inter = jnp.zeros_like(params.bdp, bool)
    return steady_state_core(net, params, state0, is_inter, scheme,
                             n_warm, n_meas, lb, churn, backend,
                             block=block, rel=rel, fault=fault)
