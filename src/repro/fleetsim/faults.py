"""Scheduled fault injection for the fluid fleet simulator.

The packet simulator has always been able to kill a link mid-run
(netsim.topology.fail_link, scheduled through `sim.at`) and corrupt a WAN
segment with correlated Gilbert-Elliott loss — that is how the paper's
Fig 13 failure study runs.  This module gives the fleet-scale fluid model
the same axis WITHOUT leaving the jitted `lax.scan`: a scenario's declared
`FaultSpec`s (repro.scenarios.spec) compile into one compact
`FaultSchedule` of epoch-indexed events, and each epoch the step derives

  * a per-link capacity multiplier (`cap_scale`): hard-down events pin a
    link's capacity to 0, brownouts to a fraction, flaps toggle on a
    period/duty square wave — all pure arithmetic on the carried epoch
    counter, so a whole sweep grid of different fail times vmaps into one
    executable;
  * a per-link extra loss probability (`p_extra`): Gilbert-Elliott-style
    correlated bursts from a seeded two-state chain carried per event in
    `FaultCarry.ge_bad` (the fluid analogue of netsim's per-packet chain —
    here the chain ticks once per EPOCH and the loss it emits is the
    expectation over that epoch's bytes, see ROADMAP fidelity notes).

`apply_modulation` folds both into the epoch's effective FluidNet
(`cap`/`drain` scaled, `p_extra` composed into `p_loss`), which threads
through EVERY link-aggregation backend unchanged — the backends only ever
read `net.cap`/`net.p_loss`.  `degrade_split` drains the epoch's send
split from dead paths (capacity 0 anywhere on the path) so multipath flows
shift rate to surviving paths immediately; a flow whose ENTIRE path-set is
down keeps its stored split — its subflow scale is 0 on every hop, goodput
is 0, marks saturate, and CC parks it at `min_cwnd` (a finite floor rate,
never NaN/Inf) until a repair lets it resume.

Sharding: the schedule's link ids live in the same id space as the link
buffers, so `shard.shard_scenario` relabels them through `plan.old2new`
exactly like the route tensor; every shard then computes an identical
modulation over its full (relabeled) link buffer and the halo exchange is
untouched.  The carry's PRNG key is replicated, so the burst chains agree
across shards by construction.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.fleetsim import links as L

# t1 sentinel for events that never clear (fits int32, compares cleanly)
OPEN_END = 2 ** 31 - 1


class FaultSchedule(NamedTuple):
    """Epoch-indexed fault events, compiled once per scenario.

    Two static-shape event families (either may be empty — the matching
    half of the modulation then vanishes at trace time):

      capacity events, (E,) arrays — active on epochs [t0, t1); while
      active (and, for flaps, while the duty phase is in its fault half)
      the link's capacity is multiplied by `cap_frac` (0.0 = hard down);

      Gilbert-Elliott events, (G,) arrays — a two-state chain per event
      (state in FaultCarry.ge_bad) transitioning once per epoch with
      P(good->bad) = ge_p_gb, P(bad->good) = ge_p_bg inside [ge_t0,
      ge_t1), emitting loss probability ge_p_bad / ge_p_good by state.

    Multiple events may target one link: capacity multipliers combine by
    min, loss probabilities by max.
    """
    link: jnp.ndarray       # (E,) int32 target link id
    t0: jnp.ndarray         # (E,) int32 first active epoch
    t1: jnp.ndarray         # (E,) int32 first epoch past the event
    cap_frac: jnp.ndarray   # (E,) float32 capacity multiplier while faulted
    period: jnp.ndarray     # (E,) int32 flap period in epochs (0 = steady)
    duty: jnp.ndarray       # (E,) float32 fraction of a period spent faulted
    ge_link: jnp.ndarray    # (G,) int32 target link id
    ge_t0: jnp.ndarray      # (G,) int32
    ge_t1: jnp.ndarray      # (G,) int32
    ge_p_good: jnp.ndarray  # (G,) float32 loss prob in the good state
    ge_p_bad: jnp.ndarray   # (G,) float32 loss prob in the bad state
    ge_p_gb: jnp.ndarray    # (G,) float32 per-epoch P(good -> bad)
    ge_p_bg: jnp.ndarray    # (G,) float32 per-epoch P(bad -> good)

    @property
    def n_cap_events(self) -> int:
        return self.link.shape[-1]

    @property
    def n_ge_events(self) -> int:
        return self.ge_link.shape[-1]


class FaultCarry(NamedTuple):
    """Fault state threaded through the scan carry.

    Replicated (never flow-indexed) under sharding, like the churn PRNG
    key: every shard advances an identical copy."""
    epoch: jnp.ndarray    # int32 scalar: epochs since simulation start
    ge_bad: jnp.ndarray   # (G,) bool: burst chains currently in BAD state
    key: jnp.ndarray      # PRNG key driving the chain transitions


def make_schedule(cap_events: Sequence[Tuple] = (),
                  ge_events: Sequence[Tuple] = ()) -> FaultSchedule:
    """Build a FaultSchedule from host-side event tuples.

    `cap_events` rows are (link, t0, t1, cap_frac, period, duty) with
    epoch-valued times (t1=None -> OPEN_END, period 0 -> steady fault);
    `ge_events` rows are (link, t0, t1, p_good, p_bad, p_gb, p_bg).
    Either list may be empty — the schedule keeps (0,)-shaped arrays and
    that half of the fault math is skipped at trace time.
    """
    def col(rows, j, dtype, none=None):
        vals = [none if (rows and rows[0] is not None and r[j] is None)
                else r[j] for r in rows]
        return jnp.asarray(vals, dtype).reshape(len(rows))

    cap_events = [tuple(r) for r in cap_events]
    ge_events = [tuple(r) for r in ge_events]
    return FaultSchedule(
        link=col(cap_events, 0, jnp.int32),
        t0=col(cap_events, 1, jnp.int32),
        t1=col(cap_events, 2, jnp.int32, none=OPEN_END),
        cap_frac=col(cap_events, 3, jnp.float32),
        period=col(cap_events, 4, jnp.int32),
        duty=col(cap_events, 5, jnp.float32),
        ge_link=col(ge_events, 0, jnp.int32),
        ge_t0=col(ge_events, 1, jnp.int32),
        ge_t1=col(ge_events, 2, jnp.int32, none=OPEN_END),
        ge_p_good=col(ge_events, 3, jnp.float32),
        ge_p_bad=col(ge_events, 4, jnp.float32),
        ge_p_gb=col(ge_events, 5, jnp.float32),
        ge_p_bg=col(ge_events, 6, jnp.float32))


def init_fault_carry(fault: FaultSchedule, seed: int = 0) -> FaultCarry:
    """Epoch 0, every burst chain in the good state, seeded chain PRNG.

    The key is folded away from the churn PRNG (which uses the raw seed)
    so fault randomness never aliases churn draws on the same scenario."""
    return FaultCarry(
        epoch=jnp.int32(0),
        ge_bad=jnp.zeros(fault.n_ge_events, bool),
        key=jax.random.fold_in(jax.random.PRNGKey(seed), 0xFA))


@jax.named_scope("fleetsim.faults")
def fault_modulation(fault: FaultSchedule, carry: FaultCarry, n_links: int):
    """One epoch of fault evaluation.

    Returns (cap_scale, p_extra, carry') where `cap_scale` is the
    (n_links,) capacity multiplier (None when the schedule has no
    capacity events) and `p_extra` the (n_links,) extra loss probability
    (None without GE events).  Pure array math on the carried epoch
    counter — vmaps across a grid of schedules with identical shapes.
    """
    ep = carry.epoch
    cap_scale = None
    if fault.n_cap_events:
        active = (ep >= fault.t0) & (ep < fault.t1)
        phase = jnp.mod(ep - fault.t0, jnp.maximum(fault.period, 1))
        flap_on = phase.astype(jnp.float32) < \
            fault.duty * fault.period.astype(jnp.float32)
        in_fault = jnp.where(fault.period > 0, flap_on, True)
        eff = jnp.where(active & in_fault, fault.cap_frac, 1.0)
        cap_scale = jnp.ones(n_links, jnp.float32).at[fault.link].min(eff)
    p_extra = None
    ge_bad = carry.ge_bad
    key = carry.key
    if fault.n_ge_events:
        key, sub = jax.random.split(carry.key)
        u = jax.random.uniform(sub, fault.ge_link.shape)
        win = (ep >= fault.ge_t0) & (ep < fault.ge_t1)
        # outside the window the chain is pinned to good (fresh burst
        # structure each time a windowed event re-opens)
        ge_bad = jnp.where(ge_bad, u >= fault.ge_p_bg,
                           u < fault.ge_p_gb) & win
        p_ev = jnp.where(win,
                         jnp.where(ge_bad, fault.ge_p_bad, fault.ge_p_good),
                         0.0)
        p_extra = jnp.zeros(n_links, jnp.float32).at[fault.ge_link].max(p_ev)
    return cap_scale, p_extra, FaultCarry(epoch=ep + 1, ge_bad=ge_bad,
                                          key=key)


@jax.named_scope("fleetsim.faults")
def apply_modulation(net: L.FluidNet, cap_scale, p_extra) -> L.FluidNet:
    """This epoch's effective FluidNet: capacity (and the proportional
    phantom drain) scaled, extra loss composed into `p_loss` as an
    independent drop stage (1 - (1-a)(1-b)).  Every downstream consumer —
    all six offered_load backends, the queue step, the gathers — reads
    the modulated arrays with no per-backend changes."""
    if cap_scale is not None:
        net = net._replace(cap=net.cap * cap_scale,
                           drain=net.drain * cap_scale)
    if p_extra is not None:
        base = 0.0 if net.p_loss is None else net.p_loss
        net = net._replace(p_loss=1.0 - (1.0 - base) * (1.0 - p_extra))
    return net


@jax.named_scope("fleetsim.faults")
def degrade_split(net: L.FluidNet, split: jnp.ndarray, cap_scale,
                  pmask: jnp.ndarray) -> jnp.ndarray:
    """The epoch's effective send split with dead paths drained.

    A path is dead when any hop's capacity multiplier is 0 this epoch;
    its weight redistributes over the flow's surviving paths (uniform
    fallback when the stored weights there round to zero).  Flows with NO
    surviving path keep the stored split unchanged: their subflow scale
    is 0 end to end, so they park at the CC floor rate — and because the
    PERSISTENT split is never overwritten here, a repaired/flapped-back
    link resumes with the pre-fault weights instantly.
    """
    cs = jnp.concatenate([cap_scale, jnp.ones(1, cap_scale.dtype)])
    # a gather of its own: the split it yields feeds the offered load, so
    # it cannot join `link_epoch`'s one take, which runs after the load
    alive = L.hop_reduce(net, cs, jnp.min) > 0.0
    ok = pmask & alive
    any_alive = jnp.any(ok, axis=1)
    w = jnp.where(ok, split, 0.0)
    return jnp.where(any_alive[:, None], L.normalize_split(w, ok), split)
