"""Plain reference of the fluid epoch that fleetsim's scan computes.

Written from the model's description (Uno's Algorithm 1 controller on one
epoch clock, UnoLB-style split weights, dynamic EC + NACK recovery, and
scheduled link faults), in straightforward `jax.numpy` and independent of
the code under test: it imports nothing of `repro`, and it reads only the
scenario's input arrays (links, routes, per-flow constants, fault events,
EC geometry).  It builds no layout, path table or binomial table of the
program's: the flow -> link load is one scatter-add over the raw route
tensor, every link -> flow reduction one gather over it, and the EC pmf
coefficients come from `math.comb` here.

Per-subflow arrays are kept hop-major, (paths, flows) and (hops, paths,
flows), so that the chip's compiler builds the gathers quickly at a
million flows.  `dtype` sets the float precision of the whole epoch:
float32 is the reference, bfloat16 the control that a correct run must
tell apart from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-9
FRAC_EPS = 1e-6
MAX_R = 16

# scenario input fields, by family; `None`-valued ones are simply absent
NET_FIELDS = ("cap", "qcap", "ecn_lo", "ecn_hi", "drain", "vcap",
              "use_phantom", "p_loss")
PARAM_FIELDS = ("bdp", "rtt", "mtu", "alpha", "k_md", "beta", "ewma_g",
                "gentle_scale", "gentle_floor", "md_cap", "delay_thresh",
                "min_cwnd", "max_cwnd", "cc_period", "qa_period")
LB_FIELDS = ("eta", "repath_thresh", "repath_patience", "w_floor", "ec_eff")
REL_FIELDS = ("enabled", "ec_k", "ec_r", "ec_eff", "nack_period",
              "nack_hold", "loss_md", "rtx_cap", "nack_quantum")
LADDER_FIELDS = ("adapt_on", "ladder_k", "ladder_r", "ladder_eff",
                 "ladder_up", "ladder_down")
FAULT_FIELDS = ("link", "t0", "t1", "cap_frac", "period", "duty",
                "ge_link", "ge_t0", "ge_t1", "ge_p_good", "ge_p_bad",
                "ge_p_gb", "ge_p_bg")
REL_STATE = ("pending", "backlog", "ack_cd", "hold", "md_cd", "rtx_ewma",
             "lat_ewma", "nacks", "rec_bytes", "rtx_bytes", "wire_bytes",
             "lost_bytes", "rung", "loss_ewma", "adapt_cd")
# per-subflow state fields, stored (paths, flows) here
SUBFLOW = ("split", "path_frac", "bad_count")


def coef_row(k: int, r: int) -> np.ndarray:
    """C(k+r, i) for i <= r, zero past the parity window (MAX_R + 1,)."""
    return np.asarray([math.comb(k + r, i) if i <= r else 0.0
                       for i in range(MAX_R + 1)], np.float32)


def inputs(sc: dict) -> dict:
    """Reference inputs from a scenario's plain arrays.

    `sc` maps "routes" (flows, paths, hops; -1 pads), "dt", "is_inter",
    and the fields above under "net", "params", "lb", "rel", "fault"
    (each a dict or None).  Routes become the hop-major index (hops,
    paths, flows) with pads pointing at the scratch slot n_links."""
    r = np.asarray(sc["routes"])
    if r.ndim == 2:
        r = r[:, None, :]
    n_links = int(np.asarray(sc["net"]["cap"]).shape[0])
    idx = np.transpose(np.where(r >= 0, r, n_links), (2, 1, 0))
    inp = {"idx": jnp.asarray(idx, jnp.int32),
           "hop": jnp.asarray(np.transpose(r >= 0, (2, 1, 0))),
           "dt": jnp.float32(sc["dt"]),
           "is_inter": jnp.asarray(sc["is_inter"])}
    for fam in ("net", "params", "lb", "fault"):
        d = sc.get(fam)
        inp[fam] = None if d is None else {
            k: jnp.asarray(v) for k, v in d.items() if v is not None}
    rel = sc.get("rel")
    if rel is None:
        inp["rel"] = None
    else:
        rd = {k: jnp.asarray(rel[k]) for k in REL_FIELDS}
        k = np.asarray(rel["ec_k"]).astype(int)
        r_ = np.asarray(rel["ec_r"]).astype(int)
        en = np.asarray(rel["enabled"])
        table = {kr: coef_row(*kr) for kr in set(zip(k.tolist(),
                                                      r_.tolist()))}
        rd["coef"] = jnp.asarray(np.stack(
            [table[(a, b)] for a, b in zip(k.tolist(), r_.tolist())])
            * en[:, None])
        if rel.get("ladder_k") is not None:
            for f in LADDER_FIELDS:
                rd[f] = jnp.asarray(rel[f])
            rd["ladder_coef"] = jnp.asarray(np.stack(
                [coef_row(int(a), int(b)) for a, b in
                 zip(np.asarray(rel["ladder_k"]),
                     np.asarray(rel["ladder_r"]))]))
        inp["rel"] = rd
    return inp


def init_state(inp: dict, cwnd0=None, seed: int = 0) -> dict:
    """Line-rate start (cwnd = BDP unless given), empty queues, uniform
    split over each flow's real paths, idle recovery machine."""
    p = inp["params"]
    n = p["bdp"].shape[0]
    n_links = inp["net"]["cap"].shape[0]
    f0 = jnp.zeros(n, jnp.float32)
    i0 = jnp.zeros(n, jnp.int32)
    real = jnp.any(inp["hop"], axis=0).astype(jnp.float32)      # (p, n)
    split = real / jnp.maximum(jnp.sum(real, axis=0, keepdims=True), 1.0)
    st = {"cwnd": p["bdp"] if cwnd0 is None else jnp.asarray(cwnd0),
          "ecn_ewma": f0, "md_scale": jnp.ones_like(f0),
          "q_phys": jnp.zeros(n_links, jnp.float32),
          "q_phantom": jnp.zeros(n_links, jnp.float32),
          "obs_frac": f0, "obs_delay": f0, "win_acked": f0,
          "win_marked": f0, "win_delay_min": jnp.full_like(f0, jnp.inf),
          "win_delay_max": f0, "cc_countdown": p["cc_period"],
          "qa_acked": f0, "qa_prev_acked": f0, "qa_deficits": i0,
          "qa_countdown": p["qa_period"], "skip": i0, "fi_clean": i0,
          "fi_active": jnp.zeros(n, bool), "fi_ceiling": p["max_cwnd"],
          "split": split, "path_frac": jnp.zeros_like(split),
          "bad_count": jnp.zeros(split.shape, jnp.int32),
          "active": jnp.ones(n, bool), "key": jax.random.PRNGKey(seed)}
    rel = inp["rel"]
    if rel is not None:
        z = jnp.zeros(n, jnp.float32)
        st["rel"] = {f: z for f in REL_STATE}
        st["rel"].update(ack_cd=rel["nack_period"],
                         hold=jnp.zeros(n, jnp.int32),
                         rung=jnp.zeros(n, jnp.int32))
    if inp["fault"] is not None:
        g = inp["fault"]["ge_link"].shape[0]
        st["fault"] = {"epoch": jnp.int32(0),
                       "ge_bad": jnp.zeros(g, bool),
                       "key": jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 0xFA)}
    return st


def cast(tree, dtype):
    """Every floating leaf of `tree` in `dtype`; other leaves unchanged."""
    def one(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) \
            else x
    return jax.tree.map(one, tree)


def _normalize(w, mask, floor=None):
    m = mask.astype(w.dtype)
    w = jnp.maximum(w, 0.0) * m
    n_valid = jnp.maximum(jnp.sum(m, axis=0, keepdims=True), 1.0)
    if floor is not None:
        w = jnp.maximum(w, (floor[None, :] / n_valid) * m)
    s = jnp.sum(w, axis=0, keepdims=True)
    return jnp.where(s > EPS, w / jnp.maximum(s, EPS), m / n_valid)


def _hops(inp, per_link, pad):
    """(hops, paths, flows) values of `per_link` along every route."""
    ext = jnp.concatenate([per_link, jnp.full((1,), pad, per_link.dtype)])
    return ext[inp["idx"]]


def epoch(inp: dict, st: dict):
    """One epoch: (state', goodput).  Uno scheme, backlogged flows."""
    net, p, lb, rel, fault = (inp["net"], inp["params"], inp["lb"],
                              inp["rel"], inp["fault"])
    dt = inp["dt"]
    fdt = p["bdp"].dtype
    mask = jnp.any(inp["hop"], axis=0)                        # (p, n)
    n_links = net["cap"].shape[0]
    cap, drain = net["cap"], net["drain"]
    p_loss = net.get("p_loss")
    split = st["split"]
    out = dict(st)

    if fault is not None:
        fc = st["fault"]
        ep = fc["epoch"]
        cap_scale = None
        if fault["link"].shape[0]:
            active = (ep >= fault["t0"]) & (ep < fault["t1"])
            per = fault["period"]
            phase = jnp.mod(ep - fault["t0"], jnp.maximum(per, 1))
            flap_on = phase.astype(fdt) < fault["duty"] * per.astype(fdt)
            down = active & jnp.where(per > 0, flap_on, True)
            eff = jnp.where(down, fault["cap_frac"], 1.0).astype(fdt)
            cap_scale = jnp.ones(n_links, fdt).at[fault["link"]].min(eff)
        ge_bad, key = fc["ge_bad"], fc["key"]
        if fault["ge_link"].shape[0]:
            key, sub = jax.random.split(fc["key"])
            u = jax.random.uniform(sub, fault["ge_link"].shape)
            win = (ep >= fault["ge_t0"]) & (ep < fault["ge_t1"])
            ge_bad = jnp.where(ge_bad, u >= fault["ge_p_bg"],
                               u < fault["ge_p_gb"]) & win
            p_ev = jnp.where(win, jnp.where(ge_bad, fault["ge_p_bad"],
                                            fault["ge_p_good"]), 0.0)
            p_extra = jnp.zeros(n_links, fdt).at[fault["ge_link"]].max(
                p_ev.astype(fdt))
            base = 0.0 if p_loss is None else p_loss
            p_loss = 1.0 - (1.0 - base) * (1.0 - p_extra)
        out["fault"] = {"epoch": ep + 1, "ge_bad": ge_bad, "key": key}
        if cap_scale is not None:
            cap, drain = cap * cap_scale, drain * cap_scale
            if split.shape[0] > 1:     # send only on paths that are up
                alive = jnp.min(_hops(inp, cap_scale, 1.0), axis=0) > 0.0
                ok = mask & alive
                w = jnp.where(ok, split, 0.0)
                split = jnp.where(jnp.any(ok, axis=0)[None, :],
                                  _normalize(w, ok), split)

    rate = st["cwnd"] / p["rtt"]
    if rel is not None:
        rs = st["rel"]
        rtx = jnp.minimum(rs["backlog"] / jnp.maximum(p["rtt"], 1.0),
                          rel["rtx_cap"] * rate)
        wire = rate + rtx
    else:
        wire = rate

    # flow -> link: scatter-add every subflow's rate onto each hop
    per_hop = jnp.where(inp["hop"], (wire[None, :] * split)[None], 0.0)
    load = jnp.zeros(n_links + 1, fdt).at[inp["idx"]].add(per_hop)
    load = load[:n_links]
    q_prev = st["q_phys"]
    q_phys = jnp.clip(q_prev + (load - cap) * dt, 0.0, net["qcap"])
    q_ph = jnp.clip(st["q_phantom"] + (load - drain) * dt, 0.0,
                    net["vcap"])
    q_mark = jnp.where(net["use_phantom"], q_ph, q_phys)
    p_link = jnp.clip((q_mark - net["ecn_lo"])
                      / jnp.maximum(net["ecn_hi"] - net["ecn_lo"], EPS),
                      0.0, 1.0)

    # link -> flow: min / product / sum over each subflow's hops
    sub_scale = jnp.min(_hops(inp, jnp.minimum(
        1.0, cap / jnp.maximum(load, EPS)), 1.0), axis=0)
    sub_frac = 1.0 - jnp.prod(_hops(inp, 1.0 - p_link, 1.0), axis=0)
    sub_delay = jnp.sum(_hops(inp, q_phys / jnp.maximum(cap, EPS), 0.0),
                        axis=0)
    if p_loss is not None:
        sub_scale = sub_scale * jnp.prod(_hops(inp, 1.0 - p_loss, 1.0),
                                         axis=0)
    sc = jnp.sum(split * sub_scale, axis=0)
    inst_frac = jnp.sum(split * sub_frac, axis=0)
    inst_delay = jnp.sum(split * sub_delay, axis=0)
    goodput = wire * sc

    if rel is not None:
        p_drop = jnp.clip(jnp.maximum(q_prev + (load - cap) * dt
                                      - net["qcap"], 0.0)
                          / jnp.maximum(load * dt, EPS), 0.0, 1.0)
        if p_loss is not None:
            p_drop = 1.0 - (1.0 - p_drop) * (1.0 - p_loss)
        sub_loss = 1.0 - jnp.prod(_hops(inp, 1.0 - p_drop, 1.0), axis=0)
        new_rel, cut, recovered = _rel_epoch(
            rel, rs, rate, rtx, wire, jnp.sum(split * sub_loss, axis=0),
            dt, p["rtt"])
        out["rel"] = new_rel

    fb = jnp.minimum(dt / p["rtt"], 1.0)
    frac = st["obs_frac"] + fb * (inst_frac - st["obs_frac"])
    delay = st["obs_delay"] + fb * (inst_delay - st["obs_delay"])
    path_frac = st["path_frac"] if lb is None else \
        st["path_frac"] + fb[None, :] * (sub_frac - st["path_frac"])
    acked = goodput * dt
    win_acked = st["win_acked"] + acked
    win_marked = st["win_marked"] + frac * acked
    win_dmin = jnp.minimum(st["win_delay_min"], delay)
    fire = st["cc_countdown"] <= 1
    can_md = st["skip"] <= 0
    wfrac = win_marked / jnp.maximum(win_acked, 1.0)
    marked = wfrac > FRAC_EPS

    # additive increase, and fast increase after clean windows
    inc = p["alpha"] * acked * (1.0 - frac) / jnp.maximum(st["cwnd"], 1.0)
    m_fi = inst_frac > FRAC_EPS
    fi_on = st["fi_active"] & ~m_fi
    inc = jnp.where(fi_on, jnp.maximum(inc, acked * (1.0 - frac)), inc)
    cwnd = st["cwnd"] + inc

    # Algorithm 1 window reaction, once per epoch
    ecn_ewma = jnp.where(fire, (1.0 - p["ewma_g"]) * st["ecn_ewma"]
                         + p["ewma_g"] * wfrac, st["ecn_ewma"])
    gentle = jnp.where(win_dmin < p["delay_thresh"],
                       jnp.maximum(st["md_scale"] * p["gentle_scale"],
                                   p["gentle_floor"]), 1.0)
    cut_md = fire & marked & can_md
    md_scale = jnp.where(cut_md, gentle,
                         jnp.where(fire & ~marked, 1.0, st["md_scale"]))
    gain = 4.0 * p["k_md"] / (p["k_md"] + p["bdp"])
    factor = 1.0 - jnp.minimum(ecn_ewma * gain * md_scale, p["md_cap"])
    cwnd = jnp.where(cut_md, jnp.maximum(cwnd * factor, p["min_cwnd"]),
                     cwnd)
    out["win_acked"] = jnp.where(fire, 0.0, win_acked)
    out["win_marked"] = jnp.where(fire, 0.0, win_marked)
    out["win_delay_min"] = jnp.where(fire, jnp.inf, win_dmin)
    out["cc_countdown"] = jnp.where(fire, p["cc_period"],
                                    st["cc_countdown"] - 1)

    # fast-increase bookkeeping at window close
    fi_clean = jnp.where(fire, jnp.where(m_fi, 0, st["fi_clean"] + 1),
                         st["fi_clean"])
    engage = (fi_clean >= 3) & (cwnd < 0.7 * st["fi_ceiling"])
    out["fi_clean"] = fi_clean
    out["fi_active"] = jnp.where(fire, ~m_fi & (fi_on | engage), fi_on)
    out["fi_ceiling"] = jnp.where(fire & m_fi,
                                  jnp.maximum(cwnd, 4.0 * p["min_cwnd"]),
                                  st["fi_ceiling"])

    # Quick-Adapt, once per flow RTT
    qa_acked = st["qa_acked"] + acked
    tick = st["qa_countdown"] <= 1
    deficit = tick & (st["cwnd"] >= 4.0 * p["mtu"]) \
        & (qa_acked < p["beta"] * st["cwnd"])
    trigger = deficit & (st["qa_deficits"] >= 1) & can_md
    cwnd = jnp.where(trigger, jnp.maximum(
        jnp.maximum(qa_acked, st["qa_prev_acked"]), p["min_cwnd"]), cwnd)
    out["qa_deficits"] = jnp.where(
        tick, jnp.where(deficit & ~trigger, st["qa_deficits"] + 1, 0),
        st["qa_deficits"])
    skip = jnp.maximum(st["skip"] - 1, 0)
    out["skip"] = jnp.where(trigger, 2 * p["qa_period"], skip)
    out["qa_prev_acked"] = jnp.where(tick, qa_acked, st["qa_prev_acked"])
    out["qa_acked"] = jnp.where(tick, 0.0, qa_acked)
    out["qa_countdown"] = jnp.where(tick, p["qa_period"],
                                    st["qa_countdown"] - 1)

    if rel is not None:      # a NACK batch cuts the window, once per RTT
        cwnd = jnp.where(cut & can_md,
                         jnp.maximum(cwnd * rel["loss_md"], p["min_cwnd"]),
                         cwnd)
    out["cwnd"] = jnp.clip(cwnd, p["min_cwnd"], p["max_cwnd"])

    if lb is not None:       # UnoLB weights shift toward cleaner paths
        bad = mask & (path_frac > lb["repath_thresh"][None, :])
        bad_count = jnp.where(bad, st["bad_count"] + 1, 0)
        repath = bad_count >= lb["repath_patience"][None, :]
        w = split * jnp.exp(-lb["eta"][None, :] * path_frac)
        w = jnp.where(repath, 0.0, w)
        out["bad_count"] = jnp.where(repath, 0, bad_count)
        out["split"] = _normalize(w, mask, lb["w_floor"])
        if rel is None:
            goodput = goodput * lb["ec_eff"]
    if rel is not None:
        eff = rel["ec_eff"]
        if "ladder_eff" in rel:
            eff = jnp.where(rel["adapt_on"], rel["ladder_eff"][rs["rung"]],
                            eff)
        goodput = goodput * eff + rtx * sc * (1.0 - eff) + recovered
    out.update(ecn_ewma=ecn_ewma, md_scale=md_scale, q_phys=q_phys,
               q_phantom=q_ph, obs_frac=frac, obs_delay=delay,
               win_delay_max=st["win_delay_max"], path_frac=path_frac)
    return out, goodput


def _rel_epoch(rel, st, rate, rtx, wire, loss, dt, rtt):
    """EC parity recovery within the block, NACK batches beyond it, and
    the adaptive EC ladder; returns (state', cut mask, recovered rate)."""
    fdt = rate.dtype
    ec_k, ec_r, coef = rel["ec_k"], rel["ec_r"], rel["coef"]
    ladder = "ladder_k" in rel
    if ladder:
        on = rel["adapt_on"]
        ec_k = jnp.where(on, rel["ladder_k"][st["rung"]], ec_k)
        ec_r = jnp.where(on, rel["ladder_r"][st["rung"]], ec_r)
        coef = jnp.where(on[:, None], rel["ladder_coef"][st["rung"]], coef)
    q = jnp.clip(loss, 0.0, 1.0)
    n = ec_k + ec_r
    i = jnp.arange(MAX_R + 1, dtype=fdt)[None, :]
    pmf = coef * jnp.power(q[:, None], i) * \
        jnp.power(1.0 - q[:, None], jnp.maximum(n[:, None] - i, 0.0))
    rec_win = jnp.sum(i * pmf, axis=1)              # E[X; X <= r]
    nack_win = jnp.maximum(n * q - rec_win, 0.0)    # E[X; X > r]
    scale = jnp.where(rel["enabled"], ec_k / jnp.maximum(n * n, 1.0), 0.0)
    recovered = rate * rec_win * scale
    pending = st["pending"] + rate * nack_win * scale * dt + rtx * q * dt
    tick = st["ack_cd"] <= 1
    fire = tick & (st["hold"] <= 0) & (pending >= rel["nack_quantum"]) \
        & rel["enabled"]
    backlog = jnp.maximum(st["backlog"] - rtx * dt, 0.0) + \
        jnp.where(fire, pending, 0.0)
    cut = fire & (st["md_cd"] <= 0.0)
    new = dict(st)
    new.update(
        pending=jnp.where(fire, 0.0, pending), backlog=backlog,
        hold=jnp.where(fire, rel["nack_hold"], jnp.maximum(st["hold"] - 1,
                                                           0)),
        ack_cd=jnp.where(tick, rel["nack_period"], st["ack_cd"] - 1),
        md_cd=jnp.where(cut, rtt, jnp.maximum(st["md_cd"] - dt, 0.0)))
    g = jnp.minimum(dt / rtt, 1.0)
    if ladder:
        n_rungs = rel["ladder_k"].shape[0]
        loss_ewma = st["loss_ewma"] + g * (q - st["loss_ewma"])
        cd = jnp.maximum(st["adapt_cd"] - dt, 0.0)
        can = rel["adapt_on"] & rel["enabled"] & (cd <= 0.0)
        up = can & (loss_ewma > rel["ladder_up"][st["rung"]]) \
            & (st["rung"] < n_rungs - 1)
        dn = can & (loss_ewma < rel["ladder_down"][st["rung"]]) \
            & (st["rung"] > 0)
        new.update(rung=st["rung"] + up.astype(jnp.int32)
                   - dn.astype(jnp.int32), loss_ewma=loss_ewma,
                   adapt_cd=jnp.where(up | dn, rtt, cd))
    lat_nack = 1.5 * rtt + 0.5 * (rel["nack_period"] + rel["nack_hold"]) \
        .astype(fdt) * dt
    vol = recovered + rtx
    inst_lat = (recovered * rtt + rtx * lat_nack) / jnp.maximum(vol, EPS)
    new.update(
        rtx_ewma=st["rtx_ewma"] + g * (rtx - st["rtx_ewma"]),
        lat_ewma=jnp.where(vol > 0.0,
                           st["lat_ewma"] + g * (inst_lat - st["lat_ewma"]),
                           st["lat_ewma"]),
        nacks=st["nacks"] + fire.astype(fdt),
        rec_bytes=st["rec_bytes"] + recovered * dt,
        rtx_bytes=st["rtx_bytes"] + rtx * dt,
        wire_bytes=st["wire_bytes"] + wire * dt,
        lost_bytes=st["lost_bytes"] + wire * q * dt)
    return new, cut, recovered


@functools.partial(jax.jit, static_argnames=("n_epochs", "n_meas"))
def run(inp: dict, st: dict, n_epochs: int, n_meas: int = 0):
    """`n_epochs` epochs from `st`; returns (state', mean goodput over the
    last `n_meas` epochs, or None when n_meas == 0)."""
    def body(carry, e):
        s, acc = carry
        s, g = epoch(inp, s)
        keep = e >= n_epochs - n_meas
        return (s, acc + jnp.where(keep, g, 0.0)), None

    acc0 = jnp.zeros_like(st["cwnd"])
    (st, acc), _ = jax.lax.scan(body, (st, acc0), jnp.arange(n_epochs))
    return st, (acc / n_meas if n_meas else None)
