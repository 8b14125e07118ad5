"""Uno's two-DC fat tree under permutation traffic (paper §5.1, Fig. 9).

Every host sends one long-lived flow to one other host, drawn as a
uniform derangement of all hosts from the spec seed (as
`netsim.workloads.permutation` draws them), so about half of the flows
cross the WAN.  Links and path sets come from the program's own
`TwoDCFatTree` path oracle; intra-DC sets longer than `n_paths` are
sampled as `fat_tree_spec` samples them.  Intra flows use ECMP, inter
flows UnoLB, both over `n_paths` subflows.  The conditions on the WAN are
the dumbbell builder's keys: `wan_p_loss` on every WAN link, `inter_rel`
on the inter flows, and `faults` on named links.
"""
from __future__ import annotations

import random

import numpy as np


def pairs(n_hosts: int, seed: int) -> np.ndarray:
    """(n_hosts, 2) (src, dst): a uniform derangement, one flow a host."""
    rng = np.random.default_rng([seed, 0x9E2])
    src = np.arange(n_hosts)
    while True:
        dst = rng.permutation(n_hosts)
        if not np.any(dst == src):
            return np.stack([src, dst], axis=1)


def build(*, k: int, n_wan: int, n_paths: int, rate: float,
          intra_rtt: float, inter_rtt: float, qcap: float, seed: int,
          wan_p_loss: float = 0.0, inter_rel=None, faults=()):
    from repro.netsim.topology import TwoDCFatTree
    from repro.scenarios.fat_tree import link_tier_from_name
    from repro.scenarios.spec import FlowGroup, LbSpec, LinkSpec, Scenario
    oracle = TwoDCFatTree(k=k, n_wan=n_wan, rate=rate, qcap=int(qcap),
                          intra_rtt=intra_rtt, inter_rtt=inter_rtt,
                          seed=seed, max_paths=n_paths)
    wan = {ln.name for ln in oracle.wan_links}
    links = tuple(
        LinkSpec(ln.name, ln.rate, ln.pdelay, float(ln.qcap),
                 wan=ln.name in wan, tier=link_tier_from_name(ln.name),
                 p_loss=wan_p_loss if ln.name in wan else 0.0)
        for ln in oracle.links.values())

    def path_set(s: int, d: int):
        ps = oracle.path_link_names(s, d)
        if len(ps) > n_paths:
            rng = random.Random((s * 131071 + d) ^ (seed << 12) ^ 0x5A17)
            ps = tuple(rng.sample(ps, n_paths))
        return ps

    per_dc, pod_hosts = oracle.hosts_per_dc, (k // 2) ** 2
    classes = {"intra_pod": [], "cross_pod": [], "inter": []}
    for s, d in pairs(oracle.n_hosts, seed).tolist():
        if s // per_dc != d // per_dc:
            classes["inter"].append((s, d))
        elif s // pod_hosts == d // pod_hosts:
            classes["intra_pod"].append((s, d))
        else:
            classes["cross_pod"].append((s, d))
    groups = []
    for name, prs in classes.items():
        inter = name == "inter"
        groups.append(FlowGroup(
            name, len(prs), tuple(path_set(s, d) for s, d in prs),
            inter=inter,
            lb=LbSpec(kind="unolb" if inter else "ecmp", n_subflows=n_paths),
            rel=inter_rel if inter else None))
    return Scenario(name=f"two_dc_permutation_k{k}", links=links,
                    groups=tuple(g for g in groups if g.n), rate=rate,
                    intra_rtt=intra_rtt, inter_rtt=inter_rtt, seed=seed,
                    faults=tuple(faults)).validate()
