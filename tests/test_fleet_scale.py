"""Million-flow fleetsim machinery: RouteLayout equivalence (segment / CSR /
Pallas link aggregation vs the original scatter), the fused Pallas
link->flow gathers, locality shard plans + halo-exchange sharded steady
state vs single device, and the compensated fairness reductions at 10^5
flows."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleetsim import dumbbell, links as L, make_params, simulate
from repro.fleetsim.links import RATE_100G, US
from repro.fleetsim.sweeps import fleet_sum, jain
from repro.kernels import fleet_pallas
from repro.kernels import ref as kref
from repro.scenarios import plan_shards

INTRA_RTT = 14 * US
INTRA_BDP = RATE_100G * INTRA_RTT


def _random_net(rng, n_links=None, n_flows=None, n_paths=None, max_hops=None):
    """Random topology with -1 padding on both the hop and path axes."""
    n_links = n_links or int(rng.integers(2, 9))
    n_flows = n_flows or int(rng.integers(2, 14))
    n_paths = n_paths or int(rng.integers(1, 5))
    max_hops = max_hops or int(rng.integers(1, 5))
    routes = rng.integers(-1, n_links, size=(n_flows, n_paths, max_hops))
    routes[:, 0, 0] = rng.integers(0, n_links, size=n_flows)  # >=1 real path
    cap = jnp.asarray(rng.uniform(1.0, 20.0, n_links), jnp.float32)
    qcap = jnp.asarray(rng.uniform(10.0, 1000.0, n_links), jnp.float32)
    return L.FluidNet(cap=cap, qcap=qcap, ecn_lo=0.25 * qcap,
                      ecn_hi=0.75 * qcap, drain=0.9 * cap, vcap=qcap,
                      use_phantom=jnp.asarray(
                          rng.integers(0, 2, n_links), bool),
                      routes=jnp.asarray(routes, jnp.int32),
                      dt=jnp.float32(1.0))


def _random_rates_split(rng, net):
    n, p = net.routes.shape[:2]
    rates = jnp.asarray(rng.uniform(0.0, 10.0, n), jnp.float32)
    split = L.normalize_split(
        jnp.asarray(rng.uniform(0, 1, (n, p)), jnp.float32),
        L.path_mask(net))
    return rates, split


# ------------------------------------------------ aggregation equivalence

@pytest.mark.parametrize("backend", ["segment", "csr", "pallas", "pt",
                                     "pt_pallas"])
def test_offered_load_backends_match_reference(backend):
    """Every fast aggregation path == the `.at[].add` scatter within 1e-6
    over random route tensors (incl. -1 padding and multipath splits).
    The pt backends force the PathTable build — random tensors rarely
    compress enough for the auto policy to attach one."""
    rng = np.random.default_rng(7)
    force = backend in ("pt", "pt_pallas")
    for _ in range(12):
        net = L.with_layout(_random_net(rng),
                            path_table=True if force else "auto")
        rates, split = _random_rates_split(rng, net)
        ref = np.asarray(kref.fleet_offered_load_ref(
            net.routes, rates, split, net.n_links)[:net.n_links])
        got = np.asarray(L.offered_load(net, rates, split, backend=backend))
        # <= 1e-6 at unit scale: the fast paths sum in a different order
        # than the scatter, so the bound is on the normalized load
        scale = max(1.0, float(np.abs(ref).max()))
        np.testing.assert_allclose(got / scale, ref / scale, atol=1e-6)


def test_offered_load_trimmed_layout_matches():
    """trim=True drops the padding entries from the CSR view but the
    aggregate is unchanged."""
    rng = np.random.default_rng(11)
    for _ in range(6):
        net = _random_net(rng)
        rates, split = _random_rates_split(rng, net)
        ref = kref.fleet_offered_load_ref(
            net.routes, rates, split, net.n_links)[:net.n_links]
        got = L.offered_load(L.with_layout(net, trim=True), rates, split,
                             backend="csr")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)
        trimmed = L.compute_layout(net.routes, net.n_links, trim=True)
        full = L.compute_layout(net.routes, net.n_links)
        assert trimmed.sort_link.shape[0] <= full.sort_link.shape[0]


def test_csr_per_link_relative_error_at_scale():
    """CSR aggregation error must scale with each link's OWN load, not the
    fleet total (regression: the original global-prefix differencing had
    ulp(grand total) absolute error per link — ~13% relative on lightly
    loaded uplinks at 500k flows)."""
    n = 200_000
    net, _, _ = dumbbell(n // 2, n - n // 2, n_bottleneck=max(1, n // 64))
    rng = np.random.default_rng(2)
    rates = jnp.asarray(rng.uniform(5.0, 20.0, n), jnp.float32)
    split = L.uniform_split(net)
    ref = np.asarray(kref.fleet_offered_load_ref(
        net.routes, rates, split, net.n_links))[:net.n_links]
    got = np.asarray(L.offered_load(net, rates, split, backend="csr"))
    rel = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-9)
    assert float(rel.max()) < 1e-4, float(rel.max())


def test_layout_csr_invariants():
    """Sorted view: link ids ascending, CSR pointers consistent, every real
    route entry accounted for exactly once."""
    rng = np.random.default_rng(3)
    for _ in range(8):
        net = _random_net(rng)
        lay = L.compute_layout(net.routes, net.n_links)
        link = np.asarray(lay.sort_link)
        ptr = np.asarray(lay.link_ptr)
        assert np.all(np.diff(link) >= 0)
        assert ptr[0] == 0 and ptr[-1] == link.shape[0]
        assert np.all(np.diff(ptr) >= 0)
        for l in range(net.n_links + 1):
            assert np.all(link[ptr[l]:ptr[l + 1]] == l)
        n_real = int(np.sum(np.asarray(net.routes) >= 0))
        assert int(ptr[net.n_links]) == n_real
        routes = np.asarray(net.routes)
        hop = np.asarray(lay.hop_idx)
        n = routes.shape[0]
        assert hop.shape[:2] == routes.shape[:0:-1] and hop.shape[2] >= n
        np.testing.assert_array_equal(
            hop[..., :n],
            np.where(routes >= 0, routes, net.n_links).transpose(2, 1, 0))
        assert np.all(hop[..., n:] == net.n_links)


def test_pallas_link_gathers_match_reference():
    """The fused kernel's one pass == three separate gathers within 1e-6."""
    rng = np.random.default_rng(5)
    for _ in range(8):
        net = _random_net(rng)
        scale = jnp.asarray(rng.uniform(0.05, 1.0, net.n_links), jnp.float32)
        clean = jnp.asarray(rng.uniform(0.0, 1.0, net.n_links), jnp.float32)
        delay = jnp.asarray(rng.uniform(0.0, 50.0, net.n_links), jnp.float32)
        hop_idx = L.hop_major_idx(net.routes, net.n_links)
        got = fleet_pallas.link_gathers(hop_idx, scale, clean, delay,
                                        net.routes.shape[0], block=4)
        ref = kref.fleet_link_gathers_ref(net.routes, scale, clean, delay)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=1e-6, rtol=1e-6)


def test_pallas_scatter_pads_nondivisible_flow_counts():
    rng = np.random.default_rng(9)
    net = _random_net(rng, n_links=5, n_flows=7, n_paths=2, max_hops=3)
    rates, split = _random_rates_split(rng, net)
    # an index without lane padding: the wrapper pads it to the block
    hop_idx = L.hop_major_idx(net.routes, net.n_links)[..., :7]
    got = fleet_pallas.link_scatter(hop_idx, rates[:, None] * split,
                                    net.n_links, block=4)
    ref = kref.fleet_offered_load_ref(net.routes, rates, split, net.n_links)
    # real links must match exactly; the scratch slot is allowed to differ
    # (the kernel parks -1-hop mass there, the reference masks it out)
    np.testing.assert_allclose(np.asarray(got)[:net.n_links],
                               np.asarray(ref)[:net.n_links], atol=1e-6)


def test_simulate_backends_agree_end_to_end():
    """A full jitted simulation reaches the same state on every backend
    (pt backends on a force-built table — the dumbbell never auto-attaches
    one)."""
    net, bdp, rtt = dumbbell(3, 3)
    p = make_params(bdp, rtt, INTRA_BDP, INTRA_RTT)
    pt_net = L.with_layout(net, path_table=True)
    finals = {}
    for backend in ("reference", "segment", "csr", "pallas", "pt",
                    "pt_pallas"):
        use = pt_net if backend in ("pt", "pt_pallas") else net
        f, _ = simulate(use, p, n_epochs=300, backend=backend)
        finals[backend] = np.asarray(f.cwnd)
    for backend, cwnd in finals.items():
        np.testing.assert_allclose(cwnd, finals["reference"], rtol=1e-4,
                                   err_msg=backend)


def test_layout_backends_require_layout():
    net, bdp, rtt = dumbbell(2, 0)
    bare = net._replace(layout=None)
    with pytest.raises(ValueError):
        L.offered_load(bare, jnp.ones(2), backend="csr")
    with pytest.raises(ValueError):
        L.offered_load(bare, jnp.ones(2), backend="nope")
    # a flat layout (no table) must refuse the compressed backends rather
    # than silently fall back
    flat = L.with_layout(net, path_table=False)
    for backend in ("pt", "pt_pallas"):
        with pytest.raises(ValueError):
            L.offered_load(flat, jnp.ones(2), backend=backend)


# ------------------------------------------------- path-table compression

def test_path_table_reconstructs_routes():
    """Prefix + suffix segments reassemble each subflow's real hop
    multiset exactly — the invariant every compressed gather rests on."""
    rng = np.random.default_rng(31)
    for _ in range(8):
        net = _random_net(rng)
        pt = L.compute_path_table(net.routes, net.n_links)
        r = np.asarray(net.routes)
        n, p, h = r.shape
        seg_idx = np.asarray(pt.seg_idx)
        pre_id = np.asarray(pt.pre_id).reshape(-1)
        suf_id = np.asarray(pt.suf_id).reshape(-1)
        flat = r.reshape(n * p, h)
        for s in range(n * p):
            hops = np.concatenate([seg_idx[pre_id[s]], seg_idx[suf_id[s]]])
            hops = hops[hops < net.n_links]          # drop scratch pads
            want = flat[s][flat[s] >= 0]
            assert sorted(hops.tolist()) == sorted(want.tolist()), s


def test_path_table_auto_policy():
    """auto attaches the table only where the factorization pays: never
    on the shallow dumbbell, always on deep repetitive multipath, and
    never inside jit (tracer routes cannot be deduped host-side)."""
    net, _, _ = dumbbell(16, 16)
    assert L.compute_layout(net.routes, net.n_links).path_table is None

    # 64 flows re-walking the same 4 deep paths: dedupes massively
    deep = jnp.asarray(
        np.tile(np.arange(24, dtype=np.int32).reshape(4, 6), (64, 1, 1)))
    lay = L.compute_layout(deep, 24)
    assert lay.path_table is not None
    assert lay.path_table.n_segments <= 16

    def inside(routes):
        return L.compute_layout(routes, 24).path_table is None
    assert jax.jit(inside)(deep)        # tracer -> stays flat, no crash

    with pytest.raises(ValueError):
        jax.jit(lambda r: L.compute_layout(r, 24, path_table=True))(deep)


def test_link_epoch_pt_matches_reference_with_loss():
    """Full with_loss epoch (scale/mark/delay gathers + queue-overflow and
    p_loss thinning) agrees between the compressed and reference
    backends on lossy random nets."""
    rng = np.random.default_rng(37)
    for _ in range(6):
        net = _random_net(rng)
        net = net._replace(p_loss=jnp.asarray(
            rng.uniform(0.0, 0.05, net.n_links), jnp.float32))
        net = L.with_layout(net, path_table=True)
        rates, split = _random_rates_split(rng, net)
        qp = jnp.asarray(rng.uniform(0, 1, net.n_links),
                         jnp.float32) * net.qcap
        qv = jnp.asarray(rng.uniform(0, 1, net.n_links),
                         jnp.float32) * net.vcap
        got = L.link_epoch(net, rates, split, qp, qv, backend="pt",
                           with_loss=True)
        ref = L.link_epoch(net, rates, split, qp, qv, backend="reference",
                           with_loss=True)
        for f in got._fields:
            a, b = getattr(got, f), getattr(ref, f)
            if a is None:
                assert b is None, f
                continue
            a, b = np.asarray(a), np.asarray(b)
            scale = max(1.0, float(np.abs(b).max()))
            np.testing.assert_allclose(a / scale, b / scale, atol=2e-5,
                                       err_msg=f)


def test_path_table_sharded_pad_to_common_shape():
    """Per-shard tables with different (U, E1) are rebuilt padded to the
    widest shape so the stacked shard_map operand has one shape — and the
    padding changes nothing numerically."""
    from repro.fleetsim.shard import flow_mesh, steady_state_sharded
    from repro.fleetsim import steady_state
    net, bdp, rtt = dumbbell(6, 5, n_bottleneck=2)
    p = make_params(bdp, rtt, INTRA_BDP, INTRA_RTT)
    ii = jnp.arange(11) >= 6
    mesh = flow_mesh(1)
    _, r1 = steady_state(net, p, n_warm=2000, n_meas=500, is_inter=ii)
    _, r2 = steady_state_sharded(net, p, n_warm=2000, n_meas=500,
                                 is_inter=ii, mesh=mesh, path_table=True)
    np.testing.assert_allclose(np.asarray(r2), np.asarray(r1), atol=1e-5)


def test_stack_scenarios_strips_mismatched_tables():
    """Grid stacking keeps per-cell PathTables only when their shapes all
    agree; a mismatched mix is stripped (with a warning) so the sweep
    falls back to the flat CSR backend instead of crashing in stack."""
    from repro.fleetsim.sweeps import _strip_unstackable_path_tables
    rng = np.random.default_rng(41)
    deep_a = jnp.asarray(
        np.tile(np.arange(24, dtype=np.int32).reshape(4, 6), (8, 1, 1)))
    # same route shape but only 2 distinct paths -> fewer unique segments
    deep_b = jnp.asarray(np.tile(np.repeat(
        rng.integers(0, 24, (2, 6)).astype(np.int32), 2, axis=0),
        (8, 1, 1)))
    net_a = _random_net(rng, n_links=24, n_flows=8, n_paths=4, max_hops=6)
    na = L.with_layout(net_a._replace(routes=deep_a), path_table=True)
    nb = L.with_layout(net_a._replace(routes=deep_b), path_table=True)
    same = _strip_unstackable_path_tables((na, na))
    assert all(n.layout.path_table is not None for n in same)
    if nb.layout.path_table.seg_idx.shape == \
            na.layout.path_table.seg_idx.shape:
        pytest.skip("random tables collided to one shape")
    with pytest.warns(UserWarning, match="mismatched"):
        mixed = _strip_unstackable_path_tables((na, nb))
    assert all(n.layout.path_table is None for n in mixed)


def test_pick_block():
    """Block size tracks the route size: small inputs keep one 128-lane
    tile, mid sizes scale in powers of two, large ones saturate at
    BLOCK_ROWS, and a gather's whole-link-axis one-hot tile stays inside
    the VMEM budget."""
    fp = fleet_pallas
    assert fp.pick_block(1) == fp.LANES
    assert fp.pick_block(2048) == 256
    assert fp.pick_block(4096) == 512
    assert fp.pick_block(1_000_000) == fp.BLOCK_ROWS
    for n in (1, 3, 77, 1000, 5000, 10 ** 6):
        b = fp.pick_block(n)
        assert b & (b - 1) == 0 and fp.LANES <= b <= fp.BLOCK_ROWS
    # fat-tree k=8 gather: 1,617 link columns cap the block at 128 lanes
    b = fp.pick_block(10 ** 6, 1617)
    assert b * 1664 <= fp.ONEHOT_BUDGET and b >= fp.LANES


# --------------------------------------------------- locality shard plans

def _shards_touching(routes, n_links, plan):
    """(n_shards, n_links) bool recomputed from the plan's own flow
    assignment — the ground truth the boundary classification must match."""
    r3 = np.asarray(routes)
    r3 = r3 if r3.ndim == 3 else r3[:, None, :]
    touched = np.zeros((plan.n_shards, n_links), bool)
    for s in range(plan.n_shards):
        ids = plan.gather[s]
        links = r3[ids[ids < plan.n_real]].ravel()
        touched[s, links[links >= 0]] = True
    return touched


@pytest.mark.filterwarnings("ignore:plan_shards:RuntimeWarning")
@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_plan_shards_invariants(n_shards):
    """gather is a padded permutation of the flows, the link relabeling is
    a permutation with boundary links exactly at the tail, and every
    private link lands in its single touching shard's contiguous range."""
    rng = np.random.default_rng(13)
    for _ in range(6):
        net = _random_net(rng, n_flows=int(rng.integers(4, 30)))
        n_links = net.n_links
        plan = plan_shards(np.asarray(net.routes), n_links, n_shards)
        flat = plan.flat_gather
        real = flat[flat < plan.n_real]
        assert sorted(real.tolist()) == list(range(plan.n_real))
        assert plan.rows * n_shards >= plan.n_real
        assert sorted(plan.new2old.tolist()) == list(range(n_links))
        assert np.array_equal(plan.old2new[plan.new2old],
                              np.arange(n_links))
        inv = plan.inverse_flow
        assert np.array_equal(flat[inv], np.arange(plan.n_real))

        touched = _shards_touching(net.routes, n_links, plan)
        n_touch = touched.sum(axis=0)
        want_boundary = set(np.flatnonzero(n_touch >= 2).tolist())
        tail = set(plan.new2old[n_links - plan.n_boundary:].tolist())
        assert tail == want_boundary
        ptr = plan.owner_ptr
        assert ptr[0] == 0 and ptr[-1] == n_links - plan.n_boundary
        for s in range(n_shards):
            owned_old = plan.new2old[ptr[s]:ptr[s + 1]]
            for l in owned_old:
                # private by construction: only shard s (or nobody) uses it
                assert n_touch[l] <= 1
                if n_touch[l] == 1:
                    assert touched[s, l]


def test_plan_shards_dumbbell_boundary_is_tiny():
    """On the standard dumbbell the only cross-shard links are the WAN
    pipe and at most one downlink straddling the cut — the halo payload
    must be >= 10x smaller than the full link buffer (the CI guard)."""
    n = 4096
    net, _, _ = dumbbell(n // 2, n - n // 2, n_bottleneck=n // 64)
    plan = plan_shards(np.asarray(net.routes), net.n_links, 2)
    assert plan.n_boundary <= 3
    assert plan.boundary_frac < 0.01
    assert (plan.n_links + 1) >= 10 * plan.n_boundary
    # flows stay balanced: both shards fully populated (n divides evenly)
    assert plan.gather.shape == (2, n // 2)
    assert np.all(plan.flat_gather < plan.n_real)


def test_scatter_tiles_matches_reference():
    """The Pallas scatter buffer split at the boundary into the private
    tile and the boundary tile a halo exchange reduces == the reference
    tiles, over random routes incl. -1 padding and repeated hops."""
    rng = np.random.default_rng(21)
    for _ in range(6):
        net = _random_net(rng)
        rates, split = _random_rates_split(rng, net)
        n_boundary = int(rng.integers(1, net.n_links))
        n_priv = net.n_links - n_boundary
        buf = fleet_pallas.link_scatter(
            L.hop_major_idx(net.routes, net.n_links),
            rates[:, None] * split, net.n_links, block=4)
        priv, bnd = buf[:n_priv], buf[n_priv:]
        rp, rb = kref.fleet_offered_load_tiles_ref(
            net.routes, rates, split, net.n_links, n_boundary)
        assert priv.shape == rp.shape == (n_priv,)
        assert bnd.shape == rb.shape == (n_boundary + 1,)
        got = np.concatenate([np.asarray(priv), np.asarray(bnd)])
        want = np.concatenate([np.asarray(rp), np.asarray(rb)])
        # real links must match (normalized: the kernel sums in a
        # different order); the scratch slot is backend-specific
        scale = max(1.0, float(np.abs(want[:net.n_links]).max()))
        np.testing.assert_allclose(got[:net.n_links] / scale,
                                   want[:net.n_links] / scale, atol=1e-6)


def test_offered_load_pallas_halo_tiles():
    """offered_load(backend="pallas", halo=...) still reproduces the
    reference loads."""
    rng = np.random.default_rng(23)
    for _ in range(4):
        net = L.with_layout(_random_net(rng))
        rates, split = _random_rates_split(rng, net)
        ref = np.asarray(kref.fleet_offered_load_ref(
            net.routes, rates, split, net.n_links))[:net.n_links]
        halo = int(rng.integers(1, net.n_links))
        got = np.asarray(L.offered_load(net, rates, split,
                                        backend="pallas", halo=halo))
        scale = max(1.0, float(np.abs(ref).max()))
        np.testing.assert_allclose(got / scale, ref / scale, atol=1e-6)


def test_sharded_one_device_mesh_matches_single():
    """The full locality machinery (plan, flow/link permutation, stacked
    layouts, ownership reassembly, inverse permutation) on a 1-device
    mesh must reproduce the plain steady state — no collectives involved,
    so this runs in-process on any host."""
    from repro.fleetsim import steady_state
    from repro.fleetsim.shard import flow_mesh, steady_state_sharded
    net, bdp, rtt = dumbbell(6, 5, n_bottleneck=2)
    p = make_params(bdp, rtt, INTRA_BDP, INTRA_RTT)
    ii = jnp.arange(11) >= 6
    mesh = flow_mesh(1)
    _, r1 = steady_state(net, p, n_warm=2000, n_meas=500, is_inter=ii)
    s2, r2 = steady_state_sharded(net, p, n_warm=2000, n_meas=500,
                                  is_inter=ii, mesh=mesh)
    np.testing.assert_allclose(np.asarray(r2), np.asarray(r1), atol=1e-5)
    # unroll is loop restructuring only — same epochs, same numbers
    _, r3 = steady_state_sharded(net, p, n_warm=2000, n_meas=500,
                                 is_inter=ii, mesh=mesh, unroll=4)
    np.testing.assert_allclose(np.asarray(r3), np.asarray(r1), atol=1e-5)


# ------------------------------------------------------- sharded flow axis

def _run(code: str) -> dict:
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_steady_state_matches_single_device():
    """Locality-sharded steady state (4 CPU shards, flow count NOT
    divisible -> inert padding, boundary-only halo exchange) == the
    single-device run to float-sum tolerance across single-path,
    multipath + adaptive LB, churn-enabled, and PR-3-style full-exchange
    configurations; final per-link queue state is reassembled correctly
    from the owning shards."""
    res = _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, json
from repro.fleetsim import dumbbell, make_params, steady_state
from repro.fleetsim.shard import steady_state_sharded
from repro.fleetsim.links import RATE_100G, US
from repro.scenarios import (ChurnSpec, dumbbell_scenario, plan_shards,
                             to_fleetsim)

out = {}
net, bdp, rtt = dumbbell(5, 5)
p = make_params(bdp, rtt, RATE_100G * 14 * US, 14 * US)
ii = jnp.arange(10) >= 5
s1, r1 = steady_state(net, p, n_warm=5000, n_meas=1000, is_inter=ii)
s2, r2 = steady_state_sharded(net, p, n_warm=5000, n_meas=1000,
                              is_inter=ii)
out["err_single_path"] = float(
    np.max(np.abs(np.asarray(r1) - np.asarray(r2))))
out["err_q"] = float(
    np.max(np.abs(np.asarray(s1.q_phantom) - np.asarray(s2.q_phantom))))
out["q_scale"] = float(np.max(np.asarray(s1.q_phantom)))
plan = plan_shards(np.asarray(net.routes), net.n_links, 4)
out["n_boundary"] = plan.n_boundary
out["n_links"] = plan.n_links
# PR-3-style contiguous sharding (full-buffer exchange) must still agree
_, r2f = steady_state_sharded(net, p, n_warm=5000, n_meas=1000,
                              is_inter=ii, locality=False)
out["err_full_exchange"] = float(
    np.max(np.abs(np.asarray(r1) - np.asarray(r2f))))

fs = to_fleetsim(dumbbell_scenario(3, 5, multipath=True, n_wan=4))
_, ra = steady_state(fs.net, fs.params, n_warm=5000, n_meas=1000,
                     is_inter=fs.is_inter, lb=fs.lb)
_, rb = steady_state_sharded(fs.net, fs.params, n_warm=5000, n_meas=1000,
                             is_inter=fs.is_inter, lb=fs.lb)
out["err_multipath"] = float(
    np.max(np.abs(np.asarray(ra) - np.asarray(rb))))

US_ = 14 * US
fs2 = to_fleetsim(dumbbell_scenario(
    6, 5, intra_churn=ChurnSpec(50 * US_, 20 * US_)))
_, rc = steady_state(fs2.net, fs2.params, n_warm=3000, n_meas=1000,
                     is_inter=fs2.is_inter, churn=fs2.churn, seed=7)
_, rd = steady_state_sharded(fs2.net, fs2.params, n_warm=3000,
                             n_meas=1000, is_inter=fs2.is_inter,
                             churn=fs2.churn, seed=7)
out["err_churn"] = float(
    np.max(np.abs(np.asarray(rc) - np.asarray(rd))))
out["churn_scale"] = float(np.max(np.abs(np.asarray(rc))))
out["scale"] = float(np.max(np.abs(np.asarray(r1))))
print(json.dumps(out))
""")
    scale = max(1.0, res["scale"])
    assert res["err_single_path"] < 1e-5 * scale
    assert res["err_full_exchange"] < 1e-5 * scale
    assert res["err_multipath"] < 1e-4
    # churn flips whole flows on identical PRNG draws — any mismatch in the
    # draw alignment would show up as O(1) rate differences, not rounding
    assert res["err_churn"] < 1e-4 * max(1.0, res["churn_scale"])
    assert res["err_q"] <= 1e-4 * max(1.0, res["q_scale"])
    # the dumbbell boundary is the WAN pipe + at most the shared downlinks
    assert res["n_boundary"] < res["n_links"]


@pytest.mark.slow
def test_sharded_path_table_matches_flat_two_devices():
    """pt-sharded steady state (forced 2-device mesh, per-shard tables
    padded to a common (U, E1), halo exchange on the compressed scatter)
    == the flat-sharded run on a deep-multipath net."""
    res = _run(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np, json
from repro.fleetsim import dumbbell, make_params
from repro.fleetsim.shard import shard_scenario, steady_state_prepared
from repro.fleetsim import links as L

rng = np.random.default_rng(3)
n, p_, h, n_links = 64, 4, 6, 24
paths = np.arange(n_links, dtype=np.int32).reshape(4, 6)
routes = jnp.asarray(np.tile(paths, (n, 1, 1))[:, :p_, :])
net, bdp, rtt = dumbbell(n // 2, n - n // 2)
cap = jnp.asarray(rng.uniform(5.0, 20.0, n_links), jnp.float32)
qcap = jnp.asarray(rng.uniform(100.0, 1000.0, n_links), jnp.float32)
net = L.FluidNet(cap=cap, qcap=qcap, ecn_lo=0.25 * qcap,
                 ecn_hi=0.75 * qcap, drain=0.9 * cap, vcap=qcap,
                 use_phantom=jnp.zeros(n_links, bool), routes=routes,
                 dt=net.dt)
params = make_params(bdp, rtt, float(np.mean(np.asarray(bdp))),
                     float(np.mean(np.asarray(rtt))))
out = {}
# short horizon: this is an equivalence check, not a convergence check —
# the nonlinear CC dynamics amplify float32 reorder rounding between the
# pt and csr scatters chaotically (1e-7 at 50 epochs, 1e-2 by 200)
kw = dict(n_warm=50, n_meas=5)
sf_pt = shard_scenario(net, params, path_table=True)
out["has_pt"] = sf_pt.layouts.path_table is not None
_, r_pt = steady_state_prepared(sf_pt, **kw)
sf_flat = shard_scenario(net, params, path_table=False)
_, r_flat = steady_state_prepared(sf_flat, **kw)
out["err"] = float(np.max(np.abs(np.asarray(r_pt) - np.asarray(r_flat))))
out["scale"] = float(np.max(np.abs(np.asarray(r_flat))))
print(json.dumps(out))
""")
    assert res["has_pt"]
    assert res["err"] < 1e-5 * max(1.0, res["scale"])


# --------------------------------------------- numerical hygiene at scale

def test_fleet_sum_matches_float64_at_100k():
    """Compensated float32 sum tracks the float64 truth where the naive
    sequential float32 accumulation drifts."""
    rng = np.random.default_rng(0)
    n = 100_000
    # wide dynamic range + offset: worst-ish case for float32 accumulation
    x = (rng.lognormal(0.0, 2.0, n) + 0.125).astype(np.float32)
    want = float(np.sum(x.astype(np.float64)))
    got = float(fleet_sum(jnp.asarray(x)))
    assert abs(got - want) / abs(want) < 1e-6
    naive = np.float32(0.0)
    for c in x.reshape(-1, 1000).sum(axis=1, dtype=np.float32):
        naive += c
    # the compensated sum must beat a chunked-sequential float32 reduce
    assert abs(got - want) <= abs(float(naive) - want) + 1e-3 * abs(want)


def test_jain_regression_100k_flows():
    """Fairness metrics stay meaningful at 10^5 flows: jain() matches the
    float64 formula to 1e-6 on a heterogeneous rate vector."""
    rng = np.random.default_rng(1)
    n = 100_000
    rates = rng.gamma(2.0, 0.005, n).astype(np.float32)
    r64 = rates.astype(np.float64)
    want = float(r64.sum() ** 2 / (n * (r64 ** 2).sum()))
    got = float(jain(jnp.asarray(rates)))
    assert got == pytest.approx(want, abs=1e-6)
    # sanity: a perfectly fair fleet scores 1 even at this scale
    assert float(jain(jnp.full(n, 0.01, jnp.float32))) == \
        pytest.approx(1.0, abs=1e-6)
