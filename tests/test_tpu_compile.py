"""Compile every Pallas kernel of the main paths for a described TPU v5e at
the sizes its users run — the compile rehearsal that guards each change at
no chip time.  Nothing runs: the TPU compiler lowers each program for a
chip that is described, not attached, and refuses what the chip would
refuse (unaligned tiles, layouts Mosaic cannot match, VMEM overflow).

Sizes: the cross-pod payload of `chip_smoke.py` (4M f32 -> int8 + RS(8,2)
over 8 rows) and the fat_tree_k8 / 100k-flow route tensor (1,616 links,
8 ECMP paths, 9 hops; its PathTable holds ~58k unique 5-hop segments).
The link epoch's hop gathers are counted at the benchmark's fat_tree_k8
size (256 flows).
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.fleetsim import links as L
from repro.kernels import fleet_pallas, quant_pallas, rs_pallas

PAYLOAD = 1 << 22               # f32 elements of the cross-pod payload
N_FLOWS, N_PATHS, MAX_HOPS = 100_000, 8, 9
N_LINKS, N_SEGMENTS, SEG_HOPS = 1616, 58_056, 5


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the Mosaic kernel, not a fallback


def test_quant_int8_compiles(one_chip):
    _compile(lambda x: quant_pallas.quant_int8(x, interpret=False),
             one_chip, ((PAYLOAD,), jnp.float32))


def test_dequant_int8_compiles(one_chip):
    _compile(lambda q, s: quant_pallas.dequant_int8(q, s, interpret=False),
             one_chip, ((PAYLOAD,), jnp.int8),
             ((PAYLOAD // 256,), jnp.float32))


@pytest.mark.parametrize("decode", [False, True])
def test_rs_8_2_compiles(one_chip, decode):
    def fn(rows):
        if decode:
            return rs_pallas.rs_decode(rows, 8, 2, (0, 1), (0, 1),
                                       interpret=False)
        return rs_pallas.rs_encode(rows, 2, interpret=False)
    _compile(fn, one_chip, ((8, PAYLOAD // 8), jnp.uint8))


_ROUTES = ((MAX_HOPS, N_PATHS, fleet_pallas.pad_lanes(N_FLOWS)), jnp.int32)
_SUB = ((N_FLOWS, N_PATHS), jnp.float32)
_IDS = ((N_FLOWS, N_PATHS), jnp.int32)
_LINK = ((N_LINKS,), jnp.float32)
_SEGS = ((N_SEGMENTS, SEG_HOPS), jnp.int32)


@pytest.mark.parametrize("kernel", ["link_scatter", "link_gathers",
                                    "path_rates", "path_table_scatter",
                                    "path_table_gathers"])
def test_fleet_kernel_compiles_fat_tree_k8(one_chip, kernel):
    fp = fleet_pallas
    cases = {
        "link_scatter": (
            lambda i, v: fp.link_scatter(i, v, N_LINKS, interpret=False),
            (_ROUTES, _SUB)),
        "link_gathers": (
            lambda i, a, b, c: fp.link_gathers(i, a, b, c, N_FLOWS,
                                               interpret=False),
            (_ROUTES, _LINK, _LINK, _LINK)),
        "path_rates": (
            lambda a, b, v: fp.path_rates(a, b, v, N_SEGMENTS,
                                          interpret=False),
            (_IDS, _IDS, _SUB)),
        "path_table_scatter": (
            lambda a, b, s, v: fp.path_table_scatter(a, b, s, v, N_LINKS,
                                                     interpret=False),
            (_IDS, _IDS, _SEGS, _SUB)),
        "path_table_gathers": (
            lambda a, b, s, x, y, z: fp.path_table_gathers(
                a, b, s, x, y, z, interpret=False),
            (_IDS, _IDS, _SEGS, _LINK, _LINK, _LINK)),
    }
    fn, shapes = cases[kernel]
    _compile(fn, one_chip, *shapes)


BENCH_FLOWS = 256               # the benchmark's fat_tree_k8: one per server


@pytest.mark.parametrize("p_loss,with_loss,batch",
                         [(False, False, 0), (True, False, 0),
                          (True, True, 0), (False, False, 4),
                          (True, True, 4)])
def test_link_epoch_one_hop_gather_fat_tree_k8(one_chip, p_loss, with_loss,
                                               batch):
    """The csr link epoch compiles to ONE gather through the hop index,
    K = 3 to 5 rows at once, and none of one row; and each gathered row
    has a hop reduction of its own.  Where one multi-output fusion
    reduced every row of the take (lane slices of the (K, h, p, n) take,
    K minor), the v5e epoch scan answered wrong; the barrier in
    `links._hop_reduce_many` keeps the K reductions apart.  `batch`
    vmaps the epoch over that many nets, as the what-if grid runs it."""
    lead = (batch,) if batch else ()

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)
    n, p, h = BENCH_FLOWS, N_PATHS, MAX_HOPS
    e = n * p * h                  # entries; a whole number of CSR blocks
    link = spec((N_LINKS,))
    lay = L.RouteLayout(
        hop_idx=spec((h, p, fleet_pallas.pad_lanes(n)), jnp.int32),
        path_mask=spec((n, p), jnp.bool_),
        sort_sub=spec((e,), jnp.int32), sort_link=spec((e,), jnp.int32),
        link_ptr=spec((N_LINKS + 2,), jnp.int32),
        csr_gather=spec((e // L.CSR_BLOCK, L.CSR_BLOCK), jnp.int32))
    net = L.FluidNet(cap=link, qcap=link, ecn_lo=link, ecn_hi=link,
                     drain=link, vcap=link,
                     use_phantom=spec((N_LINKS,), jnp.bool_),
                     routes=spec((n, p, h), jnp.int32), dt=spec(()),
                     layout=lay, p_loss=link if p_loss else None)

    def epoch(*a):
        return L.link_epoch(*a, backend="csr", with_loss=with_loss)
    fn = jax.jit(jax.vmap(epoch) if batch else epoch)
    text = fn.lower(net, spec((n,)), spec((n, p)), link,
                    link).compile().as_text()
    k = 3 + p_loss + with_loss
    b = f"{batch}," if batch else ""
    hop = re.findall(rf"= f32\[{b}(?:(\d+),)?{h},{p},{n}\]\S* gather\(",
                     text)
    assert hop == [str(k)], hop
    per_comp = _hop_reductions(text, f"{b}{p},{n}")
    assert sum(per_comp.values()) == k, per_comp
    assert all(c == 1 for name, c in per_comp.items()
               if name.startswith("fused_computation")), per_comp


def _hop_reductions(text: str, dims: str) -> dict:
    """Computation name -> its `link_gathers` reductions to a subflow
    column of shape f32[dims], in compiled HLO text."""
    out: dict = {}
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
        elif re.search(rf"= f32\[{dims}\]\S* reduce\(", line) and \
                re.search(r"fleetsim\.link_gathers\)?/reduce_", line):
            out[comp] = out.get(comp, 0) + 1
    return out
