"""Compile every Pallas kernel of the main paths for a described TPU v5e at
the sizes its users run — the compile rehearsal that guards each change at
no chip time.  Nothing runs: the TPU compiler lowers each program for a
chip that is described, not attached, and refuses what the chip would
refuse (unaligned tiles, layouts Mosaic cannot match, VMEM overflow).

Sizes: the cross-pod payload of `chip_smoke.py` (4M f32 -> int8 + RS(8,2)
over 8 rows) and the fat_tree_k8 / 100k-flow route tensor (1,616 links,
8 ECMP paths, 9 hops; its PathTable holds ~58k unique 5-hop segments).
"""
import jax
import jax.numpy as jnp
import pytest

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
