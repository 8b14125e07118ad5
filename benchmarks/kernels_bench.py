"""Kernel microbenchmarks: RS encode/decode + int8 quant throughput, plus
the fleetsim link scatter/gather kernels at an auto-picked vs fixed flow
block.

On this CPU container the Pallas kernels run in interpret mode, so absolute
numbers are not TPU numbers; we therefore report (a) wall time of the
jnp-oracle path (what the dry-run embeds), (b) interpret-mode correctness
sweep timing, and (c) the analytic VPU-op count per byte of the bit-sliced
kernel — the quantity the roofline in EXPERIMENTS.md §Perf uses:

  encode (k=8, r=2): per k rows: <=8 xtime steps (4 int ops) shared across
  parity rows + <=2*8 masked XOR accumulates -> ~*6 int32 vector ops per
  input byte lane*, i.e. ~0.75 ops/byte/parity-row.

The fleet section times link_scatter / link_gathers at a small flow count
under pick_block(n) (the default since the hardcoded BLOCK_FLOWS=512 fix
— at n=1024 it picks 128) against the old fixed 512-row block.  Read the
two with care: on compiled hardware a padded grid mostly processes
sentinel rows (the cost the hardcode used to hide), while in interpret
mode the per-grid-step Python overhead instead rewards FEWER, larger
blocks — both numbers land in the JSON so the trade is visible rather
than asserted.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.fleetsim.links import hop_major_idx
from repro.kernels import fleet_pallas, ops, ref


def _time(fn, *args, reps=5):
    fn(*args)                                    # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(quick: bool = True) -> dict:
    rng = np.random.default_rng(0)
    B = 1 << (18 if quick else 22)
    data = jnp.asarray(rng.integers(0, 256, (8, B), dtype=np.uint8))
    flat = jnp.asarray(rng.normal(size=B * 4).astype(np.float32))

    enc_ref = jax.jit(lambda d: ref.rs_encode_ref(d, 2))
    t_enc_ref = _time(enc_ref, data)
    t_enc_pallas = _time(lambda d: ops.rs_encode(d, 2), data)
    parity = ops.rs_encode(data, 2)
    surv = jnp.concatenate([data[2:], parity], 0)
    t_dec = _time(lambda s: ops.rs_decode(s, 8, 2, (0, 1), (0, 1)), surv)
    t_q = _time(lambda x: ops.quant_int8(x)[0], flat)

    mb = 8 * B / 1e6
    out = {
        "payload_MB": mb,
        "rs_encode_ref_jnp_MBps": mb / t_enc_ref,
        "rs_encode_pallas_interp_MBps": mb / t_enc_pallas,
        "rs_decode_pallas_interp_MBps": mb / t_dec,
        "quant_int8_MBps": 4 * B / 1e6 / t_q,
        "analytic_vpu_ops_per_byte_encode": 6.0 / 8.0,
        "note": "interpret-mode wall times (CPU container); the analytic "
                "ops/byte is what the TPU roofline uses",
    }
    out["fleet_kernels"] = _fleet_kernels(rng)
    common.save("kernels_bench", out)
    return out


def _fleet_kernels(rng, n=1024, p=4, h=5, n_links=64) -> dict:
    """Interpret-mode wall times of the fleetsim scatter/gather kernels at
    a flow count where the block size matters: pick_block(1024) = 128 vs
    the old hardcoded 512 (3/4 of every padded 512-grid row is
    sentinels on compiled hardware; interpret mode pays per grid step
    instead — see the module docstring)."""
    routes = rng.integers(-1, n_links, size=(n, p, h)).astype(np.int32)
    routes[:, 0, 0] = rng.integers(0, n_links, size=n)
    hop_idx = hop_major_idx(jnp.asarray(routes), n_links)
    sub = jnp.asarray(rng.uniform(0, 1, (n, p)).astype(np.float32))
    scale = jnp.asarray(rng.uniform(0.1, 2.0, n_links + 1), jnp.float32)
    frac = jnp.asarray(rng.uniform(0, 1, n_links + 1), jnp.float32)
    delay = jnp.asarray(rng.uniform(0, 50, n_links + 1), jnp.float32)

    picked = fleet_pallas.pick_block(n)
    res = {"n_flows": n, "picked_block": picked}
    for label, blk in (("picked", None), ("fixed512", 512)):
        t_s = _time(lambda pi, s: fleet_pallas.link_scatter(
            pi, s, n_links, block=blk), hop_idx, sub)
        t_g = _time(lambda pi, a, b, c: fleet_pallas.link_gathers(
            pi, a, b, c, n, block=blk), hop_idx, scale, frac, delay)
        res[f"link_scatter_{label}_ms"] = round(t_s * 1e3, 2)
        res[f"link_gathers_{label}_ms"] = round(t_g * 1e3, 2)
    return res
