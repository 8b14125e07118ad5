"""The work a training step must do, from the configuration's published
widths and the traffic's shapes, whatever the program's implementation.

Model FLOPs per token (the PaLM count, forward and backward, nothing
recomputed counted):

    6 * N + 12 * L * (H * hd) * S

N counts the weights that take part in a matmul: per layer the q, k, v
and o projections and the three MLP matrices, and the output head
(d_model x vocab; tied, the embedding lookup does no FLOPs).  The second
term is attention's scores and weighted sum over the whole S x S matrix.

Bytes each Uno kernel must move per step on one chip: every chip sends
its whole gradient (replicated weights) of `n` float32 values over the
pod hop, so per step it quantizes n values and dequantizes n received
ones, RS-encodes the n int8 bytes into parity, and RS-decodes y of every
x rows from x survivor rows:

    quant    4n read + n int8 + 4n/block scales written
    dequant  n int8 + 4n/block scales read, 4n written
    rs       encode (n read + n*y/x written) + decode (the same)
"""
from __future__ import annotations

QUANT_BLOCK = 256        # values per int8 scale (RunConfig's int8 format)


def matmul_params(m: dict) -> int:
    d, f, V, L = m["d_model"], m["d_ff"], m["vocab"], m["n_layers"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    mlp = (3 if m["act"] == "swiglu" else 2) * d * f
    return L * (2 * d * qd + 2 * d * kvd + mlp) + d * V


def n_params(m: dict) -> int:
    """Every weight: the matmuls' and the norms' (2 per layer + final)."""
    return matmul_params(m) + (2 * m["n_layers"] + 1) * m["d_model"]


def flops_per_token(m: dict, seq: int) -> int:
    return 6 * matmul_params(m) + 12 * m["n_layers"] * m["n_heads"] * \
        m["head_dim"] * seq


def step_flops(cell: dict) -> int:
    tr = cell["traffic"]
    return flops_per_token(cell["config"]["model"], tr["seq"]) * \
        tr["rows"] * tr["seq"]


def kernel_bytes(cell: dict) -> dict:
    """{"quant", "dequant", "rs"}: bytes per step on one chip."""
    n = n_params(cell["config"]["model"])
    uno = cell["config"]["uno"]
    x, y = uno["uno_ec_data"], uno["uno_ec_parity"]
    scales = 4 * n / QUANT_BLOCK
    return {"quant": 4 * n + n + scales, "dequant": n + scales + 4 * n,
            "rs": 2 * (n + n * y / x)}


def chips(cell: dict) -> int:
    out = 1
    for v in cell["traffic"]["mesh"].values():
        out *= v
    return out


def roofline(run, kernel: str, wrapper: str):
    """A Uno kernel's roofline share in a traced run, in percent: the
    bytes it must move in the traced steps over its device self time (the
    trace's ops named after its jitted wrapper, `%quant_int8.4 = ...
    custom-call(...)`, averaged over the chips) x HBM bandwidth.  The
    kernels do no MXU work, so bandwidth is their roofline.  None where
    the kernel did not run."""
    import re

    from bench import peaks
    s = run.trace_summary
    steps = sum(c[3] for c in run.traced_calls)
    if not s or not steps:
        return None
    pat = re.compile(rf"^%{wrapper}(\.\d+)? = ")
    t = sum(v for k, v in s["scan_ops"].items() if pat.match(k))
    if not t:
        return None
    moved = kernel_bytes(run.cell)[kernel] * steps
    return 100.0 * moved / (t * peaks.peak(peaks.device_kind(),
                                           "hbm_bytes_per_s"))
