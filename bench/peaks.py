"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.

TPU v5e (JAX: "TPU v5 lite"), Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  A device that
is not in the table is an error: a share of an unknown peak is no number.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind][what]


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind
