"""Pallas TPU kernels for the paper's hot spots (RS coding, int8 quant, the
fleetsim flow<->link exchange), their jnp oracles (`ref`) and the padded
public API (`ops`)."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a `pallas_call` runs in the Pallas interpreter.

    The kernels are Mosaic TPU kernels: on a TPU backend they always
    compile — nothing can select the interpreter there.  Every other
    backend interprets by default; `interpret=False` is honoured off the
    TPU only so a compile rehearsal can lower a kernel for a described
    (not attached) chip.
    """
    if jax.default_backend() == "tpu":
        return False
    return True if interpret is None else bool(interpret)
