"""The training cell with its timed path broken underneath comes out not
correct, at a tiny size on four forced CPU devices: once for each fault
the cell can have (a step that hands back its state unchanged; half of
the batch left out, the mean taken over the rest; the exchange between
the pods left out; an answer altered where it is produced), and for two
rows of every block lost on the pod hop with no RS decode to rebuild
them.  The control, the reference in float8, is not correct either."""
import pytest

import tiny

PLANT = {
    "stale_state": """
from repro import train
make = train.make_train_step
def broken(*a, **kw):
    step = make(*a, **kw)
    def frozen(state, batch, idx):
        _, metrics = step(state, batch, idx)
        return state, metrics
    return frozen
train.make_train_step = broken
""",
    "half_batch": """
import jax.numpy as jnp
from repro import train
make = train.make_train_step
def broken(*a, **kw):
    step = make(*a, **kw)
    def halved(state, batch, idx):
        def first_half(x):
            h = x.shape[0] // 2
            return jnp.concatenate([x[:h], x[:h]])
        return step(state, {k: first_half(v) for k, v in batch.items()},
                    idx)
    return halved
train.make_train_step = broken
""",
    "exchange_left_out": """
from repro.core import uno_collectives
uno_collectives._pod_ring_psum = lambda v, run, n_pods, axis="pod": v
""",
    "altered_answer": """
from repro import train
make = train.make_train_step
def broken(*a, **kw):
    step = make(*a, **kw)
    def altered(state, batch, idx):
        new, metrics = step(state, batch, idx)
        return new, dict(metrics, loss=metrics["loss"] * 1.01)
    return altered
train.make_train_step = broken
""",
    "rs_decode_skipped": """
import jax
import jax.numpy as jnp
from repro.core import uno_collectives as uc
def no_decode(rows, scales, parity, n0, run, dtype=uc.F32):
    lost = rows.at[:run.uno_ec_parity].set(0)
    q = jax.lax.bitcast_convert_type(lost.reshape(-1), jnp.int8)
    return uc._dequant(q, scales, n0).astype(dtype)
uc._unprotect = no_decode
""",
}

RUN = """
import json, time
from bench import harness, peaks
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
out = harness.run_cell(tiny.train_cell(), tiny.SEED, 0.3, False,
                       t_start=time.perf_counter())
print(json.dumps(out))
"""


@pytest.mark.parametrize("fault", sorted(PLANT))
def test_fault_is_not_correct(fault):
    out = tiny.four_devices(PLANT[fault] + RUN)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1


CONTROL = """
import json
from bench import train_control
[rec] = train_control.readings(tiny.train_cell(), [tiny.SEED], True, 1,
                               emit=lambda s: None)
print(json.dumps(rec))
"""


def test_control_and_reference_faults_are_not_correct():
    rec = tiny.four_devices(CONTROL)
    limits = {k: v["limit"] for k, v in tiny.train_cell()["limits"].items()}
    assert all(rec["program"][k] <= limits[k] for k in limits), rec
    for reading in ("control", "half_batch", "pod_local", "altered",
                    "stale"):
        assert any(rec[reading][k] > limits[k] for k in limits), \
            (reading, rec[reading])
