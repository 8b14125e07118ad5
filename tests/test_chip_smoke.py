"""chip_smoke.py's phases at tiny size on the CPU, and its refusal to run
without a TPU."""
import pathlib
import sys

import numpy as np
import pytest

from repro.fleetsim import links as fl

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def service_phase(tmp_path_factory):
    return chip_smoke.phase_service(tmp_path_factory.mktemp("scenarios"),
                                    k=4, n_flows=600, n_warm=40, n_meas=10)


def test_phase_service_tiny(service_phase):
    rec, fs = service_phase
    assert rec["warm_traces"] == 0
    assert rec["cold_traces"] == 1 and rec["batch"]["traces"] <= 1
    assert rec["load_err_vs_f64"]["auto"] <= chip_smoke.LOAD_TOL
    assert rec["n_flows"] == 600 and fs.params.bdp.shape == (600,)


def test_phase_scan_tiny():
    rec = chip_smoke.phase_scan(n_flows=2_000, n_warm=40, n_meas=10)
    assert rec["n_flows"] == 2_000 and rec["n_epochs"] == 50


def test_phase_pallas_tiny(service_phase):
    _, fs = service_phase
    net = fl.with_layout(fs.net._replace(layout=None), path_table=True)
    rec = chip_smoke.phase_pallas(net, n=1 << 16)
    assert rec["interpret"] is True          # CPU: the Pallas interpreter
    assert rec["max_err_over_half_step"] <= 1.0
    assert set(rec["load_err_vs_f64"]) == {"pt_pallas", "pallas"}


def test_check_failure_raises():
    with pytest.raises(chip_smoke.SmokeError):
        chip_smoke._check(False, "boom")
    assert chip_smoke._finite_positive(np.ones(3))
    assert not chip_smoke._finite_positive(np.array([1.0, np.nan]))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    assert capsys.readouterr().out == ""


_FOUR_DEVICE_PHASE = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
import jax
import chip_smoke
from repro.configs.base import reduced
devices, cache = jax.devices(), {cache!r}
rec = {{
    "multi_dc": lambda: chip_smoke.phase_multi_dc(
        devices, cache, n_flows=1500, n_warm=40, n_meas=10),
    "fat_tree_sharded": lambda: chip_smoke.phase_fat_tree_sharded(
        devices, cache, k=4, n_flows=2000, n_warm=40, n_meas=10),
    "uno_step": lambda: chip_smoke.phase_uno_step(
        devices, reduced(chip_smoke.uno_config()), batch=8, seq=32),
}}[{phase!r}]()
print(json.dumps(rec))
"""


@pytest.mark.parametrize("phase", ["multi_dc", "fat_tree_sharded",
                                   "uno_step"])
def test_four_device_phase_tiny(phase, tmp_path):
    """Each `--chips 4` phase at tiny size on four forced CPU devices (a
    child process: the device count is fixed before JAX starts)."""
    import json
    import subprocess
    root = str(pathlib.Path(chip_smoke.__file__).resolve().parent)
    code = _FOUR_DEVICE_PHASE.format(root=root, cache=str(tmp_path),
                                     phase=phase)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["phase"] == phase
    if phase == "multi_dc":
        assert rec["n_devices"] == 3 and rec["exchange"] == "nbr"
    elif phase == "fat_tree_sharded":
        assert rec["n_devices"] == 4 and rec["err"] < rec["tol"]
    else:
        full = chip_smoke.uno_config()
        assert (full.d_model, full.vocab, full.n_layers) == (
            576, 49152, chip_smoke.UNO_LAYERS)
        assert rec["mesh"] == {"pod": 2, "data": 2}
        assert rec["max_param_delta"] < 5e-4
