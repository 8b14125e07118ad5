"""Tiny versions of the benchmark's cells for the CPU tests: the real
traffic files and limits, with the fabric cut to k=4 (32 servers, one
flow each) and the query to a few dozen epochs."""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, scenario  # noqa: E402

CELLS = ("fat_tree_k8.whatif_sweep", "fat_tree_k8.steady",
         "fat_tree_k8.lossy_wan")
TINY_KWARGS = {"fat_tree_k8": {"k": 4, "n_wan": 4}}
TINY_TRAFFIC = {"chunk_epochs": 20, "n_warm": 40, "n_meas": 10}
SEED = 2 ** 31 + 12345         # larger than 32 signed bits hold


def cell(workload: str) -> dict:
    c = harness.cell_spec(workload)
    name = workload.split(".")[0]
    c["config"] = dict(c["config"], kwargs=dict(c["config"]["kwargs"],
                                                **TINY_KWARGS[name]))
    c["traffic"] = dict(c["traffic"], **{
        k: v for k, v in TINY_TRAFFIC.items() if k in c["traffic"]})
    return c


def isolate(monkeypatch, tmp_path) -> None:
    """Bundles under tmp_path, and no change to JAX's compile cache."""
    monkeypatch.setattr(scenario, "CACHE_DIR", tmp_path)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)


def run(workload: str, seconds: float = 0.3, trace: bool = False,
        seed: int = SEED) -> dict:
    return harness.run_cell(cell(workload), seed, seconds, trace,
                            t_start=time.perf_counter())


# ------------------------------------------------ the four-chip training cell

# Not in BENCHMARK.json until a four-chip host has measured it: its
# entries wait in bench/pending/<cell>.json.
TRAIN_CELL = "smollm135m.uno_step_pod2x2"
# 2 layers at d_model 64 (4 heads / 2 KV heads of 16, d_ff 128), vocab
# 512; 4 sequences of 64 tokens, one per device of the 2 x 2 mesh
TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512}
TINY_TRAIN = {"rows": 4, "seq": 64, "pool": 4}
# Limits for this tiny size on the CPU, set as the chip's are to be: the
# program's worst of 7 seeds (loss 2.57e-4, grad norm 2.0e-3, change
# 2.5e-3, gradient 1.92e-2) against the float8 control's best of 4
# (8.35e-4, 1.17e-2, 7.1e-3 (under 3x: the stale state's 1 instead),
# 0.223), two thirds of the way up in log scale.
TINY_LIMITS = {"loss_err": {"limit": 5.5e-4},
               "grad_norm_err": {"limit": 6e-3},
               "change_norm_err": {"limit": 0.1},
               "grad_l2_err": {"limit": 0.1}}


def pending(workload: str = TRAIN_CELL) -> dict:
    return harness.load_json(harness.BENCH / "pending" / f"{workload}.json")


def full_train_cell() -> dict:
    """The cell's spec as `harness.cell_spec` will give it once the
    pending entries are in BENCHMARK.json: its files, the metrics listed
    for every cell, then its own."""
    p = pending()
    w = p["workload"]
    bj = harness.load_json(harness.ROOT / "BENCHMARK.json")

    def every(kind):
        return [m for m in bj[kind] if "workloads" not in m]
    return {"workload": TRAIN_CELL, "chips": w["chips"],
            "config": harness.load_json(harness.BENCH / "configs" /
                                        f"{w['config']}.json"),
            "traffic": harness.load_json(harness.BENCH / "traffic" /
                                         f"{w['traffic']}.json"),
            "limits": TINY_LIMITS,
            "end_to_end": every("end_to_end") + p["end_to_end"],
            "per_layer": every("per_layer") + p["per_layer"]}


def train_cell(**model) -> dict:
    """The cell's spec at the tiny size (model keys in `model` override)
    and with the tiny size's limits."""
    c = full_train_cell()
    return dict(c, config=dict(c["config"], model=dict(
                    c["config"]["model"], **TINY_MODEL, **model)),
                traffic=dict(c["traffic"], **TINY_TRAIN))


def four_devices(code: str, timeout: int = 600) -> dict:
    """Run `code` in a fresh interpreter on 4 forced CPU devices, with the
    checkout and `src` importable and `tiny` imported; returns the JSON
    object its last line of output prints."""
    import json
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT), str(ROOT / "tests" /
                                                     "bench")]))
    env.pop("REPRO_UNO_KERNELS", None)
    out = subprocess.run([sys.executable, "-c", "import tiny\n" + code],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
