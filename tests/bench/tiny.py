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
