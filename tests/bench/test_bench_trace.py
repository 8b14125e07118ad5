"""The trace reduction, checked on a trace recorded on the chip and on
hand-made events."""
import gzip
import json
import pathlib

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the checkout root on sys.path)
from bench import harness
from bench import trace as tr

FIXTURE = pathlib.Path(__file__).with_name("tpu_trace_lossy.json.gz")
GATHER = ("%fusion.95 = f32[32768]{0:T(1024)S(1)} fusion(f32[4096,4]"
          "{0,1:T(4,128)S(1)} %get-tuple-element.2283, s32[32768]"
          "{0:T(1024)S(1)} %reshape.243), kind=kCustom, "
          "calls=%fused_computation.2.clone.clone.clone")
ELEMENTWISE = ("%maximum_select_fusion.3 = f32[4096]{0:T(1024)} fusion("
               "f32[4096]{0:T(1024)} %a, f32[4096]{0:T(1024)} %b), "
               "kind=kLoop, calls=%fused_computation.40")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        d = json.load(f)
    return {k: d[k] for k in ("ops", "modules", "host")}


def _share():
    return harness.load_module("metrics", "gather_scatter_share")


def test_union_and_self_times():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [[0, 3], [5, 10]]
    st = tr.self_times([(0, 100, "while"), (10, 30, "a"), (40, 45, "b"),
                        (50, 60, "a")])
    assert st == {"while": 65.0, "a": 30.0, "b": 5.0}


def test_busy_union_matches_a_timeline(recorded):
    s = tr.summarize(recorded, ["_simulate"])
    ops = recorded["ops"]["/device:TPU:0"]
    lo = min(h[0] for h in recorded["host"])
    hi = max(h[1] for h in recorded["host"])
    t = np.zeros(int(hi - lo) + 1, bool)
    for a, b, _ in ops:
        t[int(max(a, lo) - lo):int(min(b, hi) - lo)] = True
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert s["busy_s"] == pytest.approx(t.sum() * 1e-9, rel=1e-3)
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert s["scan_s"] >= s["scan_op_s"] > 0.0


def test_op_classification(recorded):
    share = _share()
    assert share.is_gather_scatter(GATHER)
    assert share.is_gather_scatter("%gather.3 = f32[8]{0} gather(f32[9] %x,"
                                   " s32[8,1] %i)")
    assert not share.is_gather_scatter(ELEMENTWISE)
    assert not share.is_gather_scatter("%while.70 = (s32[]) while((s32[]) "
                                       "%t), condition=%c, body=%b")

    class Run:
        trace_summary = tr.summarize(recorded, ["_simulate"])
    # on the chip the scan's hop gathers and csr sums are most of its time
    assert 90.0 < share.read(Run) <= 100.0


def test_idle_gaps_tagged_by_host_span():
    events = {"ops": {"/device:TPU:0": [(0, 10, ELEMENTWISE),
                                        (60, 70, GATHER)]},
              "modules": {"/device:TPU:0": [(0, 70, "jit__simulate(1)")]},
              "host": [(0, 100, "bench.call"), (50, 100, "bench.block"),
                       (0, 100, "PjitFunction(_simulate)")]}
    s = tr.summarize(events, ["_simulate"])
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["breakdown"]["idle_gaps"] == [["call", pytest.approx(50e-9)],
                                           ["block", pytest.approx(30e-9)]]
    assert s["scan_op_s"] == pytest.approx(20e-9)
    assert s["breakdown"]["device_ops"][0][0].startswith("%maximum_select")
