"""The per-stage reduction (bench/stages.py), checked on hand-made events
and on a trace recorded on the chip."""
import gzip
import json
import pathlib
import shutil

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the checkout root on sys.path)
from bench import stages
from bench import trace as tr

# One 20-epoch `fleetsim.simulate` call of four flows over two lossy WAN
# paths (EC + NACK with a ladder, a flapping and a bursty link), recorded
# on a TPU v5e and cut to the call's `bench.call` and `bench.block` spans;
# each op's metadata keeps only its `program_id` and `tf_op` stats.
FIXTURE = pathlib.Path(__file__).with_name(
    "tpu_trace_lossy_stages.xplane.pb.gz")
DEV = "/device:TPU:0"
SCAN = "jit(_simulate)/while/body/closed_call/fleetsim.cc"


@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return str(path)


@pytest.fixture(scope="module")
def recorded(recorded_path):
    return tr.load(recorded_path), stages.op_names(recorded_path)


def _events(names):
    """A scan run holding a while loop with a fusion of the gather stage,
    an op of the cc step, an unnamed copy, and an op named in another
    program; the host opens a call, a dispatch, and a block."""
    ops = [(0, 100, "%while.1"), (10, 30, "%fusion.2"), (40, 45, "%mul.3"),
           (50, 60, "%copy.4"), (70, 75, "%add.5")]
    trace = {"ops": {DEV: ops},
             "modules": {DEV: [(0, 100, "jit__simulate(7)")]},
             "host": [(0, 200, "bench.call"), (0, 20, "fleetsim.dispatch"),
                      (120, 200, "bench.block")]}
    return trace, {DEV: names}


NAMED = {"%fusion.2": (7, f"{SCAN}/fleetsim.link_gathers/gather"),
         "%mul.3": (7, f"{SCAN}/mul"),
         "%add.5": (8, f"{SCAN}/fleetsim.reliability/add")}


def test_innermost_segment_wins():
    assert stages.stage_of(f"{SCAN}/fleetsim.offered_load/jit(floor_divide)"
                           "/select_n") == "offered_load"
    assert stages.stage_of(f"{SCAN}/fleetsim.link_gathers/mul") == \
        "link_gathers"
    assert stages.stage_of(f"{SCAN}/fleetsim.faults") == "faults"
    assert stages.stage_of("jit(_simulate)/while/body/add") == "unscoped"
    assert stages.stage_of(None) == "unscoped"


def test_attribution_by_each_ops_own_name():
    trace, names = _events(NAMED)
    s = stages.summarize(trace, names, ["_simulate"])
    # the fusion goes by its own op_name, not by the while around it; the
    # copy has none; the add's name is another program's
    assert s["stage_s"] == {"unscoped": pytest.approx(75e-9),
                            "link_gathers": pytest.approx(20e-9),
                            "cc": pytest.approx(5e-9)}
    assert s["scoped"]
    assert sum(s["stage_s"].values()) == pytest.approx(
        tr.summarize(trace, ["_simulate"])["scan_op_s"])
    assert s["host_s"] == pytest.approx(20e-9)


def test_no_scopes_reads_all_unscoped():
    trace, names = _events({})
    s = stages.summarize(trace, names, ["_simulate"])
    assert not s["scoped"]
    assert s["stage_s"] == {"unscoped": pytest.approx(100e-9)}
    assert s["host_s"] == pytest.approx(20e-9)


def test_wait_idle_is_an_exact_intersection():
    ops = [(10, 60, "%fusion.2"), (130, 140, "%fusion.2")]
    host = [(0, 200, "bench.call"), (0, 20, "fleetsim.dispatch"),
            (50, 200, "bench.submit"), (90, 170, "fleetsim.wait"),
            (100, 110, "fleetsim.unstack"), (150, 160, "PjitFunction(f)")]
    trace = {"ops": {DEV: ops}, "modules": {DEV: []}, "host": host}
    s = stages.summarize(trace, {}, ["_simulate"])
    t = np.arange(200) + 0.5
    idle = ~np.any([(a <= t) & (t < b) for a, b, _ in ops], axis=0)
    # innermost open `bench.`/`fleetsim.` span a wait: the wait, minus the
    # unstack nested inside it; a runtime event does not count
    waiting = (90 <= t) & (t < 170) & ~((100 <= t) & (t < 110))
    assert s["wait_idle_s"] == pytest.approx((idle & waiting).sum() * 1e-9)
    assert s["wait_idle_s"] == pytest.approx(60e-9)
    assert s["window_s"] == pytest.approx(200e-9)


def test_op_names_come_from_the_chip_trace(recorded):
    trace, names = recorded
    [(_, _, module)] = trace["modules"][DEV]
    program = int(module[module.index("(") + 1:-1])
    ops = {n for _, _, n in trace["ops"][DEV]}
    assert set(names) == {DEV}
    assert {p for p, _ in names[DEV].values()} == {program}
    assert all(op.startswith("jit(_simulate)") and not op.endswith(":")
               for _, op in names[DEV].values())
    assert len(ops & set(names[DEV])) > 0.3 * len(ops)


def test_stages_reconcile_with_scan_op_time_on_the_chip(recorded):
    trace, names = recorded
    whole = tr.summarize(trace, ["_simulate"])
    s = stages.summarize(trace, names, ["_simulate"])
    assert set(s["stage_s"]) == {"unscoped", *stages.STAGES}
    assert all(v > 0 for v in s["stage_s"].values())
    assert sum(s["stage_s"].values()) == pytest.approx(whole["scan_op_s"],
                                                       rel=1e-6)
    assert s["window_s"] == pytest.approx(whole["window_s"])
    assert 0 < s["host_s"] < s["window_s"]
    assert 0 < s["wait_idle_s"] <= whole["window_s"] - whole["busy_s"]


def test_command_line_prints_the_reduction(recorded, recorded_path, capsys):
    assert stages.main([recorded_path]) == 2
    capsys.readouterr()
    assert stages.main([recorded_path, "_simulate"]) == 0
    printed = json.loads(capsys.readouterr().out)
    s = stages.summarize(*recorded, ["_simulate"])
    assert printed["stage_s"] == pytest.approx(s["stage_s"])
    assert printed["host_s"] == pytest.approx(s["host_s"])
