"""Each cell of the benchmark end to end at a tiny size on the CPU: a run
is correct, the control (the reference in bfloat16 in the program's
place) is not, and the run refuses to measure without a TPU."""
import json

import pytest

import tiny
from bench import check as ck
from bench import control, harness


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    tiny.isolate(monkeypatch, tmp_path)


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_run_is_correct(workload):
    out = tiny.run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"flow_epochs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_control_is_not_correct(workload):
    cell = tiny.cell(workload)
    [rec] = control.readings(cell, [tiny.SEED], 1, 0.2, emit=lambda s: None)
    limits = {k: v["limit"] for k, v in cell["limits"].items()}
    assert all(rec["program"][k] <= limits[k] for k in limits)
    assert any(rec["control"][k] > limits[k] for k in limits), rec


def test_trace_run_reports_host_metrics_on_cpu():
    out = tiny.run("fat_tree_k8.steady", trace=True)
    assert out["correct"]
    # no device plane on the CPU: the device readers stay silent
    assert set(out["metrics"]) == {"window_compiles", "bundle_load_s"}
    assert out["metrics"]["window_compiles"]["value"] == 0


def test_refuses_without_a_tpu(capsys):
    from bench import run
    rc = run.main(["--workload", tiny.CELLS[1], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_checks_are_printed_with_limits(capsys):
    checks = ck.with_limits({"goodput_median_err": 1e-7,
                             "goodput_max_err": 1e-6},
                            harness.cell_spec(tiny.CELLS[0])["limits"])
    harness.print_checks(checks)
    err = capsys.readouterr().err.splitlines()
    assert [e.split()[1] for e in err] == list(checks)
    assert all(" limit " in e for e in err)
    json.dumps(checks)
