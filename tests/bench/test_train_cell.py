"""The four-chip training cell at a tiny size on four forced CPU devices:
a run is correct, the reference agrees with the program's plain psum
step, and its work counts, metric lists and limits hold; its readers
read a synthetic summary and one step recorded on four TPU v5e chips."""
import gzip
import json
import pathlib
import shutil

import pytest

import tiny
from bench import harness, stages, train_work
from bench import train_check as tc

# the CPU has no published peak; `mfu` reads the v5e's there
RUN = """
import json, time
from bench import harness, peaks
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
out = harness.run_cell(tiny.train_cell(), tiny.SEED, 0.5, TRACE,
                       t_start=time.perf_counter())
print(json.dumps(out))
"""


def test_train_run_is_correct():
    out = tiny.four_devices(RUN.replace("TRACE", "False"))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "mfu", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out["checks"]) == list(tiny.train_cell()["limits"])
    assert list(out)[-1] == "checks"
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": None}


def test_train_trace_run_on_cpu():
    out = tiny.four_devices(RUN.replace("TRACE", "True"))
    assert out["correct"], out["checks"]
    # no device plane on the CPU: only the host counter reads
    assert out["metrics"] == {"window_compiles.train":
                              {"value": 0, "unit": "count"}}


REFERENCE_VS_PSUM = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro import sharding, train
from repro.configs.base import RunConfig
from bench import train_check as tc, train_reference as ref
from bench.entries import train_step
cell = tiny.train_cell(param_dtype="float32", compute_dtype="float32")
cfg, tr = cell["config"], cell["traffic"]
model = train_step.model_config(cfg)
mesh = sharding.make_mesh((2, 2), ("pod", "data"))
params, tokens = tc.seeded(cell, tiny.SEED)
inputs, targets = ref.split_batch(tokens[0])
state = {"params": params,
         "opt": {"m": jax.tree.map(jnp.zeros_like, params),
                 "v": jax.tree.map(jnp.zeros_like, params),
                 "step": jnp.zeros((), jnp.int32)}}
run = RunConfig(learning_rate=cfg["optimizer"]["learning_rate"],
                warmup_steps=cfg["optimizer"]["warmup_steps"])
with sharding.use_mesh(mesh, sharding.profile_rules(model)):
    new, met = jax.jit(train.make_train_step(model, run))(
        state, {"inputs": inputs, "targets": targets},
        jnp.int32(tr["step0"]))
want = ref.train(params, tokens[:1], tr["step0"], cfg["model"],
                 cfg["optimizer"])
g = tc.host_leaves(new["opt"]["m"])
gw = {p: ref.leaf(want["grad"], p) for p in ref.LEAVES}
num = sum(float(np.sum((g[p] / 0.1 - gw[p]) ** 2)) for p in ref.LEAVES)
den = sum(float(np.sum(gw[p] ** 2)) for p in ref.LEAVES)
dp = max(float(jnp.max(jnp.abs(ref.leaf(new["params"], p) -
                               ref.leaf(want["params"], p))))
         for p in ref.LEAVES)
print(json.dumps({"loss": float(met["loss"]), "want": want["losses"][0],
                  "grad_rel": (num / den) ** 0.5, "param_gap": dp}))
"""


def test_reference_matches_the_psum_step():
    """In float32 the program's plain GSPMD step (the all-reduce over both
    axes, no Uno exchange) and the reference agree to float32 noise: the
    loss, the first gradient, the weights after one AdamW update."""
    r = tiny.four_devices(REFERENCE_VS_PSUM)
    assert r["loss"] == pytest.approx(r["want"], rel=1e-5)
    assert r["grad_rel"] < 1e-4
    assert r["param_gap"] < 1e-6


def test_flops_match_a_hand_count():
    m = tiny.train_cell()["config"]["model"]
    # per layer q, o: 64 x 64; k, v: 64 x 32; gate, up, down: 64 x 128
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    head = 64 * 512
    assert train_work.matmul_params(m) == 2 * per_layer + head
    assert train_work.flops_per_token(m, 64) == \
        6 * (2 * per_layer + head) + 12 * 2 * 4 * 16 * 64
    cell = tiny.train_cell()
    assert train_work.step_flops(cell) == \
        train_work.flops_per_token(m, 64) * 4 * 64
    assert train_work.chips(cell) == 4


def test_work_counts_at_published_widths():
    """The full cell: 134,515,008 weights as the program lays them out,
    1.23 GFLOP a token, and the bytes each kernel must move a step."""
    from repro import models
    from repro.models import api
    from bench.entries import train_step
    cell = _full_cell()
    m = cell["config"]["model"]
    assert train_work.n_params(m) == api.param_count(models.param_defs(
        train_step.model_config(cell["config"]))) == 134_515_008
    assert train_work.flops_per_token(m, 2048) == \
        6 * 134_479_872 + 12 * 30 * 576 * 2048
    b = train_work.kernel_bytes(cell)
    n = 134_515_008
    assert b["quant"] == b["dequant"] == pytest.approx(5 * n + n / 64)
    assert b["rs"] == pytest.approx(2.5 * n)


FLEET = ("flow_epochs_per_s", "setup_s")
FLEET_LAYER = ("scan_ms_per_epoch", "gather_scatter_share",
               "device_idle_share", "window_compiles", "bundle_load_s")
TRAIN = ("setup_s", "tokens_per_s", "mfu")
TRAIN_LAYER = ("step_ms", "step_mfu", "dci_exchange_share", "quant_roofline",
               "dequant_roofline", "rs_roofline", "step_idle_share",
               "window_compiles.train")


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_each_cell_gets_its_own_metrics(workload):
    """The fat-tree cells keep exactly the metrics they had."""
    spec = harness.cell_spec(workload)
    assert [m["name"] for m in spec["end_to_end"]] == list(FLEET)
    assert [m["name"] for m in spec["per_layer"]] == list(FLEET_LAYER)


def test_train_cell_has_a_reader_for_each_metric():
    """The training cell reports only its own metrics, each with a
    reader, and each listed for it alone."""
    spec = tiny.train_cell()
    assert [m["name"] for m in spec["end_to_end"]] == list(TRAIN)
    assert [m["name"] for m in spec["per_layer"]] == list(TRAIN_LAYER)
    for m in spec["end_to_end"][1:] + spec["per_layer"]:
        assert m["workloads"] == [tiny.TRAIN_CELL]
        harness.load_module("metrics", m["name"])


def test_train_cell_spec_is_whole():
    """The pending entries give the cell its four chips, a mesh of that
    many devices whose rows split evenly over it and into the reference's
    blocks of 2, and a bound still to be set (null) on each end-to-end
    metric of its own (its metrics: the test above; the fat-tree cells
    list none of them: `test_each_cell_gets_its_own_metrics`); the cell
    and its limits file wait until a four-chip host has measured it."""
    import math
    spec = tiny.full_train_cell()
    tr = spec["traffic"]
    assert spec["chips"] == 4 == math.prod(tr["mesh"].values())
    assert tr["rows"] % (spec["chips"] * 2) == 0
    assert [m["bound"] for m in spec["end_to_end"][1:]] == [None, None]
    listed = harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]
    assert tiny.TRAIN_CELL not in {w["name"] for w in listed}
    assert not (harness.BENCH / "limits" / f"{tiny.TRAIN_CELL}.json").exists()


QUANT = ("%quant_int8.4 = (s8[8912896]{0}, f32[34816]{0}) custom-call("
         "f32[34816,256]{1,0} %pad.68), custom_call_target="
         "\"tpu_custom_call\"")
DEQUANT = ("%dequant_int8.2 = f32[8912896]{0} custom-call(s8[34816,256]"
           "{1,0} %a, f32[1,34816]{1,0} %b), custom_call_target="
           "\"tpu_custom_call\"")
RS = ("%gf_matmul.8 = u8[2,1114112]{1,0} custom-call(u8[8,1114112]{1,0} "
      "%c), custom_call_target=\"tpu_custom_call\"")
PERMUTE = ("%collective-permute-done = u8[8,1114112]{1,0} "
           "collective-permute-done(%d)")
DOT = "%fusion.617 = bf16[1,1,576,576]{3,2,1,0} fusion(%e, %f), kind=kOutput"


class _Run:
    def __init__(self, cell, summary, steps):
        self.cell, self.trace_summary = cell, summary
        self.traced_calls = [(0.0, 1.0, 16384, 1)] * steps
        self.counters = {"window_compiles": 0}
        self.calls = self.traced_calls


def _full_cell():
    """The cell at its published widths and traffic."""
    return tiny.full_train_cell()


def test_readers_on_a_synthetic_summary(monkeypatch):
    """Three traced steps on four chips: 0.3 s of the step module a chip,
    of which 0.1 s in the exchange's ops, 0.5 s traced window, 0.4 s busy;
    each kernel's ops at a known self time."""
    from bench import peaks
    monkeypatch.setattr(peaks, "device_kind", lambda: "TPU v5 lite")
    cell = _full_cell()
    ops = {QUANT: 0.004, DEQUANT: 0.005, RS: 0.002, PERMUTE: 0.089,
           DOT: 0.2}
    names = {QUANT: "jit(step)/shard_map/jit(quant_int8)/pallas_call",
             DEQUANT: "jit(step)/shard_map/jit(dequant_int8)/pallas_call",
             RS: "jit(step)/shard_map/jit(gf_matmul)/pallas_call",
             PERMUTE: "jit(step)/shard_map/ppermute",
             DOT: "jit(step)/vmap(transpose(jvp()))/while/body/dot_general"}
    summary = {"window_s": 0.5, "busy_s": 0.4, "scan_s": 0.3,
               "scan_ops": ops, "scan_op_s": sum(ops.values()),
               "op_names": names}
    run = _Run(cell, summary, 3)
    read = {m["name"]: harness.load_module("metrics", m["name"]).read(run)
            for m in cell["per_layer"]}
    flops = train_work.step_flops(cell)
    b = train_work.kernel_bytes(cell)
    assert read["step_ms"] == pytest.approx(100.0)
    assert read["step_mfu"] == pytest.approx(
        100 * flops * 3 / (4 * 0.3 * 197e12))
    assert read["dci_exchange_share"] == pytest.approx(100 * 0.1 / 0.3)
    assert read["quant_roofline"] == pytest.approx(
        100 * b["quant"] * 3 / (0.004 * 819e9))
    assert read["dequant_roofline"] == pytest.approx(
        100 * b["dequant"] * 3 / (0.005 * 819e9))
    assert read["rs_roofline"] == pytest.approx(
        100 * b["rs"] * 3 / (0.002 * 819e9))
    assert read["step_idle_share"] == pytest.approx(20.0)
    assert read["window_compiles.train"] == 0
    json.dumps(read)


def test_readers_stay_silent_without_their_ops(monkeypatch):
    """No kernel ops and no op names: the kernels' rooflines and the
    exchange share give nothing, never 0."""
    from bench import peaks
    monkeypatch.setattr(peaks, "device_kind", lambda: "TPU v5 lite")
    cell = _full_cell()
    summary = {"window_s": 0.5, "busy_s": 0.4, "scan_s": 0.3,
               "scan_ops": {DOT: 0.3}, "scan_op_s": 0.3}
    run = _Run(cell, summary, 3)
    for name in ("dci_exchange_share", "quant_roofline", "dequant_roofline",
                 "rs_roofline"):
        assert harness.load_module("metrics", name).read(run) is None


def test_unknown_device_has_no_peak():
    from bench import peaks
    with pytest.raises(KeyError):
        peaks.peak("TPU v99", "bf16_flops")


def test_module_op_names_keeps_the_step_program():
    trace = {"modules": {"/device:TPU:0": [(0, 10, "jit_step(7)"),
                                           (20, 30, "jit_other(8)")]}}
    names = {"/device:TPU:0": {QUANT: (7, "jit(step)/shard_map/q"),
                               DOT: (8, "jit(other)/dot")}}
    assert stages.module_op_names(trace, names, ["step"]) == \
        {QUANT: "jit(step)/shard_map/q"}


def test_numbers_read_the_faults_by_hand():
    """The reference against itself reads 0; a state handed back
    unchanged reads 1 on both norms; the altered loss reads 1%."""
    import numpy as np
    rng = np.random.default_rng(0)
    from bench import train_reference as ref
    want = {"losses": [10.0, 9.0, 8.0],
            "grad": {p: rng.normal(size=4).astype(np.float32)
                     for p in ref.LEAVES},
            "change": {p: float(i + 1) for i, p in enumerate(ref.LEAVES)}}
    assert set(tc.numbers(want, want).values()) == {0.0}
    nums = tc.numbers(tc.stale(want), want)
    assert nums["grad_norm_err"] == nums["change_norm_err"] == 1.0
    assert tc.numbers(tc.altered(want), want)["loss_err"] == \
        pytest.approx(0.01)


def test_worst_leaves_name_the_leaf_that_sets_each_number():
    """`worst_leaves` names the leaf whose norm gap sets `grad_norm_err`
    and the one whose change gap sets `change_norm_err`."""
    import numpy as np
    from bench import train_reference as ref
    want = {"losses": [10.0],
            "grad": {p: np.ones(4, np.float32) for p in ref.LEAVES},
            "change": {p: 1.0 for p in ref.LEAVES}}
    got = {"losses": [10.0],
           "grad": {p: np.full(4, 1.5 if p == ("lm_head",) else 1.0,
                               np.float32) for p in ref.LEAVES},
           "change": {p: 0.5 if p == ("final_norm",) else 1.0
                      for p in ref.LEAVES}}
    assert tc.worst_leaves(got, want) == {"grad_norm_err": "lm_head",
                                          "change_norm_err": "final_norm"}
    nums = tc.numbers(got, want)
    assert nums["grad_norm_err"] == pytest.approx(0.5)
    assert nums["change_norm_err"] == pytest.approx(0.5)


def test_stale_reads_exactly_one_on_a_large_leaf():
    """A state handed back unchanged reads exactly 1 on `grad_l2_err`
    where one leaf holds 1.2e7 values, as the output head does: the norms
    and the difference are summed alike, in float64."""
    import numpy as np
    rng = np.random.default_rng(1)
    from bench import train_reference as ref
    sizes = {p: 4 for p in ref.LEAVES}
    sizes[("lm_head",)] = 12_000_000
    want = {"losses": [10.0],
            "grad": {p: rng.normal(size=n).astype(np.float32)
                     for p, n in sizes.items()},
            "change": {p: 1.0 for p in ref.LEAVES}}
    nums = tc.numbers(tc.stale(want), want)
    assert nums["grad_l2_err"] == 1.0
    assert nums["grad_norm_err"] == 1.0


# One step of the cell recorded on four TPU v5e chips at 2 rows a chip
# (8 x 2,048 tokens a step; `--trace 1`, seed
# 3016000001), cut to that step's `bench.call` and `bench.block` spans
# and to the chips' "XLA Modules" and "XLA Ops" lines; each op's metadata
# keeps only its name and its `program_id` and `tf_op` stats.
UNO_TRACE = pathlib.Path(__file__).with_name(
    "tpu_trace_uno_step.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_run(tmp_path_factory):
    """The recorded step, reduced by the harness's own `Profiler`."""
    d = tmp_path_factory.mktemp("uno_trace")
    with gzip.open(UNO_TRACE, "rb") as f, \
            open(d / "step.xplane.pb", "wb") as out:
        shutil.copyfileobj(f, out)
    run = harness.Run(_full_cell(), tiny.SEED, 0.0)
    run.traced_calls = [(0.0, 0.242, 16384, 1)]
    prof = harness.Profiler(run)
    prof.dir = str(d)
    prof.reduce()
    return run


@pytest.mark.parametrize("name", ["step_mfu", "dci_exchange_share",
                                  "quant_roofline", "dequant_roofline",
                                  "rs_roofline", "step_idle_share"])
def test_readers_read_the_chip_trace(chip_run, monkeypatch, name):
    from bench import peaks
    monkeypatch.setattr(peaks, "device_kind", lambda: "TPU v5 lite")
    value = harness.load_module("metrics", name).read(chip_run)
    assert 0 < value <= 100


def test_op_name_rules_on_the_chip_trace(chip_run):
    """On each chip the step ran 4 `quant_int8`, 4 `dequant_int8` and 8
    `gf_matmul` kernels (RS encode and decode of 4 chunks), each inside
    the exchange's `shard_map`; no op of the model's forward or backward
    pass lies there."""
    import re
    s = chip_run.trace_summary
    names = s["op_names"]
    for kernel, n in (("quant_int8", 4), ("dequant_int8", 4),
                      ("gf_matmul", 8)):
        ops = [k for k in s["scan_ops"] if re.match(rf"^%{kernel}\.", k)]
        assert len(ops) == n
        assert {names[k] for k in ops} == \
            {f"jit(step)/shard_map/jit({kernel})/pallas_call"}
    exchange = [n for n in names.values() if "/shard_map/" in n]
    assert exchange and not any("jvp(" in n for n in exchange)
    assert harness.load_module("metrics", "step_ms").read(chip_run) == \
        pytest.approx(236.6, rel=1e-3)
