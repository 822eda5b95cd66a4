"""The ``fedavg_lm_lanes`` family on the CPU at a tiny size, added the
way its chip cell was: new files and ``BENCHMARK.json`` entries only. A
whole run (``run.run_cell``), the rehearsal of one traced line, the
check against the control and each planted fault, the operation count
against a hand count, and every new reader's rule: a number, or None
where there is nothing to read.

The tiny model's lane step is far under the engine's
``_HEAVY_LANE_STEP``, so the tests lower that constant to steer the
program onto the lane-after-lane executable the chip cell takes: the
program has no option for it.
"""

import json
import os

import pytest

import harness
from conftest import CHECKOUT, FAKE_PEAKS, TINY_DIR, run_cell, tiny_spec

CELL, CHIP_CELL = "tiny_f2", "fedavg_lfm2_t4096"
NEW_READERS = [
    "conv_op_device_ms", "dense_mlp_device_ms", "flash_full_fwd_roofline.typed",
    "lane_steps_run_share", "moe_bias_moved_share",
]
FAULTS = ["no_bias", "acausal_conv", "no_c_gate", "no_renorm", "dense_width"]


def lfm2_spec() -> dict:
    """``tiny_spec()`` plus the tiny federation, listed wherever the
    chip cell is."""
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny_fedavg_lfm2", "source": "tests", "reduced": [], "why": "CPU rehearsal",
        "file": os.path.join(TINY_DIR, "configs", "tiny_fedavg_lfm2.json")})
    spec["workloads"].append({
        "name": CELL, "config": "tiny_fedavg_lfm2", "traffic": CELL, "chips": 1, "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CHIP_CELL in m.get("workloads", []):
                m["workloads"].append(CELL)
    return spec


@pytest.fixture
def lfm2_root(tmp_path):
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(lfm2_spec(), f)
    return str(tmp_path)


@pytest.fixture
def lanes(monkeypatch):
    from fedml_tpu.simulation import fedavg_api

    monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)


@pytest.fixture(scope="module")
def chip_cell():
    return harness.Cell(CHIP_CELL, root=CHECKOUT)


def test_end_to_end_line(lfm2_root, lanes, monkeypatch):
    cell, res = run_cell(CELL, lfm2_root, monkeypatch)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"samples_per_s", "round_p95_ms", "setup_s"} == set(line["metrics"])
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["compared"]["moe_dropped"] == [0.0, 0.0]
    for value, limit in line["compared"].values():
        assert value <= limit


def test_traced_line(lfm2_root, lanes, monkeypatch):
    """On a CPU the trace holds no TPU plane: the readers of device time
    return nothing and are left out -- never 0; the counters' readers
    and the whole step's share read."""
    cell, res = run_cell(CELL, lfm2_root, monkeypatch, trace=True)
    declared = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(declared)
    assert not {"attn_window_device_ms", "flash_window_fwd_roofline", "flash_full_fwd_roofline",
                "pad_waste_pct"} & set(declared)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) <= set(declared)
    from_trace = {
        "round_fn_device_ms", "eval_device_ms", "device_idle_pct.fedavg", "peak_hbm_pct.fedavg",
        "gather_device_ms", "local_train_device_ms", "aggregate_device_ms", "idle_unnamed_pct",
        "attn_full_device_ms", "moe_route_device_ms", "moe_experts_device_ms", "head_loss_device_ms",
        "conv_op_device_ms", "dense_mlp_device_ms", "flash_full_fwd_roofline.typed"}
    assert set(declared) - set(line["metrics"]) <= from_trace
    value = lambda name: line["metrics"][name]["value"]
    # cohorts of (3 or 2, 3 or 2) sequences at batch 2: a lane runs 2 steps or 1 of its 2
    assert 0.5 <= value("lane_steps_run_share") < 1.0
    assert 0.0 < value("moe_bias_moved_share") < 0.5
    assert 1.0 <= value("moe_load_max_over_mean") < 4.0
    assert value("mfu_pct.fedavg") > 0


def test_setup_refuses_another_round_executable(lfm2_root, monkeypatch):
    """Without the steer the tiny model keeps the static scan: the
    family refuses to measure it under this cell's name."""
    cell = harness.Cell(CELL, root=lfm2_root)
    driver = cell.family_module().Driver(cell, 3)
    with pytest.raises(harness.BenchError, match="lane-after-lane"):
        driver.setup()


def test_setup_refuses_a_program_that_cannot_build_the_model(lfm2_root, monkeypatch):
    """The parent of the PR that added the layer kinds: refused before
    any data is made."""
    from fedml_tpu.models import decoder

    cell = harness.Cell(CELL, root=lfm2_root)
    driver = cell.family_module().Driver(cell, 3)
    monkeypatch.setattr(decoder, "CONV", "no such kind")
    monkeypatch.setattr(driver, "load_data", lambda: pytest.fail("made data"))
    with pytest.raises(harness.BenchError, match="cannot build"):
        driver.setup()


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """One program drive and one reference drive, shared by the cases
    below."""
    from fedml_tpu.simulation import fedavg_api

    root = tmp_path_factory.mktemp("lfm2")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(lfm2_spec(), f)
    cell = harness.Cell(CELL, root=str(root))
    cell.traffic = dict(cell.traffic, rounds_per_call=1)
    driver = cell.family_module().Driver(cell, 3)
    heavy, fedavg_api._HEAVY_LANE_STEP = fedavg_api._HEAVY_LANE_STEP, 0
    try:
        driver.setup()
    finally:
        fedavg_api._HEAVY_LANE_STEP = heavy
    got = driver.observed
    driver.release()
    return cell, driver, got, driver.reference_numbers()


def test_program_is_inside_the_limits(checked):
    cell, driver, got, want = checked
    g = driver.gaps(got, want)
    assert all(g[k] <= cell.config["limits"][k] for k in g), g
    assert all(c["moe_dropped"] == 0.0 and c["moe_local_hits"] > 0 for c in got["counters"])
    assert want["loss"][0] > want["loss"][-1]  # the reference's loss falls


@pytest.mark.parametrize("plant", ["fp8", "half_batch"] + FAULTS)
def test_control_and_faults_fail_a_limit(checked, plant):
    """The reference in fp8, and the reference with each fault planted,
    against the reference: at least one compared number passes its
    limit -- so a program that did the same would be refused."""
    import controls

    cell, driver, _, want = checked
    kwargs = {"fp8": {"quant": controls.FP8}, "half_batch": {"row_keep": 2}}.get(
        plant, {"fault": plant})
    g = driver.gaps(driver.reference_numbers(**kwargs), want)
    limits = cell.config["limits"]
    assert any(g[k] > limits[k] for k in g), (plant, g)


def test_calibration_tool_names_this_reference_faults():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "calibrate_lfm2", os.path.join(CHECKOUT, "benchmark", "tools", "calibrate_lfm2.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ref = harness.Cell(CHIP_CELL, root=CHECKOUT).module("reference", "fedavg_lfm2")
    assert {"fault_" + f for f in ref.FAULTS if f} <= set(tool.PLANTS)
    assert {"control_fp8", "fault_half_batch"} <= set(tool.PLANTS)


# -- the operation count against a hand count --------------------------
def test_forward_count_by_hand(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    parts = fl.forward_flops_per_token(m)
    c = 2048
    assert parts["conv_projections"] == 4 * 2 * (c * 3 * c + c * c) == 134_217_728
    assert parts["attention_projections"] == 2 * (2 * c * 32 * 64 + 2 * c * 8 * 64) == 20_971_520
    assert parts["attention_full"] == 2 * 2 * 32 * 64 * 2048.5
    assert parts["dense_mlp"] == 2 * 3 * c * 7168 == 88_080_384
    assert parts["router"] == 4 * 2 * c * 32
    # 4 choices x 8 held / 32 experts = one held choice a token a layer
    assert parts["experts"] == 4 * 1.0 * 2 * 3 * c * 1792 == 88_080_384
    assert parts["head"] == 2 * c * 16384 == 67_108_864
    total = sum(parts.values())
    assert 415e6 < total < 417e6
    assert fl.eval_flops_per_token(m) == total and fl.train_flops_per_token(m) == 3 * total


def test_window_counts_useful_sequences_only(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    win = {"useful_samples": 25.0, "slot_samples": 28.0, "eval_samples": 116.0}
    want = (25 * 4096 * fl.train_flops_per_token(m) + 116 * 4096 * fl.eval_flops_per_token(m))
    assert fl.window_flops(chip_cell, win) == want
    assert 5.10e12 < 4096 * fl.train_flops_per_token(m) < 5.12e12  # a trained sequence


def test_flash_kernel_count_by_hand(chip_cell):
    fl = chip_cell.flops_module()
    need = fl.flash_fwd_sequence(chip_cell.config["model"], None)
    assert need["flops"] == 4 * 32 * 64 * 2048.5 * 4096
    # q and o: 4096 x 32 x 64 bf16 each; k and v: 4096 x 8 x 64 each; lse 32 x 4096 f32
    assert need["bytes"] == 2 * 4096 * 2048 * 2 + 2 * 4096 * 512 * 2 + 32 * 4096 * 4
    with pytest.raises(ValueError, match="window"):
        fl.flash_fwd_sequence(chip_cell.config["model"], 1024)


# -- the new readers' rule: a number, or None --------------------------
def _ctx(chip_cell, **over):
    ctx = {
        "cell": chip_cell, "peaks": dict(FAKE_PEAKS), "flops": chip_cell.flops_module(),
        "device": {"count": 1}, "facts": {}, "setup_s": 1.0,
        "trace": {"modules": {}, "kernels": {}},
        "window": {"slot_samples": 280.0, "eval_slot_samples": 256.0, "counters": {}},
        "_lm_scopes": {}, "_lane_scopes": {},
    }
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_with_nothing_to_read_returns_none(chip_cell, name):
    """A program without the scopes, kernels or counters (the parent of
    the PR that added them) leaves the metric out; it does not raise."""
    assert chip_cell.reader(name).read(_ctx(chip_cell)) is None


def test_scope_readers_read_milliseconds_a_round(chip_cell):
    ctx = _ctx(
        chip_cell,
        trace={"modules": {"jit_round_fn": {"count": 10.0, "total_s": 20.0}}, "kernels": {}},
        _lane_scopes={"blk.conv": 3.0, "blk.mlp.dense": 0.5, "blk.attn.full": 2.0})
    assert chip_cell.reader("conv_op_device_ms").read(ctx) == pytest.approx(300.0)
    assert chip_cell.reader("dense_mlp_device_ms").read(ctx) == pytest.approx(50.0)
    # the closed list's readers keep their own pass
    assert chip_cell.reader("attn_full_device_ms").read(ctx) is None


def test_counter_and_roofline_readers(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    ctx = _ctx(
        chip_cell,
        window={"slot_samples": 280.0, "train_slot_samples": 260.0, "eval_slot_samples": 256.0,
                "counters": {"moe_bias_moved": 13 * 2 * 4096 * 4 * 4 * 0.09},
                "lane_steps": {"steps_run": 13.0, "steps_packed": 14.0}},
        trace={"modules": {}, "kernels": {"flash_attention_fwd": {"count": 4.0, "total_s": 2.0}}})
    assert chip_cell.reader("lane_steps_run_share").read(ctx) == pytest.approx(13 / 14)
    # 13 steps x 2 sequences x 4,096 tokens x top 4 x 4 sparse layers
    assert chip_cell.reader("moe_bias_moved_share").read(ctx) == pytest.approx(0.09)
    # 2 passes of the 260 slots the lanes ran (remat) and 256 evaluation slots, 1 attention layer
    need = fl.flash_fwd_sequence(m, None)
    least = max(need["flops"] / FAKE_PEAKS["bf16_flops_per_s"], need["bytes"] / FAKE_PEAKS["hbm_bytes_per_s"])
    assert chip_cell.reader("flash_full_fwd_roofline.typed").read(ctx) == pytest.approx(
        100.0 * least * (2 * 260 + 256) * 1 / 2.0)


def test_lane_scopes_pass_leaves_the_closed_lists_as_it_found_them(chip_cell):
    """A recorded v5e trace of the ResNet cell names none of the LM's
    scopes: the widened pass returns nothing and restores
    ``_lm_scopes``' lists."""
    lane = chip_cell.module("layer_metrics", "_lane_scopes")
    import _lm_scopes

    before = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING
    seen = {}
    real = _lm_scopes.summary
    try:
        _lm_scopes.summary = lambda ctx: seen.setdefault("lists", (_lm_scopes.LM_SCOPES, _lm_scopes.TRAINING)) and {}
        assert lane.summary({"cell": chip_cell}) == {}
    finally:
        _lm_scopes.summary = real
    assert {"blk.conv", "blk.mlp.dense"} <= set(seen["lists"][0]) and "blk.conv" in seen["lists"][1]
    assert (_lm_scopes.LM_SCOPES, _lm_scopes.TRAINING) == before
