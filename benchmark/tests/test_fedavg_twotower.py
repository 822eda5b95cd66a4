"""The ``fedavg_lm_lanes_ssm`` family on the CPU at a tiny size, added
the way its chip cell was: new files and ``BENCHMARK.json`` entries
only. A whole run (``run.run_cell``), the rehearsal of one traced line,
the check against the control and each planted fault, and every new
reader's rule: a number, or None where there is nothing to read.

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

CELL, CHIP_CELL = "tiny_t2", "fedavg_twotower_t8192"
NEW_READERS = ["ssm_device_ms", "ssm_scan_device_ms", "moe_shared_device_ms", "ssm_scan_roofline"]
FAULTS = ["state_reset", "no_d_skip", "norm_all_channels", "no_scaling", "no_shared", "relu"]


def twotower_spec() -> dict:
    """``tiny_spec()`` plus the tiny federation, listed wherever the
    chip cell is."""
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny_fedavg_twotower", "source": "tests", "reduced": [], "why": "CPU rehearsal",
        "file": os.path.join(TINY_DIR, "configs", "tiny_fedavg_twotower.json")})
    spec["workloads"].append({
        "name": CELL, "config": "tiny_fedavg_twotower", "traffic": CELL, "chips": 1, "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CHIP_CELL in m.get("workloads", []):
                m["workloads"].append(CELL)
    return spec


@pytest.fixture
def twotower_root(tmp_path):
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(twotower_spec(), f)
    return str(tmp_path)


@pytest.fixture
def lanes(monkeypatch):
    from fedml_tpu.simulation import fedavg_api

    monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)


@pytest.fixture(scope="module")
def chip_cell():
    return harness.Cell(CHIP_CELL, root=CHECKOUT)


def test_end_to_end_line(twotower_root, lanes, monkeypatch):
    cell, res = run_cell(CELL, twotower_root, monkeypatch)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"samples_per_s", "round_p95_ms", "setup_s"} == set(line["metrics"])
    assert line["compared"]["moe_dropped"] == [0.0, 0.0]
    assert set(line["compared"]) >= {"loss_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap"}


def test_traced_line(twotower_root, lanes, monkeypatch):
    """On a CPU the trace holds no TPU plane: the readers of device time
    return nothing and are left out -- never 0; the counters' readers
    and the whole step's share read, and the window the readers are
    handed carries the scans' chunk count."""
    seen, real = {}, harness.Cell.reader

    def spy(self, metric):
        mod = real(self, metric)
        if metric == "ssm_scan_roofline":
            read = mod.read
            mod.read = lambda ctx: seen.setdefault("window", ctx["window"]) and read(ctx)
        return mod

    monkeypatch.setattr(harness.Cell, "reader", spy)
    cell, res = run_cell(CELL, twotower_root, monkeypatch, trace=True)
    declared = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(declared)
    assert not {"conv_op_device_ms", "dense_mlp_device_ms", "pad_waste_pct", "attn_window_device_ms"} & set(declared)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) <= set(declared)
    assert not set(NEW_READERS) & set(line["metrics"])  # device time, every one
    value = lambda name: line["metrics"][name]["value"]
    # cohorts of (3 or 2, 3 or 2) sequences at batch 1: a lane runs 3 steps or 2 of its 3
    assert 2 / 3 <= value("lane_steps_run_share") < 1.0
    assert 0.0 < value("moe_bias_moved_share") < 0.5
    assert value("mfu_pct.fedavg") > 0
    # ``ssm_chunks`` of the reported rounds: 3 mixers x their steps x 1 sequence x 128 / 32 chunks
    win = seen["window"]
    assert win["counters"]["ssm_chunks"] == 3 * win["lane_steps"]["steps_run"] * 1 * 4 > 0


def test_setup_refuses_a_program_that_cannot_build_the_model(twotower_root, monkeypatch):
    """The parent of the PR that added the sublayer kinds: refused
    before any data is made."""
    from fedml_tpu.models import decoder

    cell = harness.Cell(CELL, root=twotower_root)
    driver = cell.family_module().Driver(cell, 3)
    monkeypatch.setattr(decoder, "SSM", "no such kind")
    monkeypatch.setattr(driver, "load_data", lambda: pytest.fail("made data"))
    with pytest.raises(harness.BenchError, match="cannot build"):
        driver.setup()


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """One program drive and one reference drive, shared by the cases
    below."""
    from fedml_tpu.simulation import fedavg_api

    root = tmp_path_factory.mktemp("twotower")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(twotower_spec(), f)
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


def test_calibration_tool_names_this_reference_faults(chip_cell):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "calibrate_twotower", os.path.join(CHECKOUT, "benchmark", "tools", "calibrate_twotower.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ref = chip_cell.module("reference", "fedavg_twotower")
    assert {"fault_" + f for f in ref.FAULTS if f} == {"fault_" + f for f in FAULTS} <= set(tool.PLANTS)
    assert {"control_fp8", "fault_half_batch"} <= set(tool.PLANTS)


def test_the_configuration_holds_the_sources_numbers(chip_cell):
    """Every number of the catalog row's ``config`` stands under its
    own key, but the three this file reduces."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")
    cfg = chip_cell.config
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == ["n_routed_experts", "vocab_size"] and cfg["num_layers"] == 7
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert "DENOISER TOWER" in cfg["departures"]["second_tower"]


# -- the new readers' rule: a number, or None --------------------------
def _ctx(chip_cell, **over):
    ctx = {
        "cell": chip_cell, "peaks": dict(FAKE_PEAKS), "flops": chip_cell.flops_module(),
        "device": {"count": 1}, "facts": {}, "setup_s": 1.0,
        "trace": {"modules": {}, "kernels": {}},
        "window": {"slot_samples": 140.0, "eval_slot_samples": 128.0, "counters": {}},
        "_lm_scopes": {}, "_lane_scopes": {}, "_ssm_scopes": {}, "_ssm_scopes_eval": {},
    }
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_with_nothing_to_read_returns_none(chip_cell, name):
    """A program without the scopes or the counter (the parent of the
    PR that added them) leaves the metric out; it does not raise."""
    assert chip_cell.reader(name).read(_ctx(chip_cell)) is None


def test_scope_readers_read_milliseconds_a_round(chip_cell):
    ctx = _ctx(
        chip_cell,
        trace={"modules": {"jit_round_fn": {"count": 10.0, "total_s": 30.0}}, "kernels": {}},
        _ssm_scopes={"blk.ssm": 9.0, "blk.ssm.scan": 4.0, "moe.shared": 1.5})
    assert chip_cell.reader("ssm_device_ms").read(ctx) == pytest.approx(900.0)
    assert chip_cell.reader("ssm_scan_device_ms").read(ctx) == pytest.approx(400.0)
    assert chip_cell.reader("moe_shared_device_ms").read(ctx) == pytest.approx(150.0)


def test_scan_roofline_by_hand(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    window = {
        "slot_samples": 140.0, "train_slot_samples": 130.0, "eval_slot_samples": 128.0,
        # two reported rounds of 13 steps: 3 mixers x 26 sequences x 64 chunks
        "counters": {"ssm_chunks": 3 * 26 * 64.0}, "lane_steps": {"steps_run": 26.0, "steps_packed": 28.0}}
    ctx = _ctx(chip_cell, window=window, _ssm_scopes={"blk.ssm.scan": 3.0}, _ssm_scopes_eval={"blk.ssm.scan": 1.0})
    need = fl.ssd_chunk(m)
    least = max(need["flops"] / FAKE_PEAKS["bf16_flops_per_s"], need["bytes"] / FAKE_PEAKS["hbm_bytes_per_s"])
    # 192 chunks a sequence; a trained one costs 4 forward passes (remat), an evaluated one 1
    want = 100.0 * least * 192 * (4 * 130 + 128) / 4.0
    assert chip_cell.reader("ssm_scan_roofline").read(ctx) == pytest.approx(want)
    # without the program's counter there is nothing to count by
    assert chip_cell.reader("ssm_scan_roofline").read(_ctx(chip_cell, window=dict(window, counters={}))) is None


def test_ssm_scopes_pass_leaves_the_closed_lists_as_it_found_them(chip_cell, monkeypatch):
    ssm = chip_cell.module("layer_metrics", "_ssm_scopes")
    import _lm_scopes
    import _scopes

    before = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND
    seen = []

    def spy(data, raw=None):
        seen.append((_lm_scopes.LM_SCOPES, _lm_scopes.ROUND))
        return {}

    recorded = os.path.join(CHECKOUT, "benchmark", "testdata", "fedavg_scopes_round.xplane.pb.gz")
    import gzip
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with gzip.open(recorded, "rb") as src, open(path, "wb") as dst:
            dst.write(src.read())
        monkeypatch.setattr(_scopes, "find_trace", lambda name: path)
        monkeypatch.setattr(_lm_scopes, "reduce_lm_scopes", spy)
        ctx = {"cell": chip_cell}
        assert ssm.summary(ctx) == {} and ssm.eval_summary(ctx) == {}
        assert ssm.seconds_in_window(ctx, "blk.ssm.scan") is None
    assert [s[1] for s in seen] == ["jit_round_fn", "jit_eval_all"]
    assert {"blk.ssm", "blk.ssm.scan", "moe.shared", "blk.conv"} <= set(seen[0][0])
    assert (_lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND) == before
