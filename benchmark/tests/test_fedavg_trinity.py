"""The ``fedavg_lm_lanes_gated`` family on the CPU at a tiny size, added
the way its chip cell was: new files and ``BENCHMARK.json`` entries
only. A whole run (``run.run_cell``), the rehearsal of one traced line,
the set-up's refusal of a program that cannot build the model, the
check against the control and each planted fault, and every new
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

CELL, CHIP_CELL = "tiny_t2_swa", "fedavg_trinity_mini_t8192"
NEW_READERS = ["attn_gate_device_ms", "post_norm_device_ms", "flash_window_fwd_roofline.typed"]
FAULTS = ["no_gate", "no_post_norms", "no_mup", "rotate_full", "no_window", "no_bias",
          "no_route_scale", "no_shared"]


def trinity_spec() -> dict:
    """``tiny_spec()`` plus the tiny federation, listed wherever the
    chip cell is."""
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny_fedavg_trinity", "source": "tests", "reduced": [], "why": "CPU rehearsal",
        "file": os.path.join(TINY_DIR, "configs", "tiny_fedavg_trinity.json")})
    spec["workloads"].append({
        "name": CELL, "config": "tiny_fedavg_trinity", "traffic": CELL, "chips": 1, "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CHIP_CELL in m.get("workloads", []):
                m["workloads"].append(CELL)
    return spec


@pytest.fixture
def trinity_root(tmp_path):
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(trinity_spec(), f)
    return str(tmp_path)


@pytest.fixture
def lanes(monkeypatch):
    from fedml_tpu.simulation import fedavg_api

    monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)


@pytest.fixture(scope="module")
def chip_cell():
    return harness.Cell(CHIP_CELL, root=CHECKOUT)


def test_end_to_end_line(trinity_root, lanes, monkeypatch):
    cell, res = run_cell(CELL, trinity_root, monkeypatch)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"samples_per_s", "round_p95_ms", "setup_s"} == set(line["metrics"])
    assert line["compared"]["moe_dropped"] == [0.0, 0.0]
    assert set(line["compared"]) >= {"loss_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap"}


def test_traced_line(trinity_root, lanes, monkeypatch):
    """On a CPU the trace holds no TPU plane: the readers of device time
    return nothing and are left out -- never 0; the counters' readers
    and the whole step's share read."""
    cell, res = run_cell(CELL, trinity_root, monkeypatch, trace=True)
    declared = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(declared)
    assert {"attn_window_device_ms", "dense_mlp_device_ms", "moe_shared_device_ms"} <= set(declared)
    assert not {"conv_op_device_ms", "pad_waste_pct", "ssm_device_ms", "flash_window_fwd_roofline"} & set(declared)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) <= set(declared)
    assert not set(NEW_READERS) & set(line["metrics"])  # device time, every one
    value = lambda name: line["metrics"][name]["value"]
    # cohorts of (3 or 2, 3 or 2) sequences at batch 1: a lane runs 3 steps or 2 of its 3
    assert 2 / 3 <= value("lane_steps_run_share") < 1.0
    assert 0.0 < value("moe_bias_moved_share") < 1.0
    assert value("mfu_pct.fedavg") > 0


@pytest.mark.parametrize("missing", ["output_gate", "post_norms", "embedding_multiplier"])
def test_setup_refuses_a_program_that_cannot_build_the_model(trinity_root, monkeypatch, missing):
    """A program that does not know one of the three fields (the parent
    of the PR that added them) takes its argument without a word: it is
    refused before any data is made."""
    import dataclasses

    from fedml_tpu import models
    from fedml_tpu.models import decoder

    real = models.create

    def unaware(args, output_dim):
        made = real(args, output_dim)
        default = decoder.MoEDecoderLM.__dataclass_fields__[missing].default
        return dataclasses.replace(made, module=made.module.clone(**{missing: default}))

    cell = harness.Cell(CELL, root=trinity_root)
    driver = cell.family_module().Driver(cell, 3)
    monkeypatch.setattr(models, "create", unaware)
    monkeypatch.setattr(driver, "load_data", lambda: pytest.fail("made data"))
    with pytest.raises(harness.BenchError, match="cannot build"):
        driver.setup()


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """One program drive and one reference drive, shared by the cases
    below."""
    from fedml_tpu.simulation import fedavg_api

    root = tmp_path_factory.mktemp("trinity")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(trinity_spec(), f)
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
        "calibrate_trinity", os.path.join(CHECKOUT, "benchmark", "tools", "calibrate_trinity.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ref = chip_cell.module("reference", "fedavg_trinity")
    assert {"fault_" + f for f in ref.FAULTS if f} == {"fault_" + f for f in FAULTS} <= set(tool.PLANTS)
    assert {"control_fp8", "fault_half_batch"} <= set(tool.PLANTS)


# the published config.json's numbers (model_type afmoe), as the source gives them
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 1024,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "route_scale": 2.826, "sliding_window": 2048, "rope_theta": 10000,
    "num_hidden_layers": 32, "num_dense_layers": 2, "rms_norm_eps": 1e-05, "global_attn_every_n_layers": 4,
    "max_position_embeddings": 131072, "mup_enabled": True, "score_func": "sigmoid", "route_norm": True,
}


def test_the_configuration_holds_the_sources_numbers(chip_cell):
    """The source's numbers stand under their own keys, the three cut
    ones under ``published``; the run's widths are the published ones,
    and the cut's parameter count is the one the deployment states."""
    import math

    import jax

    cfg, m = chip_cell.config, chip_cell.config["model"]
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == ["num_layers", "num_experts", "vocab_size"] and cfg["num_layers"] == 5
    assert (cfg["num_experts"], cfg["vocab_size"]) == (8, 25024)
    assert {k: cfg["published"][k] for k in ("num_experts", "vocab_size")} == {"num_experts": 128, "vocab_size": 200192}
    assert len(cfg["layer_types"]) == 32 and cfg["layer_types"][2:6] == m["layer_types"][1:]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "sliding_window"):
        assert m[key] == cfg[key], key
    assert m["embedding_multiplier"] == pytest.approx(math.sqrt(cfg["hidden_size"]))
    ref = chip_cell.module("reference", "fedavg_trinity")
    shapes = jax.tree.leaves(ref.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    assert round(sum(math.prod(s) for s in shapes) / 1e6, 1) == 504.1 and "504.1M" in cfg["deployment"]


# -- the new readers' rule: a number, or None --------------------------
def _ctx(chip_cell, **over):
    ctx = {
        "cell": chip_cell, "peaks": dict(FAKE_PEAKS), "flops": chip_cell.flops_module(),
        "device": {"count": 1}, "facts": {}, "setup_s": 1.0,
        "trace": {"modules": {}, "kernels": {}},
        "window": {"slot_samples": 140.0, "eval_slot_samples": 128.0, "counters": {}},
        "_lm_scopes": {}, "_lane_scopes": {}, "_gated_scopes": {},
    }
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_with_nothing_to_read_returns_none(chip_cell, name):
    """A program without the scopes (the parent of the PR that added
    them) leaves the metric out; it does not raise."""
    assert chip_cell.reader(name).read(_ctx(chip_cell)) is None


def test_scope_readers_read_milliseconds_a_round(chip_cell):
    ctx = _ctx(
        chip_cell,
        trace={"modules": {"jit_round_fn": {"count": 10.0, "total_s": 30.0}}, "kernels": {}},
        _gated_scopes={"blk.attn.gate": 2.5, "blk.post_norm": 0.7, "blk.attn.window": 9.0})
    assert chip_cell.reader("attn_gate_device_ms").read(ctx) == pytest.approx(250.0)
    assert chip_cell.reader("post_norm_device_ms").read(ctx) == pytest.approx(70.0)


def test_window_roofline_by_hand(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    window = {"slot_samples": 140.0, "train_slot_samples": 130.0, "eval_slot_samples": 116.0}
    kernels = {"flash_attention_window_fwd": {"count": 100, "total_s": 4.0}}
    ctx = _ctx(chip_cell, window=window, trace={"modules": {}, "kernels": kernels})
    # a query sees min(t + 1, 2,048) keys: (2048 * 2049 / 2 + 6144 * 2048) / 8192
    keys = (2048 * 2049 / 2 + 6144 * 2048) / 8192
    flops = 4.0 * 32 * 128 * keys * 8192
    need = fl.flash_fwd_sequence(m, 2048)
    assert need["flops"] == pytest.approx(flops)
    least = max(flops / FAKE_PEAKS["bf16_flops_per_s"], need["bytes"] / FAKE_PEAKS["hbm_bytes_per_s"])
    # 4 sliding layers; a trained sequence runs the kernel twice (remat), an evaluated one once
    want = 100.0 * least * 4 * (2 * 130 + 116) / 4.0
    assert chip_cell.reader("flash_window_fwd_roofline.typed").read(ctx) == pytest.approx(want)
    # without the lanes' count of the steps they ran there is nothing to count by
    bare = dict(window)
    del bare["train_slot_samples"]
    assert chip_cell.reader("flash_window_fwd_roofline.typed").read(
        _ctx(chip_cell, window=bare, trace={"modules": {}, "kernels": kernels})) is None


def test_forward_operations_by_hand(chip_cell):
    """The count by hand: ~713 MFLOP a token forward over the cut's five
    layers, attention with its gate ~64% of it."""
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    parts = fl.forward_flops_per_token(m)
    total = sum(parts.values())
    assert total / 1e6 == pytest.approx(713.0, abs=1.0)
    attention = parts["attention_projections"] + parts["attention_window"] + parts["attention_full"]
    assert attention / total == pytest.approx(0.64, abs=0.01)
    assert parts["experts"] / 1e6 == pytest.approx(25.2, abs=0.1)
    assert parts["shared_expert"] / 1e6 == pytest.approx(50.3, abs=0.1)


def test_gated_scopes_pass_leaves_the_closed_lists_as_it_found_them(chip_cell, monkeypatch):
    gated = chip_cell.module("layer_metrics", "_gated_scopes")
    import gzip
    import tempfile

    import _lm_scopes
    import _scopes

    before = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND
    seen = []

    def spy(data, raw=None):
        seen.append((_lm_scopes.LM_SCOPES, _lm_scopes.ROUND))
        return {}

    recorded = os.path.join(CHECKOUT, "benchmark", "testdata", "fedavg_scopes_round.xplane.pb.gz")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with gzip.open(recorded, "rb") as src, open(path, "wb") as dst:
            dst.write(src.read())
        monkeypatch.setattr(_scopes, "find_trace", lambda name: path)
        monkeypatch.setattr(_lm_scopes, "reduce_lm_scopes", spy)
        ctx = {"cell": chip_cell}
        assert gated.summary(ctx) == {}
        assert gated.ms_per_round({**ctx, "trace": {"modules": {}}}, "blk.attn.gate") is None
    assert [s[1] for s in seen] == ["jit_round_fn"]
    assert {"blk.attn.gate", "blk.post_norm", "moe.shared", "blk.mlp.dense"} <= set(seen[0][0])
    assert (_lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND) == before
