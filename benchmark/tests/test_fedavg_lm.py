"""The ``fedavg_lm`` family on the CPU at a tiny size, added the way its
chip cell was: new files and ``BENCHMARK.json`` entries only. A whole
run (``run.run_cell``: set-up, window, release, reduction, comparison
with the plain reference), the check against the control and each
planted fault, the operation count against a hand count, and every new
reader's rule: a number, or None where there is nothing to read.

(``test_rehearsal.py``'s ``from_trace`` list is closed and a
``benchmark`` issue's to open, so the traced line is checked here.)
"""

import json
import math
import os

import pytest

import harness
from conftest import CHECKOUT, FAKE_PEAKS, TINY_DIR, run_cell, tiny_spec

CELL, CHIP_CELL = "tiny_l2", "fedavg_mellum2_c2_t4096"
NEW_READERS = [
    "attn_window_device_ms", "attn_full_device_ms", "moe_route_device_ms", "moe_experts_device_ms",
    "head_loss_device_ms", "moe_load_max_over_mean", "flash_window_fwd_roofline",
    "flash_full_fwd_roofline",
]


@pytest.fixture
def lm_root(tmp_path):
    """``tiny_spec()`` plus the tiny LM federation, listed wherever the
    chip cell is."""
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny_fedavg_lm", "source": "tests", "reduced": [], "why": "CPU rehearsal",
        "file": os.path.join(TINY_DIR, "configs", "tiny_fedavg_lm.json")})
    spec["workloads"].append({
        "name": CELL, "config": "tiny_fedavg_lm", "traffic": CELL, "chips": 1,
        "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if CHIP_CELL in m.get("workloads", []):
                m["workloads"].append(CELL)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(tmp_path)


def test_end_to_end_line(lm_root, monkeypatch):
    cell, res = run_cell(CELL, lm_root, monkeypatch)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["metrics"]) == sorted(m["name"] for m in cell.end_to_end)
    assert {"samples_per_s", "round_p95_ms", "setup_s"} == set(line["metrics"])
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["compared"]) == {
        "loss_gap", "eval_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap",
        "moe_dropped"}
    assert line["compared"]["moe_dropped"] == [0.0, 0.0]
    for value, limit in line["compared"].values():
        assert value <= limit


def test_traced_line(lm_root, monkeypatch):
    """On a CPU the trace holds no TPU plane: the readers of device time
    return nothing and are left out -- never 0; the counters' reader and
    the whole step's share read."""
    cell, res = run_cell(CELL, lm_root, monkeypatch, trace=True)
    declared = [m["name"] for m in cell.per_layer]
    assert set(NEW_READERS) <= set(declared)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) <= set(declared)
    from_trace = {
        "round_fn_device_ms", "eval_device_ms", "device_idle_pct.fedavg", "peak_hbm_pct.fedavg",
        "gather_device_ms", "local_train_device_ms", "aggregate_device_ms", "idle_unnamed_pct",
        "attn_window_device_ms", "attn_full_device_ms", "moe_route_device_ms",
        "moe_experts_device_ms", "head_loss_device_ms", "flash_window_fwd_roofline",
        "flash_full_fwd_roofline"}
    assert set(declared) - set(line["metrics"]) <= from_trace
    ratio = line["metrics"]["moe_load_max_over_mean"]
    assert ratio["unit"] == "ratio" and 1.0 <= ratio["value"] < 4.0
    assert line["metrics"]["mfu_pct.fedavg"]["value"] > 0
    assert line["metrics"]["pad_waste_pct"]["value"] == 0.0  # no padded lane, no masked batch
    assert {"busy_s", "window_s"} <= set(line["device"])


def _driver(lm_root, seed=3):
    cell = harness.Cell(CELL, root=lm_root)
    cell.traffic = dict(cell.traffic, rounds_per_call=1)
    driver = cell.family_module().Driver(cell, seed)
    driver.setup()
    got = driver.observed
    driver.release()
    return cell, driver, got


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """One program drive and one reference drive, shared by the cases
    below."""
    root = tmp_path_factory.mktemp("lm")
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny_fedavg_lm", "source": "tests", "reduced": [], "why": "CPU rehearsal",
        "file": os.path.join(TINY_DIR, "configs", "tiny_fedavg_lm.json")})
    spec["workloads"].append({
        "name": CELL, "config": "tiny_fedavg_lm", "traffic": CELL, "chips": 1, "why": "CPU rehearsal"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    cell, driver, got = _driver(str(root))
    return cell, driver, got, driver.reference_numbers()


def test_program_is_inside_the_limits(checked):
    cell, driver, got, want = checked
    g = driver.gaps(got, want)
    assert all(g[k] <= cell.config["limits"][k] for k in g), g
    assert all(c["moe_dropped"] == 0.0 and c["moe_local_hits"] > 0 for c in got["counters"])


@pytest.mark.parametrize("plant", ["fp8", "half_batch", "no_window", "no_renorm", "no_yarn"])
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


def test_a_number_without_a_limit_is_not_compared_nor_computed(checked, monkeypatch):
    """The chip cell limits no ``eval_gap`` (no reading separates it):
    the reference then follows no evaluation, the comparison leaves the
    number out, and a limit on a number the family does not read is an
    error, not a number passed over."""
    cell, driver, got, want = checked
    chip = harness.Cell(CHIP_CELL).config["limits"]
    assert set(chip) == {"loss_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap", "why"}
    assert "eval_gap" in cell.config["limits"] and want["eval_test"] and want["eval_train"]
    monkeypatch.setitem(driver.cfg, "limits", {k: v for k, v in cell.config["limits"].items()
                                               if k != "eval_gap"})
    monkeypatch.setattr(driver.ref, "evaluate", lambda *a, **k: pytest.fail("evaluated"))
    lean = driver.reference_numbers()
    assert lean["eval_test"] == [] and lean["eval_train"] == [] and lean["loss"] == want["loss"]
    compared = harness.Compared()
    driver.compare(compared)
    assert set(compared.as_dict()) == {
        "loss_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap", "moe_dropped"}
    assert compared.correct
    monkeypatch.setitem(driver.cfg, "limits", dict(driver.cfg["limits"], top1_gap=0.1))
    with pytest.raises(harness.BenchError, match="top1_gap"):
        driver.compare(harness.Compared())


def test_same_seed_same_tokens_other_seed_other_tokens():
    import numpy as np

    fam = harness.Cell(CHIP_CELL).family_module()  # puts families/ on sys.path for its import
    big = 2 ** 31 + 7  # the driver's seeds are large
    x, y = fam.synth_tokens(big, (2, 3, 2, 64), 12288)
    x2, _ = fam.synth_tokens(big, (2, 3, 2, 64), 12288)
    x3, _ = fam.synth_tokens(big + 1, (2, 3, 2, 64), 12288)
    assert x.shape == y.shape == (2, 3, 2, 64) and str(x.dtype) == "int32"
    assert (np.asarray(x) == np.asarray(x2)).all() and not (np.asarray(x) == np.asarray(x3)).all()
    assert (np.asarray(x)[..., 1:] == np.asarray(y)[..., :-1]).all()
    assert 0 <= int(x.min()) and int(x.max()) < 12288


# -- the operation count against a hand count --------------------------
@pytest.fixture(scope="module")
def chip_cell():
    return harness.Cell(CHIP_CELL)


def test_forward_count_by_hand(chip_cell):
    """Per token at the published widths: a layer's projections are
    2 x 21.23M, its router 2 x 2304 x 64, its one held-expert choice
    2 x 3 x 2304 x 896; a sliding layer's queries see 896.125 keys on
    average at T = 4,096 (window 1,024), a full layer's 2,048.5; the
    head is 2 x 2304 x 12,288."""
    fl = chip_cell.flops_module()
    m = chip_cell.config["model"]
    parts = fl.forward_flops_per_token(m)
    proj = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    assert proj == 21_233_664
    assert parts["projections"] == 4 * 2 * proj
    assert parts["router"] == 4 * 2 * 2304 * 64
    assert parts["experts"] == 4 * 2 * 3 * 2304 * 896
    assert fl.keys_seen(4096, 1024) == 896.125 and fl.keys_seen(4096, None) == 2048.5
    assert parts["attention_window"] == 3 * 4 * 32 * 128 * 896.125
    assert parts["attention_full"] == 4 * 32 * 128 * 2048.5
    assert parts["head"] == 2 * 2304 * 12288
    assert math.isclose(fl.eval_flops_per_token(m), 0.35482624e9)
    assert math.isclose(fl.train_flops_per_token(m), 1.06447872e9)


def test_window_count_by_hand(chip_cell):
    """A round trains 2 silos x 16 sequences, an evaluation scores 144:
    139.5 and 209 TFLOP."""
    fl = chip_cell.flops_module()
    round_ = fl.window_flops(chip_cell, {"useful_samples": 32.0, "eval_samples": 0.0})
    evaluation = fl.window_flops(chip_cell, {"useful_samples": 0.0, "eval_samples": 144.0})
    assert math.isclose(round_, 32 * 4096 * 1.06447872e9) and 139e12 < round_ < 140e12
    assert math.isclose(evaluation, 144 * 4096 * 0.35482624e9) and 209e12 < evaluation < 210e12


@pytest.mark.parametrize("window,keys", [(1024, 896.125), (None, 2048.5)])
def test_flash_kernel_count_by_hand(chip_cell, window, keys):
    fl = chip_cell.flops_module()
    need = fl.flash_fwd_sequence(chip_cell.config["model"], window)
    assert need["flops"] == 4 * 32 * 128 * keys * 4096
    # q and o: 4096 x 32 x 128 bf16 each; k and v: 4096 x 4 x 128 each; lse 32 x 4096 f32
    assert need["bytes"] == 2 * 4096 * 4096 * 2 + 2 * 4096 * 512 * 2 + 32 * 4096 * 4


# -- the new readers' rule: a number, or None --------------------------
def _ctx(chip_cell, **over):
    ctx = {
        "cell": chip_cell, "peaks": dict(FAKE_PEAKS), "flops": chip_cell.flops_module(),
        "device": {"count": 1}, "facts": {}, "setup_s": 1.0,
        "trace": {"modules": {}, "kernels": {}},
        "window": {"slot_samples": 64.0, "eval_slot_samples": 160.0, "counters": {}},
        "_lm_scopes": {},
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
        _lm_scopes={"blk.attn.window": 3.0, "blk.attn.full": 2.0, "moe.route": 0.5,
                    "moe.combine": 0.25, "moe.experts": 1.0, "lm.head_loss": 0.4})
    read = lambda name: chip_cell.reader(name).read(ctx)
    assert read("attn_window_device_ms") == pytest.approx(300.0)
    assert read("attn_full_device_ms") == pytest.approx(200.0)
    assert read("moe_route_device_ms") == pytest.approx(75.0)  # route + combine
    assert read("moe_experts_device_ms") == pytest.approx(100.0)
    assert read("head_loss_device_ms") == pytest.approx(40.0)


def test_counter_and_roofline_readers(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    ctx = _ctx(
        chip_cell,
        window={"slot_samples": 64.0, "eval_slot_samples": 160.0,
                "counters": {"moe_expert_tokens_max": 300.0, "moe_expert_tokens_mean": 200.0}},
        trace={"modules": {}, "kernels": {
            "flash_attention_window_fwd": {"count": 12.0, "total_s": 4.0},
            "flash_attention_fwd": {"count": 4.0, "total_s": 2.0}}})
    assert chip_cell.reader("moe_load_max_over_mean").read(ctx) == pytest.approx(1.5)
    # 2 passes of 64 training slots (remat) and 160 evaluation slots, 3 sliding layers
    need = fl.flash_fwd_sequence(m, 1024)
    least = max(need["flops"] / FAKE_PEAKS["bf16_flops_per_s"], need["bytes"] / FAKE_PEAKS["hbm_bytes_per_s"])
    want = 100.0 * least * (2 * 64 + 160) * 3 / 4.0
    assert chip_cell.reader("flash_window_fwd_roofline").read(ctx) == pytest.approx(want)
    need = fl.flash_fwd_sequence(m, None)
    least = max(need["flops"] / FAKE_PEAKS["bf16_flops_per_s"], need["bytes"] / FAKE_PEAKS["hbm_bytes_per_s"])
    assert chip_cell.reader("flash_full_fwd_roofline").read(ctx) == pytest.approx(
        100.0 * least * (2 * 64 + 160) * 1 / 2.0)


def test_lm_scopes_reduction_keeps_the_round_executable_only(chip_cell):
    """A recorded v5e trace of the ResNet cell names none of the LM's
    scopes: the reduction returns nothing and leaves ``_scopes.SCOPES``
    as it found it."""
    import gzip

    from jax.profiler import ProfileData

    lm = chip_cell.module("layer_metrics", "_lm_scopes")  # puts its directory on sys.path
    import _scopes
    with gzip.open(os.path.join(CHECKOUT, "benchmark", "testdata", "fedavg_scopes_round.xplane.pb.gz")) as f:
        raw = f.read()
    before = _scopes.SCOPES
    assert lm.reduce_lm_scopes(ProfileData.from_serialized_xspace(raw), raw) == {}
    assert _scopes.SCOPES == before
