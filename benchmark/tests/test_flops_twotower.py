"""``flops/nemotron_twotower_30b_a3b_fedavg_ep16.py`` against hand counts
at the chip cell's sizes."""

import pytest

import harness
from conftest import CHECKOUT


@pytest.fixture(scope="module")
def chip_cell():
    return harness.Cell("fedavg_twotower_t8192", root=CHECKOUT)


def test_forward_count_by_hand(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    parts = fl.forward_flops_per_token(m)
    c = 2688
    # in_proj to z 4096 + xBC 6144 + dt 64, out_proj from 4096; three mixers
    assert parts["ssm_projections"] == 3 * 2 * (c * 10304 + 4096 * c) == 232_243_200
    # a chunk of 128: C B^T a group, the masked product a head, the state and C S a head
    chunk = 2 * 128 * 128 * 128 * 8 + 2 * 128 * 128 * 64 * 64 + 2 * 2 * 128 * 64 * 128 * 64
    assert fl.ssd_chunk(m)["flops"] == chunk == 436_207_616
    assert parts["ssm_scan"] == 3 * chunk / 128
    assert parts["attention_projections"] == 2 * (2 * c * 32 * 128 + 2 * c * 2 * 128) == 46_792_704
    assert parts["attention_full"] == 2 * 2 * 32 * 128 * 4096.5
    assert parts["router"] == 3 * 2 * c * 128
    # 6 choices x 8 held / 128 experts = 0.375 held choices a token a layer, two matrices each
    assert parts["experts"] == 3 * 0.375 * 2 * 2 * c * 1856 == 22_450_176
    assert parts["shared_expert"] == 3 * 2 * 2 * c * 3712 == 119_734_272
    assert parts["head"] == 2 * c * 16384 == 88_080_384
    total = sum(parts.values())
    assert 588e6 < total < 590e6
    assert fl.eval_flops_per_token(m) == total and fl.train_flops_per_token(m) == 3 * total
    # the issue's shares: the mixers 41%, experts 24%, attention 19%, head 15%
    share = lambda *keys: sum(parts[k] for k in keys) / total
    assert share("ssm_projections", "ssm_scan") == pytest.approx(0.41, abs=0.01)
    assert share("router", "experts", "shared_expert") == pytest.approx(0.245, abs=0.01)
    assert share("attention_projections", "attention_full") == pytest.approx(0.19, abs=0.01)
    assert share("head") == pytest.approx(0.15, abs=0.01)


def test_scan_chunk_bytes_by_hand(chip_cell):
    need = chip_cell.flops_module().ssd_chunk(chip_cell.config["model"])
    # x read and y written: 128 x 4096 bf16 each; B and C: 128 x 1024 bf16 each; dt: 128 x 64 f32
    assert need["bytes"] == 2 * 128 * 4096 * 2 + 2 * 128 * 1024 * 2 + 128 * 64 * 4 == 2_654_208
    # bound by bandwidth on a v5e: 436 MFLOP / 197 TFLOP/s = 2.2 us, 2.65 MB / 819 GB/s = 3.2 us
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


def test_window_counts_useful_sequences_only(chip_cell):
    fl, m = chip_cell.flops_module(), chip_cell.config["model"]
    win = {"useful_samples": 126.0, "slot_samples": 140.0, "eval_samples": 116.0}
    want = 126 * 8192 * fl.train_flops_per_token(m) + 116 * 8192 * fl.eval_flops_per_token(m)
    assert fl.window_flops(chip_cell, win) == want
    assert 1.44e13 < 8192 * fl.train_flops_per_token(m) < 1.45e13  # a trained sequence


def test_flash_kernel_count_by_hand(chip_cell):
    fl = chip_cell.flops_module()
    need = fl.flash_fwd_sequence(chip_cell.config["model"], None)
    assert need["flops"] == 4 * 32 * 128 * 4096.5 * 8192
    # q and o: 8192 x 32 x 128 bf16 each; k and v: 8192 x 2 x 128 each; lse 32 x 8192 f32
    assert need["bytes"] == 2 * 8192 * 4096 * 2 + 2 * 8192 * 256 * 2 + 32 * 8192 * 4
    with pytest.raises(ValueError, match="window"):
        fl.flash_fwd_sequence(chip_cell.config["model"], 1024)
