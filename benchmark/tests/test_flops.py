"""The functions that count operations and bytes, against hand counts."""

import pytest

import harness
from conftest import CHECKOUT


def _cell(name):
    return harness.Cell(name, root=CHECKOUT)


def test_resnet18_cifar_hand_count():
    cell = _cell("fedavg_r18_c32")
    flops, model = cell.flops_module(), cell.config["model"]
    macs = flops.forward_macs(model)
    # stem: 32x32 outputs x 3x3 kernel x 3 -> 64 channels
    assert macs["stem"] == 32 * 32 * 9 * 3 * 64 == 1_769_472
    # stage 1: four 3x3 64->64 convolutions at 32x32
    s1 = 4 * 32 * 32 * 9 * 64 * 64
    # stages 2-4: (3x3 cin->c, /2) + three 3x3 c->c + the 1x1 projection
    s2 = 16 * 16 * (9 * 64 * 128 + 3 * 9 * 128 * 128 + 64 * 128)
    s3 = 8 * 8 * (9 * 128 * 256 + 3 * 9 * 256 * 256 + 128 * 256)
    s4 = 4 * 4 * (9 * 256 * 512 + 3 * 9 * 512 * 512 + 256 * 512)
    assert macs["blocks"] == s1 + s2 + s3 + s4
    assert macs["head"] == 512 * 10
    total = sum(macs.values())
    assert total == pytest.approx(0.556e9, rel=2e-3)  # 0.56 GMAC forward
    assert flops.eval_flops_per_sample(model) == 2.0 * total
    # backward: weight and input gradients, none into the images
    assert flops.train_flops_per_sample(model) == 2.0 * (3 * total - macs["stem"])
    assert flops.train_flops_per_sample(model) == pytest.approx(3.33e9, rel=2e-3)


def test_resnet_window_counts_useful_samples_only():
    cell = _cell("fedavg_r18_c32")
    flops, model = cell.flops_module(), cell.config["model"]
    win = {"useful_samples": 1000.0, "slot_samples": 99999.0, "eval_samples": 500.0}
    assert flops.window_flops(cell, win) == (
        1000 * flops.train_flops_per_sample(model) + 500 * flops.eval_flops_per_sample(model))


def test_gpt2_hand_count(full_root):
    cell = harness.Cell("gpt2_b8_t1024", root=full_root)
    flops, m = cell.flops_module(), cell.config["model"]
    c, v, t = 768, 50257, 1024
    assert flops.matmul_params(m) == 12 * 12 * c * c + c * v == 123_532_032
    # per token: 2 per weight, and causal attention's two products over
    # half the square: 2 x 2 x (T/2) x C a layer
    fwd = 2 * 123_532_032 + 12 * 2 * 2 * (t / 2) * c
    assert flops.forward_flops_per_token(m, t) == fwd
    assert flops.train_flops_per_token(m, t) == 3 * fwd
    assert flops.train_flops_per_token(m, t) == pytest.approx(0.798e9, rel=2e-3)


def test_flash_forward_call_hand_count(full_root):
    cell = harness.Cell("gpt2_b8_t1024", root=full_root)
    need = cell.flops_module().flash_fwd_call(cell.config["model"], 8, 1024)
    # 8 x 12 heads, the lower triangle of 1024^2, 64 wide, two products
    assert need["flops"] == 2 * 2 * 8 * 12 * (1024 * 1024 / 2) * 64 == 12_884_901_888
    # q, k, v read and o written in bf16, the log-sum-exp row in float32
    assert need["bytes"] == 4 * 8 * 1024 * 768 * 2 + 8 * 12 * 1024 * 4
