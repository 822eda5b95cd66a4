"""Operations a GroupNorm ResNet's training needs, from shapes alone.

Counts the multiply-accumulates of every convolution and of the
classifier (2 operations each); normalisation, ReLU, pooling and the
loss are left out (under 1% of the whole). Backward: every layer's
weight gradient and every layer's input gradient but the stem's (no
gradient flows into the images). Nothing recomputed, padded or masked
is counted: the numbers are per *useful* sample.
"""

from __future__ import annotations


def forward_macs(model: dict) -> dict:
    """{"stem": .., "blocks": .., "head": ..} multiply-accumulates of
    one forward pass of one image."""
    h, w, cin = model["image"]
    ks = model["stem_kernel"]
    c = model["stage_channels"][0]
    stem = h * w * ks * ks * cin * c
    blocks = 0
    for i, (size, ch) in enumerate(zip(model["stage_sizes"], model["stage_channels"])):
        for j in range(size):
            stride = 2 if (i > 0 and j == 0) else 1
            h, w = h // stride, w // stride
            blocks += h * w * 9 * c * ch + h * w * 9 * ch * ch
            if stride != 1 or c != ch:
                blocks += h * w * c * ch
            c = ch
    return {"stem": stem, "blocks": blocks, "head": c * model["classes"]}


def eval_flops_per_sample(model: dict) -> float:
    return 2.0 * sum(forward_macs(model).values())


def train_flops_per_sample(model: dict) -> float:
    m = forward_macs(model)
    total = sum(m.values())
    return 2.0 * (3 * total - m["stem"])


def window_flops(cell, win: dict) -> float:
    """Required operations of a measured window: training of the useful
    samples and the evaluations' forward passes."""
    model = cell.config["model"]
    return (win["useful_samples"] * train_flops_per_sample(model)
            + win["eval_samples"] * eval_flops_per_sample(model))
