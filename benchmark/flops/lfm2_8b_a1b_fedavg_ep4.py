"""Operations an LFM2-MoE-style decoder's training needs, from shapes.

Matrix products only (2 operations a multiply-accumulate), per *useful*
token of a sequence of ``seq_len``:

- a ``conv`` layer's two projections (``in_proj`` to 3 x hidden,
  ``out_proj``); an attention layer's four;
- attention's two products over the keys a query sees, ``(T + 1) / 2``
  on a full (causal) layer;
- the leading layers' dense gated MLP (three matrices of
  ``intermediate_size``);
- the router of every sparse layer and its experts: a token places
  ``k * held / num_experts`` of its ``k`` choices on this chip's held
  experts on average (one, at 8 of 32 and top 4), each through the three
  matrices of a gated MLP;
- the output head over the vocabulary slice (tied to the embedding: the
  product is counted, the look-up is not).

The convolution's taps and gates (7 elementwise operations a channel),
embedding look-ups, RMSNorm, the rotary embedding, SiLU, softmax,
sigmoid, top-k, sort and the optimizer are left out. Backward costs
twice the forward. Nothing recomputed (the blocks are rematerialised),
padded or masked is counted.
"""

from __future__ import annotations

FULL, CONV = "full_attention", "conv"


def keys_seen(seq_len: int) -> float:
    """Mean number of keys a causal query attends, itself included."""
    return (seq_len + 1) / 2.0


def forward_flops_per_token(m: dict) -> dict:
    c, d = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    conv = sum(1 for kind in m["layer_types"] if kind == CONV)
    full = sum(1 for kind in m["layer_types"] if kind == FULL)
    dense = m["num_dense_layers"]
    sparse = len(m["layer_types"]) - dense
    held_choices = m["num_experts_per_tok"] * m["experts_held"][1] / m["num_experts"]
    return {
        "conv_projections": conv * 2.0 * (3 * c * c + c * c),
        "attention_projections": full * 2.0 * (2 * c * h * d + 2 * c * kv * d),
        "attention_full": full * 2.0 * 2.0 * h * d * keys_seen(m["seq_len"]),
        "dense_mlp": dense * 2.0 * 3 * c * m["intermediate_size"],
        "router": sparse * 2.0 * c * m["num_experts"],
        "experts": sparse * held_choices * 2.0 * 3 * c * m["moe_intermediate_size"],
        "head": 2.0 * c * m["vocab_size"],
    }


def eval_flops_per_token(m: dict) -> float:
    return sum(forward_flops_per_token(m).values())


def train_flops_per_token(m: dict) -> float:
    return 3.0 * eval_flops_per_token(m)


def flash_fwd_sequence(m: dict, window=None, itemsize: int = 2) -> dict:
    """One sequence through one layer's flash forward kernel: the
    products over the keys each query sees, and the least traffic -- Q
    read and O written once, a KV head's K and V read once for the whole
    group of query heads that shares it, the log-sum-exp row written in
    float32. The model has no window layer."""
    if window is not None:
        raise ValueError("this configuration has no sliding-window layer")
    t, d = m["seq_len"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    flops = 2.0 * 2.0 * h * d * keys_seen(t) * t
    nbytes = 2.0 * t * h * d * itemsize + 2.0 * t * kv * d * itemsize + h * t * 4.0
    return {"flops": flops, "bytes": nbytes}


def window_flops(cell, win: dict) -> float:
    """Required operations of a measured window: training of the useful
    sequences and the evaluations' forward passes."""
    m = cell.config["model"]
    t = m["seq_len"]
    return (win["useful_samples"] * t * train_flops_per_token(m)
            + win["eval_samples"] * t * eval_flops_per_token(m))
