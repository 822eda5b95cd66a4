"""Operations a Mellum2-style MoE decoder's training needs, from shapes.

Matrix products only (2 operations a multiply-accumulate), per *useful*
token of a sequence of ``seq_len``:

- the four attention projections and the router of every layer;
- the experts: a token places ``k * held / num_experts`` of its ``k``
  choices on this chip's held experts on average (one, at 8 of 64 and
  top 8), each through the three matrices of a gated MLP;
- attention's two products over the keys a query sees: ``(T + 1) / 2``
  on a full (causal) layer, and on a sliding layer the mean of
  ``min(t + 1, window)``;
- the output head over the vocabulary slice.

Embedding look-ups, RMSNorm, the rotary embedding, SiLU, softmax, top-k,
sort and the optimizer are left out. Backward costs twice the forward.
Nothing recomputed (the blocks are rematerialised), padded or masked is
counted.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def keys_seen(seq_len: int, window) -> float:
    """Mean number of keys a query attends, itself included."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq_len - window) * window) / seq_len


def forward_flops_per_token(m: dict) -> dict:
    c, d = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    layers = len(m["layer_types"])
    sliding = sum(1 for kind in m["layer_types"] if kind == SLIDING)
    held_choices = m["num_experts_per_tok"] * m["experts_held"][1] / m["num_experts"]
    score = lambda window: 2.0 * 2.0 * h * d * keys_seen(m["seq_len"], window)
    return {
        "projections": layers * 2.0 * (2 * c * h * d + 2 * c * kv * d),
        "router": layers * 2.0 * c * m["num_experts"],
        "experts": layers * held_choices * 2.0 * 3 * c * m["moe_intermediate_size"],
        "attention_window": sliding * score(m["sliding_window"]),
        "attention_full": (layers - sliding) * score(None),
        "head": 2.0 * c * m["vocab_size"],
    }


def eval_flops_per_token(m: dict) -> float:
    return sum(forward_flops_per_token(m).values())


def train_flops_per_token(m: dict) -> float:
    return 3.0 * eval_flops_per_token(m)


def flash_fwd_sequence(m: dict, window, itemsize: int = 2) -> dict:
    """One sequence through one layer's flash forward kernel: the
    products over the keys each query sees, and the least traffic -- Q
    read and O written once, a KV head's K and V read once for the whole
    group of query heads that shares it, the log-sum-exp row written in
    float32."""
    t, d = m["seq_len"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    flops = 2.0 * 2.0 * h * d * keys_seen(t, window) * t
    nbytes = 2.0 * t * h * d * itemsize + 2.0 * t * kv * d * itemsize + h * t * 4.0
    return {"flops": flops, "bytes": nbytes}


def window_flops(cell, win: dict) -> float:
    """Required operations of a measured window: training of the useful
    sequences and the evaluations' forward passes."""
    m = cell.config["model"]
    t = m["seq_len"]
    return (win["useful_samples"] * t * train_flops_per_token(m)
            + win["eval_samples"] * t * eval_flops_per_token(m))
