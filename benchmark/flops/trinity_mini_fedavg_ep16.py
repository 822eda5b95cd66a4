"""Operations an AFMoE-style decoder's training needs, from shapes.

Matrix products only (2 operations a multiply-accumulate), per *useful*
token of a sequence of ``seq_len``:

- every layer's five attention projections (q, k, v, o and the output
  gate's ``gate_proj``, as wide as q);
- attention's two products over the keys a query sees: ``(T + 1) / 2``
  on a full (causal) layer, and on a sliding layer the mean of
  ``min(t + 1, window)``;
- the leading layers' dense gated MLP (three matrices of
  ``intermediate_size``);
- every other layer's router, its experts -- a token places ``k * held
  / num_experts`` of its ``k`` choices on this chip's held experts on
  average (0.5, at 8 of 128 and top 8), each through the three matrices
  of a gated MLP -- and the shared expert's three matrices on every
  token;
- the output head over the vocabulary slice.

Embedding look-ups and their multiplier, RMSNorm (the q/k norms and the
sandwich norms), the rotary embedding, the gate's sigmoid and product,
SiLU, softmax, sigmoid, top-k, sort and the optimizer are left out.
Backward costs twice the forward. Nothing recomputed (the blocks are
rematerialised), padded or masked is counted.
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
    dense = m["num_dense_layers"]
    sparse = layers - dense
    held_choices = m["num_experts_per_tok"] * m["experts_held"][1] / m["num_experts"]
    width = m["moe_intermediate_size"]
    score = lambda window: 2.0 * 2.0 * h * d * keys_seen(m["seq_len"], window)
    return {
        "attention_projections": layers * 2.0 * (3 * c * h * d + 2 * c * kv * d),
        "attention_window": sliding * score(m["sliding_window"]),
        "attention_full": (layers - sliding) * score(None),
        "dense_mlp": dense * 2.0 * 3 * c * m["intermediate_size"],
        "router": sparse * 2.0 * c * m["num_experts"],
        "experts": sparse * held_choices * 2.0 * 3 * c * width,
        "shared_expert": sparse * 2.0 * 3 * c * width * m["num_shared_experts"],
        "head": 2.0 * c * m["vocab_size"],
    }


def eval_flops_per_token(m: dict) -> float:
    return sum(forward_flops_per_token(m).values())


def train_flops_per_token(m: dict) -> float:
    return 3.0 * eval_flops_per_token(m)


def flash_fwd_sequence(m: dict, window, itemsize: int = 2) -> dict:
    """One sequence through one layer's flash forward kernel (the
    windowed one where ``window`` is given): the products over the keys
    each query sees, and the least traffic -- Q read and O written once,
    a KV head's K and V read once for the whole group of query heads
    that shares it, the log-sum-exp row written in float32."""
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
