"""Operations a Nemotron-H-style hybrid decoder's training needs, from
shapes.

Matrix products only (2 operations a multiply-accumulate), per *useful*
token of a sequence of ``seq_len``; every layer is one sublayer:

- a ``mamba`` sublayer's two projections (``in_proj`` to ``2 d_inner +
  2 G N + H``, ``out_proj``) and its scan in the chunked form at the
  configuration's ``chunk_size`` (``ssd_chunk``: ``C B^T`` a group, the
  masked product, the chunk's state and ``C S`` a head) -- the
  step-by-step recurrence would need fewer operations and none of them
  a matrix product;
- an attention sublayer's four projections and its two products over
  the keys a query sees, ``(T + 1) / 2`` (causal);
- an expert sublayer's router, its experts -- a token places ``k *
  held / n_routed_experts`` of its ``k`` choices on this chip's held
  experts on average (0.375 at 8 of 128 and top 6), each through the
  two matrices of a squared-ReLU MLP -- and the shared expert's two
  matrices on every token;
- the output head over the vocabulary slice.

The convolution's taps, SiLU, softplus, the decays and their
exponentials, the gated norm, embedding look-ups, RMSNorm, softmax,
sigmoid, top-k, sort and the optimizer are left out. Backward costs
twice the forward. Nothing recomputed (the blocks are rematerialised),
padded or masked is counted.
"""

from __future__ import annotations

FULL, SSM, EXPERTS = "full_attention", "mamba", "moe"


def keys_seen(seq_len: int) -> float:
    """Mean number of keys a causal query attends, itself included."""
    return (seq_len + 1) / 2.0


def ssd_chunk(m: dict, itemsize: int = 2) -> dict:
    """One chunk of ``chunk_size`` tokens of one sequence through one
    ``mamba`` sublayer's scan, forward: the four products, and the
    least traffic -- ``x`` read and ``y`` written in the compute type,
    ``B`` and ``C`` read once a group, ``dt`` read in float32. The
    carried state need never leave the chip's fast memory and is not
    counted. Whatever implements the scan is held to this work."""
    q, h, p = m["chunk_size"], m["mamba_num_heads"], m["mamba_head_dim"]
    g, n = m["n_groups"], m["ssm_state_size"]
    flops = 2.0 * q * q * n * g  # C B^T, a group
    flops += 2.0 * q * q * p * h  # (L o C B^T) (dt x), a head
    flops += 2.0 * 2.0 * q * p * n * h  # the chunk's state; C S
    nbytes = 2.0 * q * h * p * itemsize + 2.0 * q * g * n * itemsize + 4.0 * q * h
    return {"flops": flops, "bytes": nbytes}


def forward_flops_per_token(m: dict) -> dict:
    c, d = m["hidden_size"], m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    count = lambda kind: sum(1 for k in m["layer_types"] if k == kind)
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    in_proj = 2 * inner + 2 * m["n_groups"] * m["ssm_state_size"] + m["mamba_num_heads"]
    held_choices = m["num_experts_per_tok"] * m["experts_held"][1] / m["n_routed_experts"]
    return {
        "ssm_projections": count(SSM) * 2.0 * (c * in_proj + inner * c),
        "ssm_scan": count(SSM) * ssd_chunk(m)["flops"] / m["chunk_size"],
        "attention_projections": count(FULL) * 2.0 * (2 * c * h * d + 2 * c * kv * d),
        "attention_full": count(FULL) * 2.0 * 2.0 * h * d * keys_seen(m["seq_len"]),
        "router": count(EXPERTS) * 2.0 * c * m["n_routed_experts"],
        "experts": count(EXPERTS) * held_choices * 2.0 * 2 * c * m["moe_intermediate_size"],
        "shared_expert": count(EXPERTS) * 2.0 * 2 * c * m["moe_shared_expert_intermediate_size"],
        "head": 2.0 * c * m["vocab_size"],
    }


def eval_flops_per_token(m: dict) -> float:
    return sum(forward_flops_per_token(m).values())


def train_flops_per_token(m: dict) -> float:
    return 3.0 * eval_flops_per_token(m)


def flash_fwd_sequence(m: dict, window=None, itemsize: int = 2) -> dict:
    """One sequence through the attention sublayer's flash forward
    kernel: the products over the keys each query sees, and the least
    traffic -- Q read and O written once, a KV head's K and V read once
    for the whole group of query heads that shares it, the log-sum-exp
    row written in float32. The model has no window layer."""
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
