"""Operations and bytes a GPT-2 decoder's training needs, from shapes.

Matrix products only (2 operations a multiply-accumulate): the four
projections and two MLP matrices of each block, the output head, and
causal attention's two products over the lower triangle. Embedding
look-ups, LayerNorm, GELU, softmax and the optimizer are left out
(~1%). Backward costs twice the forward. Nothing recomputed is counted.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    c = m["n_embd"]
    return m["n_layer"] * 12 * c * c + c * m["vocab_size"]


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    # causal attention: QK^T and PV over half the [T, T] square
    attn = m["n_layer"] * 2 * 2 * (seq_len / 2.0) * m["n_embd"]
    return 2.0 * matmul_params(m) + attn


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len)


def flash_fwd_call(m: dict, batch: int, seq_len: int, itemsize: int = 2) -> dict:
    """One call of the flash forward kernel over [B, T, H, D]: the
    causal half of QK^T and PV, and the least traffic -- Q, K, V read
    once, O written once, the log-sum-exp row written in float32."""
    h, d = m["n_head"], m["n_embd"] // m["n_head"]
    flops = 2.0 * 2.0 * batch * h * (seq_len * seq_len / 2.0) * d
    nbytes = 4.0 * batch * seq_len * h * d * itemsize + batch * h * seq_len * 4.0
    return {"flops": flops, "bytes": nbytes}


def window_flops(cell, win: dict) -> float:
    m, t = cell.config["model"], cell.config["training"]["seq_len"]
    return (win["tokens"] * train_flops_per_token(m, t)
            + win["eval_tokens"] * forward_flops_per_token(m, t))
