"""Decoder-only transformer for NWP / long-context federated tasks.

The reference's only sequence models are small LSTMs
(``model/nlp/rnn.py`` — ``RNN_OriginalFedAvg``, ``RNN_StackOverFlow``);
SURVEY.md §5 marks long-context as green-field. This family is the
TPU-first successor: bf16-friendly widths, GroupNorm-free pre-LN
blocks, and a pluggable attention implementation:

- ``attention="full"``  — dense (default single-chip path)
- ``attention="flash"`` — pallas flash kernel (``ops.flash_attention``)
- ``attention="ring"`` / ``"ulysses"`` — resolved by the TRAINING STEP:
  the module calls whatever callable is passed as ``attn_fn``, so a
  pjit step can inject ``make_sequence_sharded_attention(mesh, ...)``
  and shard the sequence axis over the mesh ``sp`` axis.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def _dense_attention(q, k, v):
    from ..parallel.sequence import full_attention

    return full_attention(q, k, v, causal=True)


def _flash(q, k, v):
    from ..ops.flash_attention import flash_attention

    # a seq_len the kernel cannot tile raises (same rule on CPU and
    # chip) — attention="flash" never quietly becomes another path
    return flash_attention(q, k, v, True)


def resolve_attention(name_or_fn) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    table = {"full": _dense_attention, "flash": _flash}
    if name_or_fn not in table:
        raise ValueError(
            f"attention {name_or_fn!r}: only {sorted(table)} resolve by name; "
            "'ring'/'ulysses' are mesh-sharded — build them with "
            "parallel.sequence.make_sequence_sharded_attention(mesh, ...) "
            "and pass the callable as attn_fn"
        )
    return table[name_or_fn]


class Block(nn.Module):
    """Pre-LN block. ``ffn`` swaps the feed-forward half for another
    module (e.g. a routed ``models.moe.SwitchFFN``) without touching
    the attention path; the default inline MLP keeps the historical
    ``Dense_2``/``Dense_3`` param names the tp layout rules key on."""

    num_heads: int
    mlp_ratio: int = 4
    attn_fn: Callable = _dense_attention
    ffn: Optional[Callable[[], nn.Module]] = None  # factory, not module

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        h = nn.LayerNorm()(x)
        qkv = nn.Dense(3 * C)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (B, T, self.num_heads, C // self.num_heads)
        o = self.attn_fn(q.reshape(shape), k.reshape(shape), v.reshape(shape))
        x = x + nn.Dense(C)(o.reshape(B, T, C))
        h = nn.LayerNorm()(x)
        if self.ffn is not None:
            return x + self.ffn()(h)
        h = nn.Dense(self.mlp_ratio * C)(h)
        h = nn.gelu(h)
        return x + nn.Dense(C)(h)


class TransformerLM(nn.Module):
    """Causal LM: tokens [B, T] -> logits [B, T, vocab]."""

    vocab_size: int
    num_layers: int = 2
    num_heads: int = 4
    embed_dim: int = 128
    max_len: int = 512
    attention: str = "full"
    attn_fn: Optional[Callable] = None
    # rematerialization (jax.checkpoint): drop each block's activations
    # on the forward pass and recompute them in the backward — the
    # standard HBM-for-FLOPs trade for long sequences / deep stacks.
    # Param names are unchanged (flax's lifted remat preserves scopes),
    # so checkpoints and tp/ep layout rules apply identically.
    remat: bool = False

    def make_block(
        self, i: int, attn: Callable, ffn: Optional[Callable] = None
    ) -> nn.Module:
        """Layer ``i``'s block; subclasses override (MoETransformerLM
        swaps in routed FFNs on a stride) and pass ``ffn`` back here so
        remat wrapping and naming have one implementation. The explicit
        name matters: nn.remat(Block) would auto-name the module
        CheckpointBlock_i, breaking param-tree compatibility."""
        cls = nn.remat(Block) if self.remat else Block
        return cls(
            num_heads=self.num_heads, attn_fn=attn, ffn=ffn, name=f"Block_{i}"
        )

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        attn = self.attn_fn or resolve_attention(self.attention)
        B, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.embed_dim)(tokens.astype(jnp.int32))
        pos = nn.Embed(self.max_len, self.embed_dim)(jnp.arange(T))
        x = x + pos[None]
        for i in range(self.num_layers):
            x = self.make_block(i, attn)(x)
        x = nn.LayerNorm()(x)
        return nn.Dense(self.vocab_size)(x)
