"""Sequence / context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context subsystem (SURVEY.md §2.9 census /
§5: its only sequence models are small LSTMs) — this is the green-field
TPU-first design the build plan calls for. Two strategies over a mesh
``sp`` axis, both usable under ``shard_map`` with the sequence dimension
sharded:

- **Ring attention**: queries stay put; K/V shards rotate around the
  ring via ``jax.lax.ppermute`` (XLA lowers it to ICI neighbor
  exchanges) while a streaming/online softmax (flash-attention
  numerics: running max ``m``, normalizer ``l``, accumulator ``o``)
  folds in each block. Peak memory per chip is O(T/n · T/n) for scores
  — full-sequence attention never materializes. Differentiable as-is
  (``ppermute`` has a transpose rule; the scan is re-traced by autodiff).

- **Ulysses (all-to-all)**: ``lax.all_to_all`` re-shards [T/n, H] ->
  [T, H/n], runs ordinary full attention per head group, and re-shards
  back. One collective pair per layer; attention math stays dense —
  the right trade when heads >= n and T/n is small.

Both return results identical (up to fp error) to full attention on the
gathered sequence, verified in tests on the 8-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def full_attention(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Reference dense attention (the oracle). [B, T, H, D] layout."""
    scale = scale or (q.shape[-1] ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    block_k: Optional[int] = None,
):
    """Blockwise ring attention under ``shard_map``.

    Per-shard shapes [B, T/n, H, D] with the sequence sharded
    contiguously along ``axis_name`` (shard i holds positions
    [i*T/n, (i+1)*T/n)). K/V blocks travel the ring; the online softmax
    accumulates exactly the full-attention result.

    ``block_k`` chunks each hop's K/V shard for the score computation:
    the per-chip panel shrinks from [B, H, Tq, Tk] to [B, H, Tq, bk]
    (the same online-softmax fold, just more steps — bitwise-identical
    math in f32), so per-chip attention memory is O(Tq x bk) no matter
    how long the resident shard is. Default (None) folds the whole
    shard per hop. Pure ``lax.scan``, so autodiff needs no custom
    backward.
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale or (D**-0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    bk = int(block_k) if block_k else Tk
    if bk <= 0 or Tk % bk:
        raise ValueError(
            f"ring block_k={bk} must be a positive divisor of the K/V "
            f"shard length {Tk}"
        )
    n_chunks = Tk // bk

    q_pos = my_idx * Tq + jnp.arange(Tq)  # global query positions

    def fold(acc, k_chunk, v_chunk, k_pos):
        """Fold one [bk] K/V chunk into the online-softmax state.
        Scores and the state accumulate in f32 even for bf16 inputs —
        l sums T terms and bf16's 8 mantissa bits drift."""
        o, m, l = acc
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_chunk, preferred_element_type=jnp.float32
        ) * scale  # [B,H,Tq,bk] f32
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        s_max = s.max(axis=-1)  # [B,H,Tq]
        m_new = jnp.maximum(m, s_max)
        # renormalize the running state to the new max
        correction = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])  # [B,H,Tq,bk]
        l_new = l * correction + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_chunk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
        return o_new, m_new, l_new

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        src = (my_idx - i) % n  # owner of the block we currently hold
        base = src * Tk

        if n_chunks == 1:
            o, m, l = fold((o, m, l), k_cur, v_cur, base + jnp.arange(Tk))
        else:

            def inner(acc, j):
                kc = lax.dynamic_slice_in_dim(k_cur, j * bk, bk, axis=1)
                vc = lax.dynamic_slice_in_dim(v_cur, j * bk, bk, axis=1)
                return fold(acc, kc, vc, base + j * bk + jnp.arange(bk)), None

            (o, m, l), _ = lax.scan(inner, (o, m, l), jnp.arange(n_chunks))
        # rotate KV one hop around the ring
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    l_t = l.transpose(0, 2, 1)[..., None]  # [B,Tq,H,1]
    return (o / jnp.maximum(l_t, 1e-30)).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism under
    ``shard_map``: re-shard sequence->heads, per-chip attention on the
    full sequence for a head group through the pallas flash kernel
    (without it the gathered [T, T] scores are exactly the memory wall
    sequence parallelism exists to avoid), re-shard back. Requires
    ``H % n == 0`` and a gathered ``T`` the kernel tiles (a multiple of
    128 — it raises otherwise, on every platform; ring attention takes
    any length). Per-shard input [B, T/n, H, D]."""
    n = lax.psum(1, axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the sp axis "
            f"size ({n}); use ring attention otherwise"
        )

    def a2a(x, split_head: bool):
        # [B, T/n, H, D] -> [B, T, H/n, D]  (split_head) or inverse
        if split_head:
            return lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    from ..ops.flash_attention import flash_attention

    qg, kg, vg = a2a(q, True), a2a(k, True), a2a(v, True)
    og = flash_attention(qg, kg, vg, causal, scale)
    return a2a(og, False)


def make_sequence_sharded_attention(
    mesh, strategy: str = "ring", causal: bool = True, axis_name: str = "sp",
    batch_axis: str = None, ring_block_k: Optional[int] = None,
):
    """Wrap a strategy as a [B, T, H, D] -> [B, T, H, D] function whose
    sequence axis is sharded over ``mesh[axis_name]`` via shard_map —
    drop-in for dense attention inside a pjit'ed training step.

    ``batch_axis`` composes data parallelism: the batch axis is sharded
    over that mesh axis (each dp replica runs its own ring/all-to-all
    over the sp axis; without it, a multi-axis mesh would gather the
    dp-sharded batch at the shard_map boundary)."""
    from jax.sharding import PartitionSpec as P

    strategies = {"ring": ring_attention, "ulysses": ulysses_attention}
    if strategy not in strategies:
        raise ValueError(
            f"sp_strategy {strategy!r}: pick one of {sorted(strategies)}"
        )
    fn = strategies[strategy]
    inner = functools.partial(fn, axis_name=axis_name, causal=causal)
    if ring_block_k:
        if strategy != "ring":
            # refuse loudly: the user tuned a memory cap that this
            # strategy would silently not honor
            raise ValueError(
                f"sp_ring_block={ring_block_k} only applies to "
                f"sp_strategy 'ring', not {strategy!r}"
            )
        inner = functools.partial(inner, block_k=ring_block_k)
    spec = P(batch_axis, axis_name, None, None)

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
