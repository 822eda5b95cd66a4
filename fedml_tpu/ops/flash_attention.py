"""Pallas flash-attention kernel (single-chip hot op).

Blockwise attention with online softmax, tiled for the MXU: the
[T, T] score matrix never hits HBM — each (q-block, k-block) tile of
scores lives in VMEM, and the running (max, normalizer, accumulator)
state carries across k-blocks. Grid: (batch*heads, q-blocks); the
k-loop is a ``fori_loop`` inside the kernel over the head's whole K/V,
which stays resident in VMEM — that residency is what bounds the
sequence length (``max_seq_len``).

Backward: ``jax.custom_vjp`` with the standard flash residuals
(output + per-row logsumexp) and a BLOCKWISE recompute — a ``lax.scan``
over k-blocks that rebuilds one [T, bk] score panel at a time, so the
backward peak is O(T·bk) like the forward, never the dense [T, T]
matrix. Pair with ``parallel.sequence.ring_attention`` across chips:
ring for the sequence axis, this kernel for the per-chip block.

Platforms: compiled by Mosaic on ``tpu``; on ``cpu`` the SAME kernel
body runs in the Pallas interpreter (what the tests exercise). The
choice is made per lowering platform (``lax.platform_dependent``), so
lowering for any other platform raises instead of silently
interpreting. Shapes are validated identically everywhere: a shape the
TPU tiling cannot take is a ``ValueError`` on CPU too.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# TPU vector registers are (8 sublanes, 128 lanes); a BlockSpec's last
# two dims must be multiples of that tile (or span the whole array)
_LANES = 128
# The kernel keeps one head's K and V whole in VMEM, double-buffered by
# the Pallas pipeline, and asks Mosaic for that much scoped VMEM (the
# default limit is 16 MiB). The cap is what the v5e was seen to honour:
# chip_smoke.py runs the kernel at max_seq_len, i.e. at this cap, and
# checks the result (PERF.md, PR 21). Larger requests were not tried on
# hardware.
_VMEM_CAP_BYTES = 100 * 2**20
# q/o/lse blocks, the [bq, bk] score tiles and the f32 accumulator —
# small next to K/V residency; a fixed allowance keeps the bound simple
_VMEM_WORKSPACE_BYTES = 8 * 2**20


def _kv_resident_bytes(seq_len: int, head_dim: int, itemsize: int) -> int:
    """VMEM held by K and V for one head: 2 arrays x 2 pipeline buffers,
    each [T, D] with D padded to the 128-lane tile."""
    lanes = -(-head_dim // _LANES) * _LANES
    return 2 * 2 * seq_len * lanes * itemsize


def max_seq_len(head_dim: int, dtype) -> int:
    """Largest sequence length the kernel accepts for this head size and
    dtype (a multiple of 128): K/V residency plus the workspace must fit
    ``_VMEM_CAP_BYTES``. Longer sequences need a k-block grid axis or
    the cross-chip ring (``parallel.sequence``)."""
    per_row = _kv_resident_bytes(1, head_dim, jnp.dtype(dtype).itemsize)
    return (_VMEM_CAP_BYTES - _VMEM_WORKSPACE_BYTES) // per_row // _LANES * _LANES


def _check_shape(T: int, D: int, dtype, block_q: int, block_k: int) -> None:
    """One rule for every platform: blocks are multiples of the 128-lane
    tile and divide T, and the head's K/V fits VMEM."""
    for name, b in (("block_q", block_q), ("block_k", block_k)):
        if b % _LANES or b <= 0:
            raise ValueError(
                f"flash_attention: {name}={b} must be a positive multiple "
                f"of {_LANES} (the TPU lane tile)"
            )
        if T % b:
            raise ValueError(
                f"flash_attention: seq len {T} must be a multiple of "
                f"{name}={b} — the TPU tiling cannot take this shape; pad "
                "the sequence or use attention_impl='full'"
            )
    limit = max_seq_len(D, dtype)
    if T > limit:
        raise ValueError(
            f"flash_attention: seq len {T} exceeds {limit}, the largest "
            f"this kernel takes at head_dim={D} {jnp.dtype(dtype).name} "
            "(one head's K/V stays resident in VMEM); shard the sequence "
            "with parallel.sequence ring/ulysses attention"
        )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq, bk, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0]  # [bq, D], input dtype (bf16 feeds the MXU natively)
    d = q.shape[-1]
    n_kb = seq_len // bk
    # f32 operands follow the caller's matmul precision; for bf16 the
    # products are exact in the f32 accumulator already, and Mosaic
    # refuses an fp32 contract precision on bf16 operands (which
    # fedml_tpu.init's default matmul_precision="highest" would ask for)
    precision = None if q.dtype == jnp.float32 else jax.lax.Precision.DEFAULT

    def body(j, carry):
        m, l, acc = carry  # [bq, 1], [bq, 1], [bq, D] f32
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[0, pl.ds(start, bk), :]  # [bk, D]
        v = v_ref[0, pl.ds(start, bk), :]
        # q @ k^T as a transposed-rhs contraction (no in-kernel k.T)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(
            p.astype(v.dtype), v,
            precision=precision, preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    upper = n_kb if not causal else jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kb)
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # per-row logsumexp: the backward residual (flash convention),
    # stored lane-dense as a [1, bq] row
    lse_ref[0] = jnp.transpose(m + jnp.log(l))


def _flash_call(qf, kf, vf, *, scale, causal, bq, bk, interpret):
    BH, T, D = qf.shape
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, seq_len=T
    )
    need = _kv_resident_bytes(T, D, qf.dtype.itemsize) + _VMEM_WORKSPACE_BYTES
    return pl.pallas_call(
        kernel,
        grid=(BH, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, T, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, T, D), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=need,
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)


def _flash_forward(q, k, v, causal, scale, block_q, block_k):
    B, T, H, D = q.shape
    _check_shape(T, D, q.dtype, block_q, block_k)
    scale = scale or (D**-0.5)

    def reshaped(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    call = functools.partial(
        _flash_call, scale=scale, causal=causal, bq=block_q, bk=block_k
    )
    # compiled on the chip, interpreted on the CPU (tests); no default
    # branch, so lowering for any other platform raises
    out, lse = jax.lax.platform_dependent(
        reshaped(q), reshaped(k), reshaped(v),
        cpu=functools.partial(call, interpret=True),
        tpu=functools.partial(call, interpret=False),
    )
    return (
        out.reshape(B, H, T, D).transpose(0, 2, 1, 3),
        lse.reshape(B, H, T),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
):
    """Flash attention, [B, T, H, D] layout. Differentiable. ``T`` must
    be a multiple of the (128-multiple) block sizes and at most
    ``max_seq_len(D, dtype)``; anything else raises ``ValueError``."""
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _bwd(causal, scale, block_q, block_k, res, g):
    """Blockwise backward (FlashAttention-2 recompute): scan over
    k-blocks rebuilding [T, bk] score panels from the saved logsumexp —
    peak memory O(B·H·T·bk), never the dense [T, T] matrix."""
    q, k, v, o, lse = res
    B, T, H, Dh = q.shape
    sc = scale or (Dh**-0.5)
    bk = block_k
    f32 = lambda x: x.astype(jnp.float32)
    qf, kf, vf, of, gf = f32(q), f32(k), f32(v), f32(o), f32(g)
    # D_i = do_i · o_i  [B,H,T]
    d_sum = (gf * of).sum(-1).transpose(0, 2, 1)
    q_pos = jnp.arange(T)

    def body(dq_acc, j):
        ks = jax.lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)  # [B,bk,H,D]
        vs = jax.lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ks) * sc  # [B,H,T,bk]
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # [B,H,T,bk]
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vs)
        ds = p * (dp - d_sum[..., None]) * sc
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, ks)
        dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dv_j = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
        return dq_acc, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros_like(qf), jnp.arange(T // bk)
    )
    # [nkb, B, bk, H, D] -> [B, T, H, D]
    merge = lambda blocks: jnp.moveaxis(blocks, 0, 1).reshape(B, T, H, Dh)
    return dq.astype(q.dtype), merge(dks).astype(k.dtype), merge(dvs).astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)
