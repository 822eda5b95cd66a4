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
over panels of 128 keys (the lane tile, whatever blocks the forward
tiles with: ``block_q`` / ``block_k`` are the forward kernel's alone)
that rebuilds one [T, 128] score panel at a time, so the backward peak
is O(T·128) like the forward, never the dense [T, T] matrix. Its
products take their operands — the recomputed probabilities and score
gradients among them — in the input dtype and accumulate in float32,
as the forward kernel's do: float32 inputs are worked in float32
throughout; bfloat16 inputs cost the gradients about one bfloat16
rounding more than products on operands cast up to float32 would
(relative error 2.7e-3 against 1.4e-3 on a 12 x 64 head, 1,024 token
causal block, ``tests/test_moe_decoder.py``). Pair with ``parallel.sequence.ring_attention`` across chips:
ring for the sequence axis, this kernel for the per-chip block.

Grouped KV (``k``/``v`` with fewer heads than ``q``): a KV head is read
by its ``H // KV`` query heads through the block index map, never
repeated in HBM; the backward sums each KV head's gradient over its
group. ``window=w`` (causal only) keeps the keys in ``[q - w + 1, q]``:
the forward kernel's k-loop starts at the first block that holds one,
and the backward's scan slices out the ``w + 128`` query rows a panel
of keys can reach, so neither pays for the blocks outside the band.

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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, window, bq, bk, seq_len):
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
            keep = q_pos >= k_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG_INF)
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
    # the first k-block that holds a key of this q-block's band
    lower = 0 if window is None else jnp.maximum(qi * bq - (window - 1), 0) // bk
    m, l, acc = jax.lax.fori_loop(lower, upper, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # per-row logsumexp: the backward residual (flash convention),
    # stored lane-dense as a [1, bq] row
    lse_ref[0] = jnp.transpose(m + jnp.log(l))


def _flash_call(qf, kf, vf, *, scale, causal, window, bq, bk, interpret):
    BH, T, D = qf.shape
    group = BH // kf.shape[0]  # query heads per KV head (1: plain MHA)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk, seq_len=T
    )
    need = _kv_resident_bytes(T, D, qf.dtype.itemsize) + _VMEM_WORKSPACE_BYTES
    return pl.pallas_call(
        kernel,
        grid=(BH, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda i, j: (i, j, 0)),
            # row b*H + h of q reads row b*KV + h // group of k and v;
            # consecutive steps on one KV head re-fetch nothing
            pl.BlockSpec((1, T, D), lambda i, j: (i // group, 0, 0)),
            pl.BlockSpec((1, T, D), lambda i, j: (i // group, 0, 0)),
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
        # the device trace's readers tell the two kinds of layer apart
        # by this name (benchmark/layer_metrics/flash_*_fwd_roofline.py)
        name="flash_attention_fwd" if window is None else "flash_attention_window_fwd",
    )(qf, kf, vf)


def _check_heads(q, k, v, causal, window) -> int:
    """Query heads per KV head; the rules every platform shares."""
    H, KV = q.shape[2], k.shape[2]
    if k.shape != v.shape or k.shape[:2] != q.shape[:2] or k.shape[3] != q.shape[3]:
        raise ValueError(
            f"flash_attention: q {q.shape}, k {k.shape}, v {v.shape} — k and v "
            "must match and share q's batch, length and head_dim"
        )
    if KV <= 0 or H % KV:
        raise ValueError(
            f"flash_attention: {H} query heads cannot share {KV} KV heads "
            "(grouped KV needs a whole number of query heads per KV head)"
        )
    if window is not None and (not causal or window <= 0):
        raise ValueError(
            f"flash_attention: window={window} needs causal=True and a "
            "positive width (the band is [q - window + 1, q])"
        )
    return H // KV


def _flash_forward(q, k, v, causal, scale, block_q, block_k, window):
    B, T, H, D = q.shape
    _check_heads(q, k, v, causal, window)
    _check_shape(T, D, q.dtype, block_q, block_k)
    scale = scale or (D**-0.5)

    def reshaped(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], T, D)

    call = functools.partial(
        _flash_call, scale=scale, causal=causal, window=window, bq=block_q, bk=block_k
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    window: Optional[int] = None,
):
    """Flash attention, ``q`` [B, T, H, D], ``k``/``v`` [B, T, KV, D] with
    ``H`` a multiple of ``KV``. Differentiable. ``T`` must be a multiple
    of the (128-multiple) block sizes, which tile the forward kernel
    only, and at most ``max_seq_len(D, dtype)``; ``window`` needs
    ``causal``; anything else raises ``ValueError``."""
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k, window)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k, window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k, window)
    return out, (q, k, v, out, lse)


# the backward's k-panel: [B, H, rows, 128] float32 score panels, whatever
# block the forward kernel tiles with
_BWD_BLOCK_K = _LANES


def _bwd(causal, scale, _block_q, _block_k, window, res, g):
    """Blockwise backward (FlashAttention-2 recompute): scan over
    panels of ``bk`` = 128 keys rebuilding [rows, bk] score panels from
    the saved logsumexp — peak memory O(B·H·rows·bk), never the dense
    [T, T] matrix. The forward's block sizes play no part. ``rows`` is
    T, or with a window the ``window + bk`` query rows (rounded up to a
    panel) a panel of keys can reach. Operands (``p`` and ``ds`` too)
    stay in the input dtype and every product accumulates in float32,
    as the forward kernel's do (module docstring). Everything is laid out head-major
    ([B, KV, G, T, D]: the forward kernel's own order) once, outside the
    scan, so that each step's five products are plain batched matrix
    products and the dq accumulator is updated a contiguous [rows, D]
    block a head (with T minor the v5e compiler copied the whole
    accumulator every step; PERF.md, PR 28)."""
    q, k, v, o, lse = res
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    sc = scale or (Dh**-0.5)
    bk = _BWD_BLOCK_K
    dt = q.dtype
    f32 = jnp.float32
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    heads = lambda x: x.reshape(B, T, KV, G, -1).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,D]
    qh, gh = heads(q), heads(g)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,KV,T,D]
    # D_i = do_i · o_i and the saved logsumexp, [B,KV,G,T,1]
    d_sum = heads((g.astype(f32) * o.astype(f32)).sum(-1))
    lse_h = lse.reshape(B, KV, G, T, 1)
    # the query rows one k-block can reach: all of them, or the band
    rows = T if window is None else min(T, -(-(window + bk - 1) // bk) * bk)

    def body(dq_acc, j):
        k0 = j * bk
        ks = jax.lax.dynamic_slice_in_dim(kh, k0, bk, axis=2)  # [B,KV,bk,D]
        vs = jax.lax.dynamic_slice_in_dim(vh, k0, bk, axis=2)
        # keys [k0, k0+bk) are seen by queries [k0, k0+bk+window-1)
        q0 = jnp.minimum(k0, T - rows)
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, q0, rows, axis=3)
        qs, gs = cut(qh), cut(gh)
        s = dot("bhgqd,bhkd->bhgqk", qs, ks) * sc  # [B,KV,G,rows,bk]
        if causal:
            q_pos = q0 + jnp.arange(rows)[:, None]
            k_pos = k0 + jnp.arange(bk)[None, :]
            keep = q_pos >= k_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - cut(lse_h))
        dp = dot("bhgqd,bhkd->bhgqk", gs, vs)
        ds = (p * (dp - cut(d_sum)) * sc).astype(dt)
        dq_j = dot("bhgqk,bhkd->bhgqd", ds, ks)
        dq_acc = jax.lax.dynamic_update_slice_in_dim(
            dq_acc, jax.lax.dynamic_slice_in_dim(dq_acc, q0, rows, axis=3) + dq_j, q0, axis=3
        )
        dk_j = dot("bhgqk,bhgqd->bhkd", ds, qs)
        dv_j = dot("bhgqk,bhgqd->bhkd", p.astype(dt), gs)
        return dq_acc, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros((B, KV, G, T, Dh), f32), jnp.arange(T // bk)
    )
    # [nkb, B, KV, bk, D] -> [B, T, KV, D]
    merge = lambda blocks: blocks.transpose(1, 0, 3, 2, 4).reshape(B, T, KV, Dh)
    return (
        dq.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh).astype(q.dtype),
        merge(dks).astype(k.dtype),
        merge(dvs).astype(v.dtype),
    )


flash_attention.defvjp(_fwd, _bwd)
