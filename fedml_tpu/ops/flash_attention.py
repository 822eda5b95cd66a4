"""Pallas flash-attention kernel (single-chip hot op).

Blockwise attention with online softmax, tiled for the MXU: the
[T, T] score matrix never hits HBM — each (q-block, k-block) tile of
scores lives in VMEM, and the running (max, normalizer, accumulator)
state carries across k-blocks. Grid: (batch*heads, q-blocks); the
k-loop is a ``fori_loop`` inside the kernel over the head's whole K/V,
which stays resident in VMEM — that residency is what bounds the
sequence length (``max_seq_len``).

Backward: ``jax.custom_vjp`` with the standard flash residuals
(output + per-row logsumexp) and FlashAttention-2's recompute as two
more Pallas kernels (``_bwd_call``). After ``delta = rowsum(dO * O)``
(one XLA fusion, float32, lane-dense like the logsumexp), the dK/dV
kernel runs one program a (batch x KV head, k-block): K and V blocks
stay in VMEM while its inner grid axes walk the group's query heads and
the q-blocks that see this k-block, rebuilding each score tile from the
logsumexp and accumulating in float32 scratch, written once. The dQ
kernel runs one program a (batch x query head, q-block) over the
k-blocks the forward kernel's loop visits. Score tiles live in VMEM
only, square, their size derived from the shape (``_bwd_tile_size``;
``block_q`` / ``block_k`` are the forward kernel's alone), and every
operand is streamed a block at a time, so the backward holds no
sequence-long array in VMEM and never bounds ``max_seq_len``. The
products take their operands -- the recomputed probabilities and score
gradients among them -- in the input dtype and accumulate in float32,
as the forward kernel's do: float32 inputs are worked in float32
throughout; bfloat16 inputs cost the gradients about one bfloat16
rounding more than products on operands cast up to float32 would
(relative error 2.7e-3 against 1.4e-3 on a 12 x 64 head, 1,024 token
causal block, ``tests/test_moe_decoder.py``). Pair with
``parallel.sequence.ring_attention`` across chips: ring for the
sequence axis, this kernel for the per-chip block.

Grouped KV (``k``/``v`` with fewer heads than ``q``): a KV head is read
by its ``H // KV`` query heads through the block index map, never
repeated in HBM; the dK/dV kernel sums each KV head's gradient over its
group in VMEM. ``window=w`` (causal only) keeps the keys in
``[q - w + 1, q]``: the forward kernel's k-loop starts at the first
block that holds one, and the backward kernels' inner grid axes are
only as long as the band is wide, so neither pays for the blocks
outside the band -- one algorithm for windowed and full layers, the
band entering through the loop bounds.

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
# the backward kernels stream every operand a block at a time, so their
# need is fixed whatever the sequence length (it never bounds
# max_seq_len): four [1024, 1024] float32 score tiles and their
# input-dtype copies at the widest tile, with room
_BWD_VMEM_BYTES = 64 * 2**20


def _kv_resident_bytes(seq_len: int, head_dim: int, itemsize: int) -> int:
    """VMEM held by K and V for one head: 2 arrays x 2 pipeline buffers,
    each [T, D] with D padded to the 128-lane tile."""
    lanes = -(-head_dim // _LANES) * _LANES
    return 2 * 2 * seq_len * lanes * itemsize


def max_seq_len(head_dim: int, dtype) -> int:
    """Largest sequence length the kernel accepts for this head size and
    dtype (a multiple of 128): the forward kernel's K/V residency plus
    the workspace must fit ``_VMEM_CAP_BYTES`` (the backward kernels
    stream their operands and set no bound). Longer sequences need a
    k-block grid axis or the cross-chip ring (``parallel.sequence``)."""
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


def _head_major(x):
    """[B, T, heads, D] -> the kernels' layout, [B * heads, T, D]."""
    B, T, heads, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * heads, T, D)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, window):
    B, T, H, D = q.shape
    _check_heads(q, k, v, causal, window)
    _check_shape(T, D, q.dtype, block_q, block_k)
    scale = scale or (D**-0.5)
    call = functools.partial(
        _flash_call, scale=scale, causal=causal, window=window, bq=block_q, bk=block_k
    )
    # compiled on the chip, interpreted on the CPU (tests); no default
    # branch, so lowering for any other platform raises
    out, lse = jax.lax.platform_dependent(
        _head_major(q), _head_major(k), _head_major(v),
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
    ``H`` a multiple of ``KV``. Differentiable (the backward is two
    Pallas kernels of its own, tiled from the shape). ``T`` must be a
    multiple of the (128-multiple) block sizes, which tile the forward
    kernel only, and at most ``max_seq_len(D, dtype)``; ``window`` needs
    ``causal``; anything else raises ``ValueError``."""
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k, window)
    return out


def _fwd(q, k, v, causal, scale, block_q, block_k, window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k, window)
    return out, (q, k, v, out, lse)


def _bwd_tile_size(seq_len: int, window: Optional[int]) -> int:
    """The backward kernels' square tile, from the shape alone: the
    largest of 1,024 / 512 / 256 that divides the sequence and is at
    most half the band (the window, or the whole sequence), else 128. A
    tile wider than that spends most of itself outside the band; on the
    v5e at T = 4,096, D = 128: 512 under a window of 1,024, 1,024 with
    none (PERF.md, PR 29)."""
    band = seq_len if window is None else min(window, seq_len)
    return next((b for b in (1024, 512, 256) if seq_len % b == 0 and 2 * b <= band), _LANES)


def _dot(a, b, contract):
    """``a`` x ``b`` contracting dim ``contract[0]`` of ``a`` with
    ``contract[1]`` of ``b``, accumulated in float32, under the forward
    kernel's precision rule (the caller's for float32 operands,
    ``DEFAULT`` pinned for bfloat16)."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


def _bwd_tile(q, k, v, do, lse, delta, q0, k0, *, scale, causal, window):
    """One (k-block, q-block) tile of the backward, TRANSPOSED: keys on
    the sublanes and queries on the lanes, so that the per-query rows
    ``lse`` / ``delta`` ([1, bq], lane-dense as the forward wrote them)
    broadcast down the sublanes and no product needs a transposed tile.
    ``q0`` / ``k0`` are the tile's first query and key. Returns ``p``
    and ``ds / scale`` as [bk, bq] float32 (the caller scales its
    float32 accumulator once instead of every tile). Every tile is
    masked: a branch that spared the band's inner tiles the mask cost
    the v5e half as much again (PERF.md, PR 29)."""
    s = _dot(k, q, (1, 1)) * scale
    if causal:
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        s = jnp.where(keep, s, _NEG_INF)
    p = jnp.exp(s - lse)
    return p, p * (_dot(v, do, (1, 1)) - delta)


def _q_blocks_of(j, *, causal, window, b, n):
    """[first, last) of the ``n`` q-blocks that hold a query seeing
    k-block ``j`` (square tiles of ``b``): from the diagonal block down
    (causal) and no further than the last key's ``window - 1``
    successors."""
    first = j if causal else 0
    last = n if window is None else jnp.minimum((j * b + b + window - 2) // b + 1, n)
    return first, last


def _k_blocks_of(i, *, causal, window, b, n):
    """[lower, upper) of the ``n`` k-blocks that q-block ``i`` sees: the
    forward kernel's loop bounds, at square tiles of ``b``."""
    upper = i + 1 if causal else n
    lower = 0 if window is None else jnp.maximum(i * b - (window - 1), 0) // b
    return lower, upper


def _span(blocks_of, n) -> int:
    """The most blocks any of ``n`` programs walks: the extent of the
    inner grid axis (a Python count at trace time)."""
    with jax.ensure_compile_time_eval():
        return max(int(hi) - int(lo) for lo, hi in map(blocks_of, range(n)))


def _dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale, causal, window, b, q_blocks,
):
    j, g, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first, last = q_blocks(j)

    @pl.when((g == 0) & (t == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(first + t < last)
    def _():
        q, do = q_ref[0], do_ref[0]
        p, ds = _bwd_tile(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0], (first + t) * b, j * b,
            scale=scale, causal=causal, window=window,
        )
        dv_acc[...] += _dot(p.astype(do.dtype), do, (1, 0))
        dk_acc[...] += _dot(ds.astype(q.dtype), q, (1, 0))

    @pl.when((g == pl.num_programs(2) - 1) & (t == pl.num_programs(3) - 1))
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, dq_acc,
    *, scale, causal, window, b, k_blocks,
):
    i, t = pl.program_id(1), pl.program_id(2)
    lower, upper = k_blocks(i)

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(lower + t < upper)
    def _():
        k = k_ref[0]
        _, ds = _bwd_tile(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0], delta_ref[0], i * b, (lower + t) * b,
            scale=scale, causal=causal, window=window,
        )
        # ds is [keys, queries]: contract the keys, dim 0 of both
        dq_acc[...] += _dot(ds.astype(k.dtype), k, (0, 0))

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_call(qf, dof, lse, delta, kf, vf, *, scale, causal, window, interpret):
    """The two backward kernels on head-major operands ([B * heads, T, D];
    ``lse`` / ``delta`` [B * H, 1, T]); returns dq, dk, dv alike.

    dK/dV: one program a (batch x KV head, k-block). Its K and V blocks
    stay in VMEM while the two inner grid axes walk the group's query
    heads and, for each, the q-blocks that see this k-block; the float32
    accumulators are written once, summed over the group. dQ: one
    program a (batch x query head, q-block); the inner axis walks the
    k-blocks between the forward kernel's bounds. The band enters
    through those bounds alone. An inner step past a program's last
    block keeps that block's index, so the pipeline fetches nothing new,
    and the kernel skips it."""
    BH, T, D = qf.shape
    BKV = kf.shape[0]
    G = BH // BKV
    b = _bwd_tile_size(T, window)
    n = T // b
    geometry = dict(causal=causal, window=window, b=b)
    q_blocks = functools.partial(_q_blocks_of, n=n, **geometry)
    k_blocks = functools.partial(_k_blocks_of, n=n, **geometry)

    def call(kernel, part, semantics, **kw):
        return pl.pallas_call(
            functools.partial(kernel, scale=scale, **geometry),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=_BWD_VMEM_BYTES
            ),
            interpret=interpret,
            # no backward kernel's name may contain a forward kernel's:
            # the trace's readers find kernels by substring
            name=("flash_attention_bwd_" if window is None else "flash_attention_window_bwd_") + part,
            **kw,
        )(qf, dof, lse, delta, kf, vf)

    def q_of(i, j, g, t):
        first, last = q_blocks(j)
        return i * G + g, jnp.minimum(first + t, last - 1)

    q_block = pl.BlockSpec((1, b, D), lambda *a: (*q_of(*a), 0))
    q_row = pl.BlockSpec((1, 1, b), lambda *a: (q_of(*a)[0], 0, q_of(*a)[1]))
    kv_block = pl.BlockSpec((1, b, D), lambda i, j, g, t: (i, j, 0))
    dk, dv = call(
        functools.partial(_dkv_kernel, q_blocks=q_blocks), "dkv",
        ("parallel", "parallel", "arbitrary", "arbitrary"),
        grid=(BKV, n, G, _span(q_blocks, n)),
        in_specs=[q_block, q_block, q_row, q_row, kv_block, kv_block],
        out_specs=[kv_block, kv_block],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, kf.dtype), jax.ShapeDtypeStruct(vf.shape, vf.dtype)],
        scratch_shapes=[pltpu.VMEM((b, D), jnp.float32)] * 2,
    )

    def k_of(i, j, t):
        lower, upper = k_blocks(j)
        return i // G, jnp.minimum(lower + t, upper - 1), 0

    q_block = pl.BlockSpec((1, b, D), lambda i, j, t: (i, j, 0))
    q_row = pl.BlockSpec((1, 1, b), lambda i, j, t: (i, 0, j))
    kv_block = pl.BlockSpec((1, b, D), k_of)
    dq = call(
        functools.partial(_dq_kernel, k_blocks=k_blocks), "dq",
        ("parallel", "parallel", "arbitrary"),
        grid=(BH, n, _span(k_blocks, n)),
        in_specs=[q_block, q_block, q_row, q_row, kv_block, kv_block],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((b, D), jnp.float32)],
    )
    return dq, dk, dv


def _bwd(causal, scale, _block_q, _block_k, window, res, g):
    """FlashAttention-2's backward as two Mosaic kernels that rebuild
    the score tiles from the saved logsumexp in VMEM (module docstring).
    The forward's block sizes play no part: the tiles come from the
    shape (``_bwd_tile_size``)."""
    q, k, v, o, lse = res
    B, T, H, D = q.shape

    def token_major(x):  # [B * heads, T, D] -> [B, T, heads, D]
        return x.reshape(B, -1, T, D).transpose(0, 2, 1, 3)

    # D_i = do_i . o_i, lane-dense [B * H, 1, T] as the forward wrote lse
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = delta.transpose(0, 2, 1).reshape(B * H, 1, T)
    call = functools.partial(_bwd_call, scale=scale or (D**-0.5), causal=causal, window=window)
    dq, dk, dv = jax.lax.platform_dependent(
        _head_major(q), _head_major(g), lse.reshape(B * H, 1, T), delta, _head_major(k), _head_major(v),
        cpu=functools.partial(call, interpret=True),
        tpu=functools.partial(call, interpret=False),
    )
    return token_major(dq), token_major(dk), token_major(dv)


flash_attention.defvjp(_fwd, _bwd)
