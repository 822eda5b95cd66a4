"""A config-driven decoder LM: the block today's open models share.

``models/transformer.py`` is a GPT-2 block and cannot express this one:
RMSNorm, separate q/k/v/o projections whose ``head_dim`` is not
``hidden / heads``, grouped KV heads, q and k RMS-normalised per head
(``qk_norm``), a rotary embedding by the layer's *type*
(``sliding_attention``: a causal window, the default table;
``full_attention``: every key before, maybe YaRN; a type with no entry
in ``rope_parameters`` is not rotated) and routed experts for the MLP.
A layer is an *operator* (attention, or the gated short convolution,
``conv``) then a feed-forward (the experts; a dense gated-SiLU MLP on
the first ``num_dense_layers`` layers) -- or, with ``sublayers``, ONE
sublayer ``x + mixer(norm(x))``: attention, the experts (``moe``) or a
state-space mixer (``mamba``). The head may be tied to the embedding.
All sizes are arguments; nothing is a model's name.

**The gated short convolution** (``conv``): ``B, C, u =
split3(in_proj(h))``; ``z_t = sum_j w_j * (B * u)_{t - (L - 1) + j}``
over ``L = conv_L_cache`` taps, one filter a channel, causal (zeros
before the sequence), no activation; ``out_proj(C * z)``; no state.

**The state-space mixer** (``mamba``; Mamba-2): ``z, xBC, dt =
split(in_proj(u))``; ``xBC = silu(conv(xBC) + bias)``, causal and
depthwise; ``x, B, C = split(xBC)``, ``x`` as heads, ``B`` / ``C`` as
groups of ``state_size`` (head ``h`` reads group ``h // (H / G)``);
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head, float32.
Per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
C_t + D x_t``: ``ops/ssd.py`` runs it in chunks (masked products in a
chunk, a carried float32 state between chunks). Then ``RMSNorm(y *
silu(z)) * w`` over each group's channels, and ``out_proj``.

**The router** scores all experts in float32 with ``softmax`` or
``sigmoid``. With ``use_expert_bias`` a float32 leaf ``expert_bias`` is
added to the scores *for the choice only*: the weights are the chosen
experts' unbiased scores, so the bias's gradient is exactly zero. The
top-k weights are divided by their sum plus ``norm_topk_eps``
(``norm_topk_prob``) and multiplied by ``routed_scaling_factor``. An
expert is a gated-SiLU MLP (three stacks) or ``down(relu(up x) ** 2)``
(``relu2``, two); ``shared_dim`` adds a shared expert of that form on
every token, whole on every chip (a share's part counts it once).

**The expert layer holds a share.** ``experts_held = (first, count)``
(``parallel/expert.py``) says which of the ``num_experts`` experts live
on this chip. The router keeps its published width; the layer keeps
the token-choices that landed on held experts, sorts them by expert,
runs one grouped product over the held stacks (``ops/grouped_matmul``)
and adds each row, times its routing weight, back onto its token. What
the absent experts would add is left out and the partial sum goes on:
no code stands in for absent chips. Nothing is dropped at any
imbalance: the sorted list has room for every choice a token can place
here, and is worked through in chunks of ``chunk_loads`` even loads'
rows (1.25 by default), as many as the choices that did land fill.

The round engine vmaps local training over the cohort's lanes, and the
chip's grouped kernels take no batch dimension, so the grouped part is
a ``custom_vjp`` whose forward and backward each run one lane after
another (``jax.custom_batching.sequential_vmap``; batched over two lanes
the round executable needs 15.8 GB, lane after lane 13.5: PR 28).
The loop's operations keep the scopes around them, vmapped or not
(``fed.local_train`` in a round, ``fed.eval`` in an evaluation); where
the cohort's lanes run one after another (``build_round_fn``'s
``ragged``) nothing is vmapped and the loop runs the lane's rows.

Scopes (op metadata, every name in ``core/scopes.py``; device traces'
readers in ``benchmark/layer_metrics``): ``lm.embed``, ``blk.*``
(attention, ``conv``, the dense MLP, ``ffn_norm``, a ``mamba`` sublayer
and its scan), ``moe.route`` / ``experts`` / ``combine`` inside the
container ``moe.loop`` (the loops' own moves), ``moe.grad_sum``,
``moe.chunk`` (the chunk loop's bookkeeping), ``moe.out``,
``moe.shared``, and ``lm.head_loss`` (head, loss: ``core/losses.py``).
Counters (``counters``, summed over layers; ``FedModel.apply_counted``):
``moe_local_hits``, ``moe_expert_tokens_max``, ``moe_expert_tokens_mean``,
``moe_dropped``, ``moe_kernel_rows`` (the rows the Pallas kernels ran),
``moe_bias_moved`` on a layer with a selection bias (the choices it
changed against the unbiased top-k) and ``ssm_chunks`` / ``ssm_kernel_chunks``
on a ``mamba`` sublayer (``Mamba2Mixer``); a layer sows only its own.

**The gated attention output** (``output_gate``): ``o_proj(o *
sigmoid(gate_proj(a)))``, ``a`` the normed input the q/k/v projections
read, ``gate_proj`` one output a query head's channel (scope
``blk.attn.gate``, inside the layer's attention scope).

**The sandwich norms** (``post_norms``; not beside a convolution): the
attention's and the feed-forward's outputs RMS-normalised before the
residual takes them, ``x + RMSNorm(op(RMSNorm(x)))`` (``attn_post_norm``,
``ffn_post_norm``, dense or routed; scope ``blk.post_norm``, inside the
sublayer's own scope where it has one).

**The embedding multiplier** (``embedding_multiplier``; muP's
``sqrt(hidden)``): the looked-up rows times a constant before the first
layer (inside ``lm.embed``); the head is not scaled.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.core import freeze
from jax.custom_batching import sequential_vmap

from ..ops.grouped_matmul import kernel_rows, mlp_backward, mlp_forward

SLIDING, FULL, CONV = "sliding_attention", "full_attention", "conv"
_NEG_INF = -1e30


# -- rotary embedding ---------------------------------------------------

def rope_inv_freq(head_dim: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """``(inv_freq [head_dim / 2], scale)`` of one layer type's rotary
    parameters: ``rope_type`` ``default`` (``theta ** (-2i / d)``, scale
    1) or ``yarn`` (Peng et al. 2023, arXiv:2309.00071: the
    interpolated and the extrapolated frequencies blended by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context; cos and sin scaled by
    ``attention_factor``, by default ``0.1 ln(factor) + 1``)."""
    theta = float(rope["rope_theta"])
    half = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    pos_freqs = theta ** half
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: only 'default' and 'yarn' are built")
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rope.get("beta_slow", 1)))), head_dim - 1)
    if low == high:
        high += 0.001  # the ramp's width may not be 0
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv_freq = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv_freq.astype(np.float32), float(scale)


def rope_tables(seq_len: int, head_dim: int, rope: Dict[str, Any]):
    """``(cos, sin)`` [T, head_dim] in float32, the two halves alike
    (the rotate-half convention)."""
    inv_freq, scale = rope_inv_freq(head_dim, rope)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


# the TPU's vector lanes: XLA keeps a head a multiple of this wide in
# the lanes, and lays a narrower one out with the sequence there
_LANES = 128


def apply_rope(x, cos, sin):
    """``x`` [B, T, heads, D] rotated: ``x * cos + rotate_half(x) * sin``
    in float32, returned in its dtype. A bfloat16 operand whose heads
    fill whole lane rows (``D % 128 == 0``) takes its rotate-half as a
    product with a signed permutation (``_rope_bf16``): exact, and it
    writes no float32 copy of ``x``. Any other operand is upcast and its
    halves concatenated: a float32 one would be rounded by a one-pass
    product, and a narrower head's product lowers to a windowed
    convolution over the sequence-minor layout, slower on the chip than
    the copies it spares."""
    if x.dtype == jnp.bfloat16 and x.shape[-1] % _LANES == 0:
        return _rope_bf16(x, cos, sin)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None] + rotated * sin[None, :, None]).astype(x.dtype)


def _rotate_half(x, transpose: bool = False):
    """``rotate_half(x) = [-x2, x1]`` (or its transpose ``[x2, -x1]``)
    in float32, as one product of the bfloat16 ``x`` with a ±1 / 0
    matrix: one non-zero term an output, so exact in a single pass."""
    d = x.shape[-1]
    p = np.zeros((d, d), np.float32)  # rotate_half(x) = x @ p
    p[d // 2:, :d // 2] = -np.eye(d // 2)
    p[:d // 2, d // 2:] = np.eye(d // 2)
    return jax.lax.dot_general(
        x, jnp.asarray(p.T if transpose else p, x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


@jax.custom_vjp
def _rope_bf16(x, cos, sin):
    """``apply_rope``'s product form, with its own backward: autodiff of
    it would round the rotated term's cotangent to bfloat16 (the
    product's operand) before the sum."""
    return (x.astype(jnp.float32) * cos[None, :, None]
            + _rotate_half(x) * sin[None, :, None]).astype(x.dtype)


def _rope_bf16_fwd(x, cos, sin):
    return _rope_bf16(x, cos, sin), (cos, sin)


def _rope_bf16_bwd(res, g):
    """``dx = g cos + rotate_halfᵀ(g sin)``, summed in float32 and rounded
    once, as autodiff of the concatenated form does; ``sin``'s halves,
    swapped, carry each term to its column. The tables take no gradient."""
    cos, sin = res
    half = sin.shape[-1] // 2
    sin_swapped = jnp.concatenate([sin[:, half:], sin[:, :half]], axis=-1)
    dx = g.astype(jnp.float32) * cos[None, :, None] + _rotate_half(g, True) * sin_swapped[None, :, None]
    return dx.astype(g.dtype), None, None


_rope_bf16.defvjp(_rope_bf16_fwd, _rope_bf16_bwd)


# -- attention ------------------------------------------------------------

def dense_attention(q, k, v, window: Optional[int]):
    """Dense masked grouped-KV attention, [T, T] scores in float32: the
    small-size path (``attention="full"``)."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, T, KV, H // KV, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32) * D**-0.5
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    keep = i >= j
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, T, H, D)


def _flash_block(seq_len: int) -> int:
    """The forward kernel's tile: the largest of 512 / 256 / 128 that
    divides the sequence (a wider tile feeds the MXU longer products;
    a sequence the kernel cannot tile raises there)."""
    return next((b for b in (512, 256) if seq_len % b == 0), 128)


def attend(q, k, v, window: Optional[int], impl: str):
    if impl == "full":
        return dense_attention(q, k, v, window)
    if impl != "flash":
        raise ValueError(f"attention {impl!r}: the decoder block builds 'full' and 'flash'")
    from ..ops.flash_attention import flash_attention

    b = _flash_block(q.shape[1])
    # a shape the kernel cannot tile raises (same rule on CPU and chip):
    # attention="flash" never quietly becomes another path
    return flash_attention(q, k, v, True, None, b, b, window)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _proj(features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, name=name)


# the sublayer kinds beside attention (``MoEDecoderLM.sublayers``); named
# down here because the flash kernels' lowered text holds the line on
# which ``attend`` calls them
SSM, EXPERTS = "mamba", "moe"


class Attention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]  # None: a full (causal) layer
    impl: str
    eps: float
    qk_norm: bool = True
    output_gate: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        B, T, _ = x.shape
        H, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = _proj(H * D, "q_proj")(x).reshape(B, T, H, D)
        k = _proj(KV * D, "k_proj")(x).reshape(B, T, KV, D)
        v = _proj(KV * D, "v_proj")(x).reshape(B, T, KV, D)

        def prepared(h, norm_name):
            """RMS-normalised per head (``qk_norm``), then rotated
            (a layer type that has rotary parameters)."""
            if self.qk_norm:
                h = RMSNorm(self.eps, name=norm_name)(h)
            return h if cos is None else apply_rope(h, cos, sin)

        q, k = prepared(q, "q_norm"), prepared(k, "k_norm")
        o = attend(q, k, v, self.window, self.impl).reshape(B, T, H * D)
        if self.output_gate:
            with jax.named_scope("blk.attn.gate"):
                o = o * jax.nn.sigmoid(_proj(H * D, "gate_proj")(x))
        return _proj(x.shape[-1], "o_proj")(o)


class GatedShortConv(nn.Module):
    """The operator of a ``conv`` layer (module docstring), [B, T, C] ->
    [B, T, C]: ``conv_kernel`` [taps, C] holds one causal filter a
    channel, tap ``taps - 1`` on the token itself."""

    taps: int

    @nn.compact
    def __call__(self, x):
        T, C = x.shape[1:]
        gate_in, gate_out, u = jnp.split(_proj(3 * C, "in_proj")(x), 3, axis=-1)
        w = self.param("conv_kernel", nn.initializers.lecun_normal(), (self.taps, C)).astype(x.dtype)
        # zeros before the sequence; tap j reads token t - (taps - 1) + j
        padded = jnp.pad(gate_in * u, ((0, 0), (self.taps - 1, 0), (0, 0)))
        z = sum(w[j] * padded[:, j:j + T] for j in range(self.taps))
        return _proj(C, "out_proj")(gate_out * z)


class GatedMLP(nn.Module):
    """The dense feed-forward of a leading layer: ``down(silu(gate x) *
    up x)``."""

    width: int

    @nn.compact
    def __call__(self, x):
        h = jax.nn.silu(_proj(self.width, "gate_proj")(x)) * _proj(self.width, "up_proj")(x)
        return _proj(x.shape[-1], "down_proj")(h)


class SquaredReluMLP(nn.Module):
    """``down(relu(up x) ** 2)``: two matrices, no gate."""

    width: int

    @nn.compact
    def __call__(self, x):
        h = jnp.square(jax.nn.relu(_proj(self.width, "up_proj")(x)))
        return _proj(x.shape[-1], "down_proj")(h)


# what ``dt_bias`` is drawn from where the program draws its own weights
# (Mamba-2's defaults): softplus(dt_bias) log-uniform in [min, max], floored
_DT_BIAS_DRAW = (1e-3, 1e-1, 1e-4)


class Mamba2Mixer(nn.Module):
    """The mixer of a ``mamba`` sublayer (module docstring), [B, T, C]
    -> [B, T, C]. ``conv_kernel`` [taps, channels] holds one causal
    filter a channel of ``x | B | C``, tap ``taps - 1`` on the token
    itself; ``A_log``, ``D``, ``dt_bias`` one scalar a head."""

    num_heads: int
    head_dim: int
    groups: int
    state_size: int
    conv_taps: int
    chunk_size: int
    eps: float

    @nn.compact
    def __call__(self, u):
        from ..ops.ssd import chunk_counts, ssd_scan

        B, T, _ = u.shape
        H, P, G, N = self.num_heads, self.head_dim, self.groups, self.state_size
        inner, bc = H * P, G * N
        if inner % G:
            raise ValueError(f"{inner} inner channels do not split into {G} norm groups")
        z, xbc, dt = jnp.split(_proj(2 * inner + 2 * bc + H, "in_proj")(u), [inner, 2 * inner + 2 * bc], axis=-1)
        w = self.param("conv_kernel", nn.initializers.lecun_normal(), (self.conv_taps, inner + 2 * bc))
        bias = self.param("conv_bias", nn.initializers.zeros, (inner + 2 * bc,))
        # zeros before the sequence; tap j reads token t - (taps - 1) + j
        padded = jnp.pad(xbc, ((0, 0), (self.conv_taps - 1, 0), (0, 0)))
        w = w.astype(u.dtype)
        xbc = jax.nn.silu(sum(w[j] * padded[:, j:j + T] for j in range(self.conv_taps)) + bias.astype(u.dtype))
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)

        def dt_bias_init(key, shape):
            lo, hi, floor = _DT_BIAS_DRAW
            dt0 = jnp.exp(jax.random.uniform(key, shape, minval=math.log(lo), maxval=math.log(hi)))
            dt0 = jnp.maximum(dt0, floor)
            return dt0 + jnp.log(-jnp.expm1(-dt0))  # softplus of it is dt0

        def a_log_init(key, shape):
            return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))

        a_log = self.param("A_log", a_log_init, (H,))
        d_skip = self.param("D", nn.initializers.ones, (H,))
        dt_bias = self.param("dt_bias", dt_bias_init, (H,))
        f32 = jnp.float32
        with jax.named_scope("blk.ssm.scan"):
            # the step, the decay and the skip in float32 whatever the compute type
            step = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            y = ssd_scan(
                x.reshape(B, T, H, P), step, -jnp.exp(a_log.astype(f32)), b.reshape(B, T, G, N),
                c.reshape(B, T, G, N), d_skip.astype(f32), self.chunk_size)
        for name, chunks in chunk_counts(T, H, P, G, N, self.chunk_size).items():
            self.sow("counters", name, f32(B * chunks), reduce_fn=jnp.add, init_fn=lambda: f32(0))
        # gated RMSNorm: the gate first, the statistics over each group's channels
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        gated = (y.reshape(B, T, inner).astype(f32) * jax.nn.silu(z.astype(f32))).reshape(B, T, G, inner // G)
        gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + self.eps)
        y = (gated.reshape(B, T, inner) * scale.astype(f32)).astype(u.dtype)
        return _proj(u.shape[-1], "out_proj")(y)


# -- the expert layer -----------------------------------------------------

def expert_chunk_rows(tokens: int, k: int, held: int, num_experts: int, loads: float = 1.25) -> int:
    """The chunk ``held_experts`` works the sorted list through in:
    ``loads`` even loads' rows (an even load's and a quarter by default),
    at most the list's room; in whole numbers, from ``loads``' exact value."""
    num, den = float(loads).as_integer_ratio()
    return min(tokens * min(k, held), -(-num * tokens * k * held // (den * num_experts)))


def _chunks(tok, weight, sizes, rows: int):
    """How the sorted list of token-choices is worked through: ``rows``
    at a time. Returns ``(count, slice_of)``; ``slice_of(c)`` gives
    chunk ``c``'s token ids, routing weights (0 past the list's end) and
    each expert's rows inside the chunk. The list is padded to whole
    chunks (a slice that ran over its end would be moved back)."""
    # the chunk loop's own bookkeeping (op metadata: core/scopes.py)
    with jax.named_scope("moe.chunk"):
        pad = -tok.shape[0] % rows
        tok, weight = jnp.pad(tok, (0, pad)), jnp.pad(weight, (0, pad))
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        count = (ends[-1] + rows - 1) // rows

    def slice_of(c):
        with jax.named_scope("moe.chunk"):
            lo = c * rows
            t = jax.lax.dynamic_slice_in_dim(tok, lo, rows)
            w = jax.lax.dynamic_slice_in_dim(weight, lo, rows)
            inside = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
            valid = (lo + jnp.arange(rows)) < ends[-1]
            return t, jnp.where(valid, w, 0.0), inside.astype(jnp.int32), valid

    return count, slice_of


def _experts_fwd_one(rows, x, tok, weight, sizes, *stacks):
    """One lane: ``y[t] = sum over the held choices (t, e) of
    weight * MLP_e(x[t])``, float32 [N, C], and the rows the grouped
    product was given over the chunks that ran (what ``moe_dropped``
    is counted from)."""
    count, slice_of = _chunks(tok, weight, sizes, rows)

    def body(c, acc):
        y, done = acc
        t, w, inside, valid = slice_of(c)
        with jax.named_scope("moe.route"):
            xs = jnp.take(x, t, axis=0)
        with jax.named_scope("moe.experts"):
            # the rows through their own expert's MLP; those past the sum are masked below
            out = mlp_forward(xs, stacks, inside)
        with jax.named_scope("moe.combine"):
            add = jnp.where(valid[:, None], out.astype(jnp.float32) * w[:, None], 0.0)
            return y.at[t].add(add), done + jnp.sum(inside).astype(jnp.float32)

    with jax.named_scope("moe.chunk"):
        start = (jnp.zeros(x.shape, jnp.float32), jnp.float32(0))
    return jax.lax.fori_loop(0, count, body, start)


def _experts_bwd_one(rows, x, tok, weight, sizes, *stacks_dy):
    """One lane's cotangents of ``x``, ``weight`` and the stacks, each
    chunk's forward recomputed (nothing of a chunk outlives it)."""
    *stacks, dy = stacks_dy
    count, slice_of = _chunks(tok, weight, sizes, rows)

    def body(c, acc):
        dx, dweight, *dstacks = acc
        t, w, inside, valid = slice_of(c)
        with jax.named_scope("moe.route"):
            xs = jnp.take(x, t, axis=0)
        with jax.named_scope("moe.combine"):
            dys = jnp.where(valid[:, None], jnp.take(dy, t, axis=0), 0.0)
        with jax.named_scope("moe.experts"):
            # the chunk's forward again, and its cotangents: the stacks' in float32
            out, dxs, g_stacks = mlp_backward(xs, stacks, inside, (dys * w[:, None]).astype(xs.dtype))
        with jax.named_scope("moe.combine"):
            dw = jnp.where(valid, jnp.sum(dys * out.astype(jnp.float32), axis=-1), 0.0)
            dweight = jax.lax.dynamic_update_slice_in_dim(dweight, dw, c * rows, axis=0)
        with jax.named_scope("moe.route"):
            dx = dx.at[t].add(jnp.where(valid[:, None], dxs.astype(jnp.float32), 0.0))
        # a pass over every held weight a chunk: its gradient into the float32 sums
        with jax.named_scope("moe.grad_sum"):
            return (dx, dweight, *(acc_w + g for acc_w, g in zip(dstacks, g_stacks)))

    with jax.named_scope("moe.grad_sum"):
        zeros32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        whole_chunks = jnp.zeros(-(-weight.shape[0] // rows) * rows, jnp.float32)
        carry = (zeros32(x), whole_chunks, *map(zeros32, stacks))
    dx, dweight, *dstacks = jax.lax.fori_loop(0, count, body, carry)
    with jax.named_scope("moe.grad_sum"):
        return (dx.astype(x.dtype), dweight[:weight.shape[0]],
                *(dw.astype(w.dtype) for dw, w in zip(dstacks, stacks)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_experts(rows, x, tok, weight, sizes, *stacks):
    """``x`` [N, C] tokens; ``tok`` / ``weight`` [M] the token id and
    routing weight of each choice that landed on a held expert, sorted
    by expert, ``sizes`` [held] the rows of each; ``stacks`` the held
    experts' matrices (``mlp_forward``: gate and up [held, C, I] and
    down [held, I, C], or up and down alone); ``rows`` the chunk the list is
    worked through in. Returns the held experts' part
    of the layer's output, float32 [N, C], and the number of rows the
    grouped product ran on (float32; no gradient). A ``custom_vjp`` because the
    chunk loop's length is a value (reverse mode cannot unroll it): the
    backward walks the same chunks, each chunk's forward recomputed.
    Under ``vmap`` the lanes run one after another, forward and
    backward (the chip's grouped kernels have no batch dimension, and one
    lane's rows at a time is all the chip has room for)."""
    # a container (core/scopes.py): the lanes' and the chunks' loops,
    # with the slices, carries and stacked outputs XLA emits for them
    with jax.named_scope("moe.loop"):
        return sequential_vmap(functools.partial(_experts_fwd_one, rows))(x, tok, weight, sizes, *stacks)


def _held_experts_fwd(rows, x, tok, weight, sizes, *stacks):
    return held_experts(rows, x, tok, weight, sizes, *stacks), (x, tok, weight, sizes, *stacks)


def _held_experts_bwd(rows, res, cotangents):
    dy, _ = cotangents  # the row count carries no gradient
    with jax.named_scope("moe.loop"):
        dx, dweight, *dstacks = sequential_vmap(functools.partial(_experts_bwd_one, rows))(*res, dy)
    return (dx, None, dweight, None, *dstacks)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class HeldExperts(nn.Module):
    """Routed experts, [B, T, C] -> [B, T, C]: the router's full width,
    this chip's share of the stacks (module docstring). ``activation``
    ``gated_silu`` (three stacks) or ``relu2`` (``up_proj`` and
    ``down_proj`` alone); ``shared_dim`` > 0 adds a shared expert of
    that width and the same form, on every token, whole. ``chunk_loads``:
    the chunk the sorted choices are worked through in, in even loads
    (``expert_chunk_rows``)."""

    num_experts: int
    experts_per_token: int
    expert_dim: int
    experts_held: Tuple[int, int]  # (first, count)
    norm_topk_prob: bool = True
    scoring: str = "softmax"  # | "sigmoid"
    use_expert_bias: bool = False
    norm_topk_eps: float = 0.0
    activation: str = "gated_silu"  # | "relu2"
    shared_dim: int = 0
    routed_scaling_factor: float = 1.0
    chunk_loads: float = 1.25

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        N, E, K = B * T, self.num_experts, self.experts_per_token
        first, held = self.experts_held
        if not (0 <= first and held > 0 and first + held <= E):
            raise ValueError(f"experts_held {self.experts_held} is no share of {E} experts")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router scoring {self.scoring!r}: 'softmax' or 'sigmoid'")
        if self.activation not in ("gated_silu", "relu2"):
            raise ValueError(f"expert activation {self.activation!r}: 'gated_silu' or 'relu2'")
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        wide, narrow = (held, C, self.expert_dim), (held, self.expert_dim, C)
        shapes = {"gate_proj": wide, "up_proj": wide, "down_proj": narrow}
        if self.activation == "relu2":
            del shapes["gate_proj"]
        stacks = tuple(self.param(name, init, shape) for name, shape in shapes.items())
        xf = x.reshape(N, C)
        with jax.named_scope("moe.route"):
            # float32 whatever the compute type (the router's parameters
            # are cast back up; the scores decide a discrete choice)
            logits = nn.Dense(E, use_bias=False, name="router", dtype=jnp.float32)(
                xf.astype(jnp.float32))
            scores = jax.nn.softmax(logits, axis=-1) if self.scoring == "softmax" else jax.nn.sigmoid(logits)
            moved = None
            if self.use_expert_bias:
                # the bias enters the choice only: the weights are the
                # chosen experts' unbiased scores (its gradient is 0)
                bias = self.param("expert_bias", nn.initializers.zeros, (E,)).astype(jnp.float32)
                _, expert = jax.lax.top_k(scores + bias, K)
                weight = jnp.take_along_axis(scores, expert, axis=-1)
                # a choice the unbiased top-k would not have made: K or
                # more experts score above it
                above = jnp.sum(scores[:, None, :] > weight[:, :, None], axis=-1)
                moved = jnp.sum(above >= K)
            else:
                weight, expert = jax.lax.top_k(scores, K)  # [N, K]
            if self.norm_topk_prob:
                total = jnp.sum(weight, axis=-1, keepdims=True)
                weight = weight / (total + self.norm_topk_eps if self.norm_topk_eps else total)
            if self.routed_scaling_factor != 1.0:
                weight = weight * self.routed_scaling_factor
            here = (expert >= first) & (expert < first + held)
            # absent experts sort last, as one group past the held ones
            local = jnp.where(here, expert - first, held).reshape(N * K)
            order = jnp.argsort(local, stable=True)
            # the most a token can place here is min(K, held) choices
            room = N * min(K, held)
            order = order[:room]
            tok = (order // K).astype(jnp.int32)
            sorted_weight = jnp.take(weight.reshape(N * K), order)
            sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
            hits = jnp.sum(here)
        # a chunk is an even load's rows and a quarter by default: a share
        # a little fuller than even still takes one chunk, so a step's time
        # does not jump with the seed's routing (at a chunk of exactly the
        # even load, layers a per cent over it ran a second, near-empty
        # chunk: a call of 10 rounds read 62.5 to 63.9 s by seed on the v5e);
        # a model whose share swings further from step to step takes a
        # wider one (chunk_loads)
        rows = expert_chunk_rows(N, K, held, E, self.chunk_loads)
        y, done = held_experts(rows, xf, tok, sorted_weight, sizes, *stacks)
        if self.shared_dim:
            with jax.named_scope("moe.shared"):
                shared = GatedMLP if self.activation == "gated_silu" else SquaredReluMLP
                y = y + shared(self.shared_dim, name="shared")(xf).astype(jnp.float32)
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        counted = {
            "moe_local_hits": hits, "moe_expert_tokens_max": jnp.max(sizes),
            "moe_expert_tokens_mean": f32(hits) / held,
            # choices on held experts less the rows the chunk loop gave
            # the grouped product: 0 while the loop walks the whole list
            "moe_dropped": f32(hits) - jax.lax.stop_gradient(done),
            # of those rows, the ones the Pallas kernels ran on (0 where ragged_dot did)
            "moe_kernel_rows": kernel_rows(jax.lax.stop_gradient(done), (rows, C), stacks),
        }
        if moved is not None:
            counted["moe_bias_moved"] = moved
        for name, value in counted.items():
            self.sow("counters", name, f32(value), reduce_fn=jnp.add, init_fn=lambda: f32(0))
        # the output in the compute type; its transpose, the cotangent back in float32
        with jax.named_scope("moe.out"):
            return y.astype(x.dtype).reshape(B, T, C)


# -- block and model ------------------------------------------------------

class DecoderBlock(nn.Module):
    """``x + operator(norm(x))`` then ``x + ffn(norm(x))``. The operator
    is attention (``kind`` sliding or full; ``output_gate``: its output
    gated) or the gated short convolution (``conv``); the feed-forward
    the routed experts (``experts``: ``HeldExperts``' fields) or, with
    ``experts`` None, a dense gated MLP of ``intermediate_size``. With
    ``post_norms`` the attention's and the feed-forward's outputs are
    normed before the residual takes them (the sandwich norm)."""

    kind: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    attention: str
    eps: float
    conv_taps: int
    intermediate_size: int
    experts: Optional[Any]  # HeldExperts' fields (a frozen dict), or None: a dense MLP
    output_gate: bool = False
    post_norms: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        def residual(x, h, post_norm: str):
            """``x + h``, or with ``post_norms`` ``x + RMSNorm(h)`` (a
            local function: a method would add its name to the ops')."""
            if self.post_norms:
                with jax.named_scope("blk.post_norm"):
                    h = RMSNorm(self.eps, name=post_norm)(h)
            return x + h

        if self.kind == CONV:
            with jax.named_scope("blk.conv"):
                x = x + GatedShortConv(self.conv_taps, name="conv")(
                    RMSNorm(self.eps, name="conv_norm")(x))
        else:
            with jax.named_scope("blk.attn.window" if self.window is not None else "blk.attn.full"):
                x = residual(x, Attention(
                    self.num_heads, self.num_kv_heads, self.head_dim, self.window,
                    self.attention, self.eps, output_gate=self.output_gate, name="attn",
                )(RMSNorm(self.eps, name="attn_norm")(x), cos, sin), "attn_post_norm")
        with jax.named_scope("blk.ffn_norm"):
            h = RMSNorm(self.eps, name="ffn_norm")(x)
        if self.experts is not None:
            return residual(x, HeldExperts(**self.experts, name="moe")(h), "ffn_post_norm")
        with jax.named_scope("blk.mlp.dense"):
            return residual(x, GatedMLP(self.intermediate_size, name="mlp")(h), "ffn_post_norm")


class SublayerBlock(nn.Module):
    """``x + mixer(norm(x))``, one sublayer a layer: the mixer is the
    state-space one (``kind`` ``mamba``; ``ssm``: ``Mamba2Mixer``'s
    fields but eps), attention (sliding or full; ``attn``: ``Attention``'s
    fields but the window and eps) or the routed experts (``moe``; ``experts``:
    ``HeldExperts``' fields); ``eps`` is every norm's."""

    kind: str
    window: Optional[int]
    eps: float
    attn: Any
    ssm: Any
    experts: Any

    @nn.compact
    def __call__(self, x, cos, sin):
        if self.kind == SSM:
            with jax.named_scope("blk.ssm"):
                return x + Mamba2Mixer(**self.ssm, eps=self.eps, name="ssm")(
                    RMSNorm(self.eps, name="ssm_norm")(x))
        if self.kind == EXPERTS:
            with jax.named_scope("blk.ffn_norm"):
                h = RMSNorm(self.eps, name="ffn_norm")(x)
            return x + HeldExperts(**self.experts, name="moe")(h)
        with jax.named_scope("blk.attn.window" if self.window is not None else "blk.attn.full"):
            return x + Attention(window=self.window, eps=self.eps, **self.attn, name="attn")(
                RMSNorm(self.eps, name="attn_norm")(x), cos, sin)


class MoEDecoderLM(nn.Module):
    """Causal LM over ``vocab_size`` rows (a slice of a larger
    vocabulary is a smaller vocabulary: ids, logits and loss are over
    it): tokens [B, T] -> float32 logits [B, T, vocab_size]. The first
    ``num_dense_layers`` layers carry a dense MLP of
    ``intermediate_size``, every other one the routed experts; with
    ``tie_word_embeddings`` the head is the embedding's rows (one leaf,
    the gradients of both uses summed). With ``sublayers`` every entry
    of ``layer_types`` is one sublayer (``SublayerBlock``: ``mamba``,
    an attention kind or ``moe``) instead of an operator and a
    feed-forward. An attention kind with no entry in
    ``rope_parameters`` is not rotated. ``output_gate``, ``post_norms``
    and ``embedding_multiplier`` are the module docstring's; the
    sublayer form takes neither of the first two, a convolution layer
    no sandwich norms."""

    vocab_size: int
    hidden_size: int
    layer_types: Sequence[str]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sliding_window: int
    rope_parameters: Any  # {layer type: {"rope_type", "rope_theta", ...}}
    num_experts: int
    experts_per_token: int
    expert_dim: int
    experts_held: Tuple[int, int]
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    attention: str = "full"
    remat: bool = False
    num_dense_layers: int = 0
    intermediate_size: int = 0
    conv_L_cache: int = 3
    router_scoring: str = "softmax"
    use_expert_bias: bool = False
    norm_topk_eps: float = 0.0
    tie_word_embeddings: bool = False
    sublayers: bool = False
    qk_norm: bool = True
    expert_activation: str = "gated_silu"
    shared_expert_dim: int = 0
    routed_scaling_factor: float = 1.0
    expert_chunk_loads: float = 1.25
    ssm: Any = None  # the ``mamba`` sublayers': Mamba2Mixer's fields but eps (a frozen dict)
    output_gate: bool = False
    post_norms: bool = False
    embedding_multiplier: float = 1.0

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        T = tokens.shape[1]
        embed = nn.Embed(self.vocab_size, self.hidden_size, name="embed")
        with jax.named_scope("lm.embed"):
            x = embed(tokens.astype(jnp.int32))
            if self.embedding_multiplier != 1.0:
                # in float32, rounded once to the compute type
                x = (x.astype(jnp.float32) * self.embedding_multiplier).astype(x.dtype)
        kinds = (SLIDING, FULL, SSM, EXPERTS) if self.sublayers else (SLIDING, FULL, CONV)
        for kind in self.layer_types:
            if kind not in kinds:
                raise ValueError(f"layer type {kind!r}: one of {kinds}")
        # one table per rotated attention layer type, made once
        tables = {
            kind: rope_tables(T, self.head_dim, dict(self.rope_parameters[kind]))
            for kind in dict.fromkeys(self.layer_types)
            if kind in (SLIDING, FULL) and kind in self.rope_parameters
        }
        experts = freeze(dict(
            num_experts=self.num_experts, experts_per_token=self.experts_per_token,
            expert_dim=self.expert_dim, experts_held=tuple(self.experts_held),
            norm_topk_prob=self.norm_topk_prob, scoring=self.router_scoring,
            use_expert_bias=self.use_expert_bias, norm_topk_eps=self.norm_topk_eps,
            activation=self.expert_activation, shared_dim=self.shared_expert_dim,
            routed_scaling_factor=self.routed_scaling_factor, chunk_loads=self.expert_chunk_loads,
        ))
        if self.sublayers:
            if SSM in self.layer_types and self.ssm is None:
                raise ValueError(f"a {SSM!r} sublayer needs the state-space mixer's sizes (ssm)")
            if self.output_gate or self.post_norms:
                raise ValueError("the sublayer form takes no output gate and no sandwich norms")
            attn = freeze(dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                impl=self.attention, qk_norm=self.qk_norm))
            block = nn.remat(SublayerBlock) if self.remat else SublayerBlock
            layer = lambda i, kind: block(
                kind, self.sliding_window if kind == SLIDING else None, self.rms_norm_eps,
                attn, self.ssm, experts, name=f"layer_{i}")
        else:
            if self.post_norms and CONV in self.layer_types:
                raise ValueError("a convolution layer takes no sandwich norms")
            block = nn.remat(DecoderBlock) if self.remat else DecoderBlock
            layer = lambda i, kind: block(
                kind, self.num_heads, self.num_kv_heads, self.head_dim,
                self.sliding_window if kind == SLIDING else None, self.attention,
                self.rms_norm_eps, self.conv_L_cache, self.intermediate_size,
                experts if i >= self.num_dense_layers else None, self.output_gate, self.post_norms,
                name=f"layer_{i}")
        for i, kind in enumerate(self.layer_types):
            x = layer(i, kind)(x, *tables.get(kind, (None, None)))
        with jax.named_scope("lm.head_loss"):
            x = RMSNorm(self.rms_norm_eps, name="final_norm")(x)
            if self.tie_word_embeddings:
                return embed.attend(x).astype(jnp.float32)
            return _proj(self.vocab_size, "lm_head")(x).astype(jnp.float32)


def rope_parameters_from_args(args):
    """``args.rope_parameters`` ({layer type: {...}}, as a published
    ``config.json`` has it) or, without one, the default table at a
    base of 10,000 for both layer types. An attention type the given
    table leaves out is not rotated (``{}``: no layer is)."""
    given = getattr(args, "rope_parameters", None)
    if given is None:
        given = {kind: {"rope_type": "default", "rope_theta": 10000.0} for kind in (SLIDING, FULL)}
    return freeze({k: dict(v) for k, v in dict(given).items()})
