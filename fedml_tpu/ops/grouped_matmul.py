"""The experts' grouped product as Pallas grouped-matmul kernels.

Rows sorted by expert go through their own expert's MLP
(``models/decoder.held_experts``' chunks): three stacks (gate, up, down) are a
gated-SiLU expert, two (up, down) a squared-ReLU one. The reference form
is ``jax.lax.ragged_dot`` with autodiff for the backward; it stays the
path for shapes this module does not take (``takes``) and for every
platform but the TPU. On the TPU the same products run as the grouped
matmuls JAX ships for it (``jax.experimental.pallas.ops.tpu.megablox``):

- forward: ``gmm(xs, W)`` a stack, operands in the compute type,
  float32 accumulation and output; the activation between, as in the
  reference; the down product's output rounded once, to the compute type;
- backward, written out (``mlp_backward``): the forward recomputed,
  ``dh = gmm(dout, W_down, transpose_rhs=True)`` (no transposed copy of
  a stack), the activation's own VJP, ``dx`` as two more transposed
  products summed in float32, and each stack's gradient ``tgmm(lhs^T,
  d)`` in float32 (the reference rounds it to the stack's type first).

A kernel's grid visits only the row tiles that hold a group's rows: the
chunk's slack past ``sum(sizes)`` costs nothing, and the output rows
there are left unwritten -- the caller masks what they read, as it does
for the reference. Operands a product takes in float32 (a cotangent) are
rounded to the compute type first, as the chip's default precision does
inside ``ragged_dot``.

Tiles follow from the problem's ``(m, k, n)`` by ``tiling``; each
distinct body lowers once (``mlp_forward`` / ``mlp_backward`` are jitted
entries, so the layers and the evaluation share one function a shape).
No host callback, so executables that hold these kernels stay in the
persistent compile cache.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _shipped_gmm, tgmm as _shipped_tgmm

_LANES = 128
# a kernel's blocks, double-buffered, and its float32 accumulator must fit
# the default scoped VMEM (16 MiB on the v5e), with room
_VMEM_BUDGET = 12 * 2**20


def takes(m: int, k: int, n: int) -> bool:
    """Whether the kernels take a chunk of ``m`` rows through experts
    of ``k`` inputs and ``n`` hidden units: by shape alone. The rows are
    whole 128-row tiles (the grid's), the widths at least a lane tile."""
    return m % _LANES == 0 and k >= _LANES and n >= _LANES


def _row_tile(m: int) -> int:
    return next(t for t in (512, 256, _LANES) if m % t == 0)


def _narrower(width: int, tile: int) -> int:
    """The next tile of ``width`` below ``tile``: one part more, each a
    whole number of lane tiles (a block spans a whole dimension or a
    multiple of 128; the last one may hang over the edge)."""
    parts = -(-width // tile) + 1
    return -(-width // (parts * _LANES)) * _LANES


def _vmem_bytes(form: str, tm: int, tk: int, tn: int, itemsize: int) -> int:
    """The blocks, double-buffered, and the float32 accumulator."""
    if form == "tgmm":  # lhs [tm, tk] and rhs [tm, tn] in; out [tk, tn] float32
        return 2 * tm * (tk + tn) * itemsize + 3 * tk * tn * 4
    return 2 * (tm * tk + tk * tn) * itemsize + 3 * tm * tn * 4  # out [tm, tn] float32


def tiling(form: str, m: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """``(tm, tk, tn)`` of one product: ``gmm`` (``[m, k] @ [k, n]`` a
    group, the rhs read as stored or transposed) or ``tgmm`` (``[k, m]
    @ [m, n]`` a group). Rows in tiles of 512 where ``m`` allows; the
    widths whole, the wider of the two cut into more parts until the
    blocks fit ``_VMEM_BUDGET``."""
    tm, tk, tn = _row_tile(m), k, n
    while _vmem_bytes(form, tm, tk, tn, itemsize) > _VMEM_BUDGET:
        if tk >= tn:
            tk = _narrower(k, tk)
        else:
            tn = _narrower(n, tn)
    return tm, tk, tn


def _precision(dtype):
    """The flash kernels' rule for a product's precision: the caller's
    for float32 operands, ``DEFAULT`` pinned for bfloat16 (its products
    are exact in float32 either way; Mosaic refuses ``HIGHEST`` on them)."""
    return contextlib.nullcontext() if dtype == jnp.float32 else jax.default_matmul_precision("default")


def _gmm(lhs, rhs, sizes, out_dtype, *, transpose_rhs=False, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with _precision(lhs.dtype):
        return _shipped_gmm(
            lhs, rhs, sizes, out_dtype, tiling("gmm", m, k, n, lhs.dtype.itemsize),
            transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm(lhs, d, sizes, *, interpret):
    """Each group's ``lhs[rows]^T @ d[rows]``, float32 [groups, k, n]."""
    m, k = lhs.shape
    n = d.shape[1]
    with _precision(lhs.dtype):
        return _shipped_tgmm(
            lhs.swapaxes(0, 1), d, sizes, jnp.float32, tiling("tgmm", m, k, n, lhs.dtype.itemsize),
            interpret=interpret)


def _activation(stacks):
    """The hidden rows from the first products, in float32 -> compute type."""
    if len(stacks) == 3:
        return lambda gate, up: jax.nn.silu(gate) * up
    return lambda up: jnp.square(jax.nn.relu(up))


def _hidden(xs, stacks, sizes, interpret):
    """The first products and the hidden rows' VJP."""
    dtype = xs.dtype
    first = [_gmm(xs, w, sizes, jnp.float32, interpret=interpret) for w in stacks[:-1]]
    act = _activation(stacks)
    return jax.vjp(lambda *f: act(*f).astype(dtype), *first)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_forward(xs, sizes, *stacks, interpret=False):
    h, _ = _hidden(xs, stacks, sizes, interpret)
    return _gmm(h, stacks[-1], sizes, xs.dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_backward(xs, sizes, dout, *stacks, interpret=False):
    dtype = xs.dtype
    h, act_vjp = _hidden(xs, stacks, sizes, interpret)
    out = _gmm(h, stacks[-1], sizes, dtype, interpret=interpret)
    dh = _gmm(dout, stacks[-1], sizes, jnp.float32, transpose_rhs=True, interpret=interpret)
    dfirst = [d.astype(dtype) for d in act_vjp(dh.astype(dtype))]
    dxs = sum(_gmm(d, w, sizes, jnp.float32, transpose_rhs=True, interpret=interpret)
              for d, w in zip(dfirst, stacks[:-1]))
    dstacks = [_tgmm(xs, d, sizes, interpret=interpret) for d in dfirst] + [
        _tgmm(h, dout, sizes, interpret=interpret)]
    return out, dxs, tuple(dstacks)


def _reference_forward(xs, sizes, *stacks):
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=jnp.float32)
    h = _activation(stacks)(*(dot(xs, w) for w in stacks[:-1])).astype(xs.dtype)
    return dot(h, stacks[-1]).astype(xs.dtype)


def _reference_backward(xs, sizes, dout, *stacks):
    out, vjp = jax.vjp(lambda xs, *ws: _reference_forward(xs, sizes, *ws), xs, *stacks)
    dxs, *dstacks = vjp(dout)
    return out, dxs.astype(jnp.float32), tuple(d.astype(jnp.float32) for d in dstacks)


def _on_platform(kernel, reference, *operands):
    """The kernel on the TPU, the reference elsewhere."""
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=reference)


def _shape(xs, stacks):
    return xs.shape[0], xs.shape[1], stacks[0].shape[2]


def mlp_forward(xs, stacks, sizes):
    """``xs`` [m, k] rows sorted by group, ``sizes`` [groups] int32,
    ``stacks`` the experts' matrices (``[groups, k, n]`` each but the
    last, ``[groups, n, k]``): the rows' MLP outputs in ``xs``' type."""
    if not takes(*_shape(xs, stacks)):
        return _reference_forward(xs, sizes, *stacks)
    return _on_platform(_kernel_forward, _reference_forward, xs, sizes, *stacks)


def mlp_backward(xs, stacks, sizes, dout):
    """``mlp_forward``'s output again and the cotangents, from ``dout``
    (in ``xs``' type): ``(out, dxs, dstacks)``, the last two float32."""
    if not takes(*_shape(xs, stacks)):
        return _reference_backward(xs, sizes, dout, *stacks)
    return _on_platform(_kernel_backward, _reference_backward, xs, sizes, dout, *stacks)


def kernel_rows(rows, xs_shape, stacks):
    """``rows`` where ``mlp_forward`` at these shapes runs the kernels,
    else 0 (float32; what ``moe_kernel_rows`` counts)."""
    m, k = xs_shape
    if not takes(m, k, stacks[0].shape[2]):
        return jnp.zeros_like(rows)
    return _on_platform(lambda r, interpret=False: r, jnp.zeros_like, rows)
