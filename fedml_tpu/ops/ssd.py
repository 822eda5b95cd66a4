"""The chunked state-space scan (the "state-space dual" form of Dao &
Gu 2024, arXiv:2405.21060): as ``jnp`` products (``ssd_scan_jnp``) and,
for the shapes ``ops/ssd_kernel.py`` takes, as Pallas kernels.

Per head, with a scalar decay a step, the recurrence is

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t = S_t C_t + D x_t

``x_t`` [P] the head's input, ``B_t`` / ``C_t`` [N] shared by the heads
of a group (head ``h`` reads group ``h // (H / G)``), ``S`` [P, N].
Step by step that is ``T`` small outer products; here the sequence is
cut into chunks of ``chunk`` tokens and

- *inside a chunk* the outputs that the chunk's own inputs give are the
  masked product ``(L o C B^T) (dt x)`` with ``L_ij = exp(a_i - a_j)``
  for ``i >= j`` and 0 above the diagonal, ``a`` the chunk's running sum
  of ``dt A`` (the differences are taken before the exponential: every
  exponent is <= 0);
- *a chunk's state* is what its inputs leave at its end,
  ``sum_j exp(a_last - a_j) dt_j x_j B_j^T``;
- *between chunks* the carried state ``S <- exp(a_last) S + state`` is
  a ``lax.scan`` over the chunks, and a chunk's outputs gain
  ``exp(a_i) C_i S_before``.

The four products (``C B^T``, the masked one, the states, ``C S``) take
their operands in ``x``'s type with float32 accumulation (bfloat16 on
the MXU where the model computes in bfloat16); ``dt``, the decays, the
running sums, the mask and the carried state are float32 throughout.
Reverse mode of the ``jnp`` form is autodiff of it (the caller's
``remat`` decides what is kept).

``ssd_scan`` chooses by what it sees in its arguments, nothing else
(``kernel_chunks``): where the chunk and the state are whole 128-lane
tiles, a group's heads fill whole lane tiles and the sequence holds a
chunk, the scan runs as ``ops/ssd_kernel.py``'s two Pallas kernels --
the same products, casts and float32 state, the mask and the carried
state in VMEM --; every other shape (test-sized models) runs the
``jnp`` form, which is also the kernels' reference.

A ``T`` that ``chunk`` does not divide is padded at its end with steps
of ``dt = 0`` -- they decay nothing and add nothing -- and the padded
outputs are cut off: the last chunk is then partly idle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ssd_kernel


def num_chunks(seq_len: int, chunk: int) -> int:
    """Chunks the scan runs for one sequence of ``seq_len`` tokens."""
    return -(-seq_len // chunk)


def kernel_chunks(seq_len: int, heads: int, head_dim: int, groups: int, state: int, chunk: int) -> int:
    """Of ``num_chunks``, those that ``ssd_scan`` hands the Pallas
    kernels at these shapes: all of them or none."""
    geom = ssd_kernel.Geometry(heads, head_dim, groups, state, chunk)
    return num_chunks(seq_len, chunk) if ssd_kernel.takes(seq_len, geom) else 0


def chunk_counts(seq_len: int, heads: int, head_dim: int, groups: int, state: int, chunk: int) -> dict:
    """What a mixer sows into ``counters`` for one sequence: the chunks
    its scan runs (``ssm_chunks``) and those of them that run as the
    Pallas kernels (``ssm_kernel_chunks``: all at kernel-sized widths,
    none at a test's)."""
    return {
        "ssm_chunks": num_chunks(seq_len, chunk),
        "ssm_kernel_chunks": kernel_chunks(seq_len, heads, head_dim, groups, state, chunk),
    }


def _whole_chunks(x, dt, b, c, chunk):
    """The operands padded at their end to whole chunks with steps of
    ``dt = 0`` (module docstring)."""
    pad = -x.shape[1] % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
    return x, dt, b, c


def _check(h, g, chunk):
    if chunk <= 0 or h % g:
        raise ValueError(f"{h} heads over {g} groups in chunks of {chunk}: no such scan")


def ssd_scan(x, dt, a_head, b, c, d_head, chunk: int = 128) -> jax.Array:
    """``x`` [Bt, T, H, P]; ``dt`` [Bt, T, H] float32, positive (after
    its softplus); ``a_head`` [H] float32, negative; ``b``, ``c``
    [Bt, T, G, N] with ``H % G == 0``; ``d_head`` [H] float32. Returns
    ``y`` [Bt, T, H, P] in ``x``'s type (module docstring): through the
    Pallas kernels where ``ssd_kernel.takes`` the shape, else ``ssd_scan_jnp``."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    _check(h, g, chunk)
    geom = ssd_kernel.Geometry(h, p, g, n, chunk)
    if not ssd_kernel.takes(t, geom):
        return ssd_scan_jnp(x, dt, a_head, b, c, d_head, chunk)
    f32 = jnp.float32
    x, dt, b, c = _whole_chunks(x, dt, b, c, chunk)
    padded = x.shape[1]
    y = ssd_kernel.scan(
        x.reshape(bt, padded, h * p), dt.astype(f32), a_head.astype(f32), b.reshape(bt, padded, g * n),
        c.reshape(bt, padded, g * n), d_head.astype(f32), geom)
    return y.reshape(bt, padded, h, p)[:, :t]


def ssd_scan_jnp(x, dt, a_head, b, c, d_head, chunk: int = 128) -> jax.Array:
    """``ssd_scan``'s operands and result, every shape, as ``jnp``
    products and a ``lax.scan`` over the chunks."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    _check(h, g, chunk)
    nc = num_chunks(t, chunk)
    x, dt, b, c = _whole_chunks(x, dt, b, c, chunk)
    per = h // g
    f32, dtype = jnp.float32, x.dtype
    dot = lambda spec, *ops: jnp.einsum(spec, *ops, preferred_element_type=f32)
    xc = x.reshape(bt, nc, chunk, g, per, p)
    bc = b.reshape(bt, nc, chunk, g, n)
    cc = c.reshape(bt, nc, chunk, g, n)
    dtc = dt.astype(f32).reshape(bt, nc, chunk, g, per)
    # the chunk's running sum of dt * A, its own step included
    a = jnp.cumsum(dtc * a_head.astype(f32).reshape(g, per), axis=2)  # [bt, nc, q, g, per]
    a_last = a[:, :, -1]  # [bt, nc, g, per]

    # inside a chunk: (L o C B^T) (dt x), one mask a head
    aq = jnp.moveaxis(a, 2, -1)  # [bt, nc, g, per, q]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, aq[..., :, None] - aq[..., None, :], -jnp.inf))
    scores = dot("bcqgn,bckgn->bcgqk", cc, bc)
    masked = decay * scores[:, :, :, None] * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y = dot("bcgrqk,bckgrp->bcqgrp", masked.astype(dtype), xc)

    # what a chunk's own inputs leave at its end
    to_end = jnp.exp(a_last[:, :, None] - a) * dtc  # [bt, nc, q, g, per]
    states = dot("bckgrp,bckgn->bcgrpn", (xc.astype(f32) * to_end[..., None]).astype(dtype), bc)

    # between chunks: the carried state, float32
    def carry(s, inputs):
        state, decay_c = inputs
        return decay_c[..., None, None] * s + state, s  # emits the state *before* the chunk

    _, before = jax.lax.scan(
        carry, jnp.zeros((bt, g, per, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(a_last), 1, 0)))
    before = jnp.moveaxis(before, 0, 1)  # [bt, nc, g, per, p, n]
    y = y + dot("bcqgn,bcgrpn->bcqgrp", cc, before.astype(dtype)) * jnp.exp(a)[..., None]

    y = y + xc.astype(f32) * d_head.astype(f32).reshape(g, per, 1)
    return y.reshape(bt, nc * chunk, h, p)[:, :t].astype(dtype)

