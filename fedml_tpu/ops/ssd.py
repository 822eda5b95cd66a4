"""The chunked state-space scan (the "state-space dual" form of Dao &
Gu 2024, arXiv:2405.21060), as ``jnp`` products.

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
Reverse mode is autodiff of this form (the caller's ``remat`` decides
what is kept). No Pallas kernel: a later one has this as its reference.

A ``T`` that ``chunk`` does not divide is padded at its end with steps
of ``dt = 0`` -- they decay nothing and add nothing -- and the padded
outputs are cut off: the last chunk is then partly idle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def num_chunks(seq_len: int, chunk: int) -> int:
    """Chunks the scan runs for one sequence of ``seq_len`` tokens."""
    return -(-seq_len // chunk)


def ssd_scan(x, dt, a_head, b, c, d_head, chunk: int = 128) -> jax.Array:
    """``x`` [Bt, T, H, P]; ``dt`` [Bt, T, H] float32, positive (after
    its softplus); ``a_head`` [H] float32, negative; ``b``, ``c``
    [Bt, T, G, N] with ``H % G == 0``; ``d_head`` [H] float32. Returns
    ``y`` [Bt, T, H, P] in ``x``'s type (module docstring)."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    if h % g or chunk <= 0:
        raise ValueError(f"{h} heads over {g} groups in chunks of {chunk}: no such scan")
    nc = num_chunks(t, chunk)
    pad = nc * chunk - t
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
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

