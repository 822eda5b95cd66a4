"""The chunked state-space scan of ``ops/ssd.py`` as two Pallas kernels.

Same mathematics, operand types and casts as ``ssd.ssd_scan_jnp`` (its
module docstring has the recurrence and the chunked form), which stays
the reference and the path for shapes this module does not take
(``takes``). What changes is where the intermediates live: a chunk's
decay mask ``exp(a_i - a_j)``, its ``C B^T`` and the carried state
never leave VMEM.

Layout. Operands stay token-major as the mixer's projections leave
them: ``x`` / ``y`` [Bt, T, H P], ``B`` / ``C`` [Bt, T, G N], so no
transpose of a sequence-sized array surrounds the kernels. One program
is one (sequence, chunk): the grid is (Bt, T / chunk), the chunk axis
``arbitrary``, the carried state of every head [H P / W, W, N] float32
in a VMEM scratch across it. Inside, a loop over the groups (``C B^T``
once a group) holds a loop over the group's *lane tiles*: ``W = max(P,
128)`` lanes of ``x``, i.e. the ``W / P`` heads that share a 128-lane
tile (two at P = 64), read with a 128-aligned dynamic lane slice. A
tile's heads each build their own [chunk, chunk] mask and multiply it
into the whole tile, the product kept on the head's own lanes -- on a
128-wide MXU that costs what a 64-wide product would --; the chunk's
state and ``C S`` are one full-width product a tile. Both loops are
unrolled (``_unrolled`` says why and what it costs); the chunks are the
grid and never unroll.

The per-head scalars (``a`` = the chunk's running sum of ``dt A``,
``dt``, and the chunk's last ``a`` at each of its tokens) are made by
the wrapper in XLA, float32, head-major [Bt, 3H, T] (T x 3H numbers, 5%
of ``x``): a head's *row* [1, chunk] is a sublane index and one vector
register. What has to run down the sublanes -- ``a_i`` against ``a_j``
in the mask, a tile of per-token factors beside ``x`` -- is a row
broadcast and transposed (``_down``).

Backward (``jax.custom_vjp``). The forward rule runs the same kernel
with one more output, each chunk's entry state ([Bt, T / chunk, H P / W,
W, N] float32: 134 MB a layer at the TwoTower cell's sizes, alive
inside one layer's backward); under the block's ``remat`` that is the
recompute, so a trained chunk is forward twice and backward, as with
the ``jnp`` form. The reverse kernel walks the chunks from the last to
the first with the state's cotangent in VMEM, rebuilds each mask, and
gives ``dx``, ``dB`` / ``dC`` (summed over a group's heads in VMEM),
``dD`` and, as rows [Bt, 3H, T], the cotangents of ``a``, ``dt`` and
the chunk's last ``a`` (the wrapper takes ``a``'s back through its
running sum: ``d dt``, ``dA``). Products take operands in ``x``'s type
-- the rebuilt masks, the mask's gradient, the state's cotangent and
the 0/1 rows that sum over a head's lanes or a mask's rows among them
-- and accumulate in float32, as the flash kernels' backward does.

Platforms: compiled by Mosaic on ``tpu``, the same body in the Pallas
interpreter on ``cpu`` (``lax.platform_dependent``); no host callback
and no debug print anywhere, so the executables that hold these kernels
are written to and read from the persistent compile cache like any
other. Tiles are the chunk itself: nothing is swept or probed at
set-up.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _dot  # the lane tile; products under the flash kernels' precision rule

_NEG = -1e30
# blocks of x / y / dy / dx [chunk, H P] and a chunk's entry states,
# double-buffered, at the TwoTower widths: ~16 MiB backward; with room
_VMEM_BYTES = 64 * 2**20


class Geometry(NamedTuple):
    """The scan's static shape: ``heads`` of ``head_dim`` over
    ``groups`` with states of ``state`` in chunks of ``chunk``."""

    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int

    @property
    def width(self) -> int:
        """Lanes of one tile of heads."""
        return max(self.head_dim, _LANES)

    @property
    def heads_per_tile(self) -> int:
        return self.width // self.head_dim

    @property
    def tiles(self) -> int:
        return self.heads * self.head_dim // self.width

    @property
    def tiles_per_group(self) -> int:
        return self.tiles // self.groups


def takes(seq_len: int, geom: Geometry) -> bool:
    """Whether the kernels take this scan: by shape alone. The chunk
    and the state are whole 128-lane tiles, a group's heads fill whole
    lane tiles, a head is a whole share of a tile or whole tiles, and
    the sequence holds at least one chunk."""
    h, p, g, n, q = geom
    return (
        q % _LANES == 0 and n % _LANES == 0 and seq_len >= q and h % g == 0
        and (h // g * p) % _LANES == 0 and (_LANES % p == 0 or p % _LANES == 0)
    )


def _unrolled(n, body, init):
    """``fori_loop`` over the groups or a group's lane tiles, unrolled:
    one tile is one long chain (rows -> transpose -> exp -> product ->
    store) and a rolled loop leaves the units waiting on it -- 1.07 ms a
    sequence forward at the TwoTower widths against 0.41 unrolled
    (PERF.md, PR 36) -- while the unrolled body costs the lowering 0.1 s."""
    return jax.lax.fori_loop(0, n, body, init, unroll=True)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


class _Head(NamedTuple):
    """One head's scalars in a chunk, float32, as rows [1, q] along the
    lanes (a row costs one vector register, a column sixteen): ``a``,
    ``dt``, ``a_last`` (the chunk's last ``a`` on every lane), ``to_end
    = exp(a_last - a)`` and the mask [q, q], ``exp(a_i - a_j)`` on and
    under the diagonal, 0 above."""

    a: jax.Array
    dt: jax.Array
    a_last: jax.Array
    to_end: jax.Array
    decay: jax.Array


def _down(rows):
    """Rows [r, q] (tokens along the lanes) -> [q, r] (tokens down the
    sublanes): the one way a per-token scalar reaches the sublanes."""
    return jnp.transpose(rows)


def _head(hd, hm_ref, lower, geom: Geometry):
    h, q = geom.heads, geom.chunk
    a, dt, a_last = (hm_ref[0, pl.ds(i * h + hd, 1), :] for i in range(3))
    # differences before the exponential: every exponent kept is <= 0
    decay = jnp.exp(jnp.where(lower, _down(jnp.broadcast_to(a, (q, q))) - a, _NEG))
    return _Head(a, dt, a_last, jnp.exp(a_last - a), decay)


def _lanes_of(j, shape, geom: Geometry):
    """Which lanes of a tile are head ``j``'s."""
    i = _iota(shape, 1)
    return (i >= j * geom.head_dim) & (i < (j + 1) * geom.head_dim)


def _tile_of(rows, geom: Geometry):
    """The tile's heads' rows [1, q] -> [q, W]: down the sublanes, each
    head's scalar on its own lanes."""
    return _down(jnp.concatenate([jnp.broadcast_to(r, (geom.head_dim, geom.chunk)) for r in rows], axis=0))


def _across(row, n):
    """A row [1, q] that holds one number on every lane, as [1, n]."""
    return jnp.concatenate([row[:, :_LANES]] * (n // _LANES), axis=1)


def _lower(q):
    return _iota((q, q), 0) >= _iota((q, q), 1)


def _fwd_kernel(x_ref, hm_ref, b_ref, c_ref, d_ref, y_ref, *rest, geom: Geometry, emit: bool):
    """One chunk of one sequence, every head. ``rest`` is the carried
    state's scratch, after the entry states' output where ``emit``."""
    state = rest[-1]
    q, w, n, p = geom.chunk, geom.width, geom.state, geom.head_dim
    dtype = x_ref.dtype
    lower = _lower(q)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def tile(k, bg, cg, scores):
        off = pl.multiple_of(k * w, _LANES)
        xk = x_ref[0, :, pl.ds(off, w)]
        xf = xk.astype(jnp.float32)
        before = state[k]
        if emit:
            rest[0][0, 0, k] = before
        heads = [_head(k * geom.heads_per_tile + j, hm_ref, lower, geom) for j in range(geom.heads_per_tile)]
        y = jnp.zeros((q, w), jnp.float32)
        for j, hd in enumerate(heads):  # the heads that share this lane tile
            masked = hd.decay * scores * hd.dt
            y = jnp.where(_lanes_of(j, (q, w), geom), _dot(masked.astype(dtype), xk, (1, 0)), y)
        y = y + _dot(cg, before.astype(dtype), (1, 1)) * jnp.exp(_tile_of([hd.a for hd in heads], geom))
        y = y + xf * d_ref[pl.ds(k, 1), :]
        y_ref[0, :, pl.ds(off, w)] = y.astype(dtype)
        new = _dot((xf * _tile_of([hd.to_end * hd.dt for hd in heads], geom)).astype(dtype), bg, (0, 0))
        for j, hd in enumerate(heads):
            rows = slice(j * p, (j + 1) * p)
            state[k, rows, :] = _across(jnp.exp(hd.a_last), n) * before[rows] + new[rows]

    def group(gi, _):
        off = pl.multiple_of(gi * n, _LANES)
        bg, cg = b_ref[0, :, pl.ds(off, n)], c_ref[0, :, pl.ds(off, n)]
        scores = _dot(cg, bg, (1, 1))
        first = gi * geom.tiles_per_group
        _unrolled(geom.tiles_per_group, lambda ti, c: (tile(first + ti, bg, cg, scores), c)[1], 0)
        return 0

    _unrolled(geom.groups, group, 0)


def _bwd_kernel(
    x_ref, hm_ref, b_ref, c_ref, d_ref, dy_ref, s_ref,
    dx_ref, db_ref, dc_ref, dhm_ref, dd_ref, dstate,
    *, geom: Geometry,
):
    """One chunk of one sequence in the reverse sweep; ``dstate`` holds
    the cotangent of the state that leaves this chunk. Sums over a
    head's lanes and over a mask's rows are products with 0/1 rows on
    the MXU (operands in ``x``'s type): they come out as rows."""
    h, q, w, n, p = geom.heads, geom.chunk, geom.width, geom.state, geom.head_dim
    dtype = x_ref.dtype
    f32 = jnp.float32
    lower = _lower(q)
    ones = jnp.ones((8, q), dtype)
    # row j: 1 on the lanes of the tile's head j
    of_heads = (_iota((8, w), 1) // p == _iota((8, w), 0)).astype(dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def tile(k, bg, cg, scores, acc):
        dscores, dbg, dcg = acc
        off = pl.multiple_of(k * w, _LANES)
        xk, dyk = x_ref[0, :, pl.ds(off, w)], dy_ref[0, :, pl.ds(off, w)]
        xf, dyf = xk.astype(f32), dyk.astype(f32)
        before, leaving = s_ref[0, 0, k], dstate[k]
        before_c, leaving_c = before.astype(dtype), leaving.astype(dtype)
        heads = [_head(k * geom.heads_per_tile + j, hm_ref, lower, geom) for j in range(geom.heads_per_tile)]
        grow = jnp.exp(_tile_of([hd.a for hd in heads], geom))
        to_end = _tile_of([hd.to_end * hd.dt for hd in heads], geom)

        dd_ref[0, pl.ds(k, 1), :] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        # y = ... + exp(a) C S_before: to C, to the entry state, to a (a row a head)
        dgrown = (dyf * grow).astype(dtype)
        dcg = dcg + _dot(dgrown, before_c, (1, 0))
        dbefore = _dot(dgrown, cg, (0, 0))
        da_grow = _dot(of_heads, (dyf * grow * _dot(cg, before_c, (1, 1))).astype(dtype), (1, 1))
        # S_leaving = exp(a_last) S_before + (x to_end)^T B
        dxs = _dot(bg, leaving_c, (1, 1))
        dbg = dbg + _dot((xf * to_end).astype(dtype), leaving_c, (1, 0))
        dxk = dyf * d_ref[pl.ds(k, 1), :] + dxs * to_end
        dto_end = _dot(of_heads, (dxs * xf).astype(dtype), (1, 1))
        kept_moved = leaving * before

        dx_mask = jnp.zeros((q, w), f32)
        for j, hd in enumerate(heads):
            i = k * geom.heads_per_tile + j
            rows = slice(j * p, (j + 1) * p)
            # y = (decay o scores o dt) x + ...
            weighted = hd.decay * scores
            masked = weighted * hd.dt
            mine = _lanes_of(j, (q, w), geom)
            dmasked = _dot(jnp.where(mine, dyk, jnp.zeros_like(dyk)), xk, (1, 1))
            dx_mask = jnp.where(mine, _dot(masked.astype(dtype), dyk, (0, 0)), dx_mask)
            dscores = dscores + dmasked * (hd.decay * hd.dt)
            ddt = jnp.sum(dmasked * weighted, axis=0, keepdims=True)
            # d decay o decay: to a_i along its rows, from a_j down its columns; both sums
            # of the same rounded numbers, so what cancels in a's running sum cancels
            through = (dmasked * masked).astype(dtype)
            da = (_dot(ones, through, (1, 1)) - _dot(ones, through, (1, 0)))[:1] + da_grow[j:j + 1]
            # to_end = exp(a_last - a) dt
            moved = dto_end[j:j + 1] * hd.to_end * hd.dt
            kept = _across(jnp.exp(hd.a_last), n)
            dkept = kept * jnp.sum(kept_moved[rows], axis=0, keepdims=True)  # [1, n]
            dkept = sum(dkept[:, i:i + _LANES] for i in range(0, n, _LANES))
            dhm_ref[0, pl.ds(i, 1), :] = da - moved
            dhm_ref[0, pl.ds(h + i, 1), :] = ddt + dto_end[j:j + 1] * hd.to_end
            # a_last's: whatever row sums to it (the wrapper adds the lanes up)
            dhm_ref[0, pl.ds(2 * h + i, 1), :] = moved + jnp.pad(dkept, ((0, 0), (0, q - _LANES)))
            dstate[k, rows, :] = kept * leaving[rows] + dbefore[rows]

        dx_ref[0, :, pl.ds(off, w)] = (dxk + dx_mask).astype(dtype)
        return dscores, dbg, dcg

    def group(gi, _):
        off = pl.multiple_of(gi * n, _LANES)
        bg, cg = b_ref[0, :, pl.ds(off, n)], c_ref[0, :, pl.ds(off, n)]
        scores = _dot(cg, bg, (1, 1))
        first = gi * geom.tiles_per_group
        zeros = (jnp.zeros((q, q), f32), jnp.zeros((q, n), f32), jnp.zeros((q, n), f32))
        dscores, dbg, dcg = _unrolled(
            geom.tiles_per_group, lambda ti, acc: tile(first + ti, bg, cg, scores, acc), zeros)
        dscores = dscores.astype(dtype)
        db_ref[0, :, pl.ds(off, n)] = (dbg + _dot(dscores, cg, (0, 0))).astype(dtype)
        dc_ref[0, :, pl.ds(off, n)] = (dcg + _dot(dscores, bg, (1, 0))).astype(dtype)
        return 0

    _unrolled(geom.groups, group, 0)


def _specs(geom: Geometry, chunks: int, reverse: bool):
    """Block specs over the grid (sequence, chunk); the reverse sweep
    visits the chunks from the last to the first."""
    h, p, g, n, q = geom
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    tokens = lambda width: pl.BlockSpec((1, q, width), lambda i, c: (i, at(c), 0))
    return dict(
        x=tokens(h * p), bc=tokens(g * n),
        hm=pl.BlockSpec((1, 3 * h, q), lambda i, c: (i, 0, at(c))),
        d=pl.BlockSpec((geom.tiles, geom.width), lambda i, c: (0, 0)),
        dd=pl.BlockSpec((1, geom.tiles, geom.width), lambda i, c: (i, 0, 0)),
        states=pl.BlockSpec((1, 1, geom.tiles, geom.width, n), lambda i, c: (i, at(c), 0, 0, 0)),
    )


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES)


def _fwd_call(x, hm, b, c, d, *, geom: Geometry, emit: bool, interpret: bool):
    bt, t, _ = x.shape
    chunks = t // geom.chunk
    s = _specs(geom, chunks, reverse=False)
    out_specs, out_shape = [s["x"]], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if emit:
        out_specs.append(s["states"])
        out_shape.append(jax.ShapeDtypeStruct((bt, chunks, geom.tiles, geom.width, geom.state), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, geom=geom, emit=emit),
        grid=(bt, chunks),
        in_specs=[s["x"], s["hm"], s["bc"], s["bc"], s["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((geom.tiles, geom.width, geom.state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_fwd",
    )(x, hm, b, c, d)


def _bwd_call(x, hm, b, c, d, dy, states, *, geom: Geometry, interpret: bool):
    bt, t, _ = x.shape
    chunks = t // geom.chunk
    s = _specs(geom, chunks, reverse=True)
    like = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, geom=geom),
        grid=(bt, chunks),
        in_specs=[s["x"], s["hm"], s["bc"], s["bc"], s["d"], s["x"], s["states"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["hm"], s["dd"]],
        out_shape=[like(x), like(b), like(c), like(hm),
                   jax.ShapeDtypeStruct((bt, geom.tiles, geom.width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((geom.tiles, geom.width, geom.state), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_bwd",
    )(x, hm, b, c, d, dy, states)


def _on_platform(call, *operands):
    """Compiled on the chip, interpreted on the CPU (tests); no default
    branch, so lowering for any other platform raises."""
    return jax.lax.platform_dependent(
        *operands, cpu=functools.partial(call, interpret=True), tpu=functools.partial(call, interpret=False))


def _scalars(dt, a_head, geom: Geometry):
    """``a`` (the chunk's running sum of ``dt A``, its own step
    included) over ``dt`` over ``a_last`` (a chunk's last ``a`` at each
    of its tokens), head-major [Bt, 3H, T], float32."""
    bt, t, h = dt.shape
    a = jnp.cumsum(dt.reshape(bt, t // geom.chunk, geom.chunk, h) * a_head, axis=2)
    rows = jnp.concatenate([a.reshape(bt, t, h), dt, jnp.broadcast_to(a[:, :, -1:], a.shape).reshape(bt, t, h)], axis=-1)
    return jnp.swapaxes(rows, 1, 2)


def _skip_lanes(d_head, geom: Geometry):
    """``D`` a lane of every tile, [tiles, W]."""
    return jnp.repeat(d_head, geom.head_dim).reshape(geom.tiles, geom.width)


@functools.partial(jax.jit, static_argnames=("geom", "emit"))
def _forward(x, dt, a_head, b, c, d_head, *, geom: Geometry, emit: bool):
    call = functools.partial(_fwd_call, geom=geom, emit=emit)
    return _on_platform(call, x, _scalars(dt, a_head, geom), b, c, _skip_lanes(d_head, geom))


@functools.partial(jax.jit, static_argnames=("geom",))
def _backward(x, dt, a_head, b, c, d_head, states, dy, *, geom: Geometry):
    bt, t, h = dt.shape
    call = functools.partial(_bwd_call, geom=geom)
    dx, db, dc, dhm, dd = _on_platform(
        call, x, _scalars(dt, a_head, geom), b, c, _skip_lanes(d_head, geom), dy, states)
    da, ddt, da_last = jnp.split(jnp.swapaxes(dhm, 1, 2).reshape(bt, -1, geom.chunk, 3 * h), 3, axis=-1)
    da = da.at[:, :, -1].add(jnp.sum(da_last, axis=2))
    ddt = ddt.reshape(bt, t, h)
    # a is the chunk's running sum of dt A: a step's dt A reaches every later a of its chunk
    reach = jnp.flip(jnp.cumsum(jnp.flip(da, 2), axis=2), 2).reshape(bt, t, h)
    return (
        dx, ddt + reach * a_head, jnp.sum(reach * dt, axis=(0, 1)), db, dc,
        jnp.sum(dd.reshape(bt, h, geom.head_dim), axis=(0, 2)),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, a_head, b, c, d_head, geom: Geometry):
    """``ssd.ssd_scan_jnp`` on flattened operands: ``x`` [Bt, T, H P],
    ``dt`` [Bt, T, H] float32, ``b`` / ``c`` [Bt, T, G N], ``a_head``
    / ``d_head`` [H] float32, ``T`` whole chunks; ``takes`` decides
    whether the shape may come here. Returns ``y`` like ``x``."""
    (y,) = _forward(x, dt, a_head, b, c, d_head, geom=geom, emit=False)
    return y


def _scan_fwd(x, dt, a_head, b, c, d_head, geom):
    y, states = _forward(x, dt, a_head, b, c, d_head, geom=geom, emit=True)
    return y, (x, dt, a_head, b, c, d_head, states)


def _scan_bwd(geom, res, dy):
    return _backward(*res, dy, geom=geom)


scan.defvjp(_scan_fwd, _scan_bwd)
