"""``ops/ssd.py``'s chunked scan against the step-by-step recurrence and
against the plain reference's dense dual form
(``benchmark/reference/fedavg_twotower.py``), values and gradients."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.ssd import num_chunks, ssd_scan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ref_fedavg_twotower_scan", os.path.join(REPO, "benchmark", "reference", "fedavg_twotower.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

BT, H, P, G, N = 2, 4, 8, 2, 16


def _inputs(t, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (BT, t, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (BT, t, H)) - 1.0)
    a_head = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (BT, t, G, N)).astype(dtype)
    c = jax.random.normal(k[4], (BT, t, G, N)).astype(dtype)
    d_head = 1.0 + 0.1 * jax.random.normal(k[5], (H,))
    return x, dt, a_head, b, c, d_head


def recurrence(x, dt, a_head, b, c, d_head):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
    + D x_t``, a token at a time; head ``h`` reads group ``h // (H / G)``."""
    per = x.shape[2] // b.shape[2]
    bh, ch = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)

    def step(s, inputs):
        xt, dtt, bt, ct = inputs
        s = jnp.exp(dtt * a_head)[..., None, None] * s + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct) + d_head[:, None] * xt

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, ys = jax.lax.scan(step, start, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bh, ch)))
    return jnp.moveaxis(ys, 0, 1)


def dual(x, dt, a_head, b, c, d_head):
    """The reference's dense dual form, a sequence at a time."""
    return jnp.stack([ref._ssm_dual(x[i], dt[i], a_head, b[i], c[i], d_head, 8, None) for i in range(x.shape[0])])


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


# (T, chunk): whole chunks, one chunk, a chunk that does not divide T
# (the tail is padded with steps of dt = 0), a chunk longer than T
SHAPES = [(64, 16), (32, 32), (50, 16), (24, 32)]


@pytest.mark.parametrize("other", [recurrence, dual], ids=["recurrence", "dual_form"])
@pytest.mark.parametrize("t,chunk", SHAPES)
def test_values(t, chunk, other):
    args = _inputs(t)
    with jax.default_matmul_precision("highest"):
        got, want = ssd_scan(*args, chunk), other(*args)
    assert got.shape == want.shape == (BT, t, H, P) and got.dtype == jnp.float32
    assert _close(got, want, 2e-5)


@pytest.mark.parametrize("other", [recurrence, dual], ids=["recurrence", "dual_form"])
@pytest.mark.parametrize("t,chunk", SHAPES[:3])
def test_gradients(t, chunk, other):
    args = _inputs(t, seed=1)
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *a: ssd_scan(*a, chunk)), argnums=range(6))(*args)
        want = jax.grad(loss(other), argnums=range(6))(*args)
    bad = [i for i, (a, b) in enumerate(zip(got, want)) if not _close(a, b, 2e-4)]
    assert not bad, bad


def test_the_carried_state_crosses_chunks():
    """An input in the first chunk is felt in the last: zeroing the
    first chunk's tokens changes the last chunk's outputs."""
    x, dt, a_head, b, c, d_head = _inputs(64)
    a_head = a_head * 0.01  # slow decay
    full = ssd_scan(x, dt, a_head, b, c, d_head, 16)
    cut = ssd_scan(x.at[:, :16].set(0.0), dt, a_head, b, c, d_head, 16)
    assert not _close(cut[:, 48:], full[:, 48:], 1e-3)


def test_bfloat16_operands_float32_state():
    """In bfloat16 the products take bfloat16 operands; the result is
    the float32 one to bfloat16's rounding, not worse (the decays, the
    running sums and the carried state stay float32)."""
    x, dt, a_head, b, c, d_head = _inputs(64, dtype=jnp.bfloat16)
    got = ssd_scan(x, dt, a_head, b, c, d_head, 16)
    want = recurrence(x.astype(jnp.float32), dt, a_head, b.astype(jnp.float32), c.astype(jnp.float32), d_head)
    assert got.dtype == jnp.bfloat16
    assert _close(got.astype(jnp.float32), want, 3e-2)


def test_vmapped_lanes_and_chunk_count():
    args = _inputs(32)
    lanes = jax.vmap(lambda x, dt, b, c: ssd_scan(x, dt, args[2], b, c, args[5], 8))(
        *(jnp.stack([v, v]) for v in (args[0], args[1], args[3], args[4])))
    assert _close(lanes[1], ssd_scan(*args, 8), 1e-6)
    assert num_chunks(32, 8) == 4 and num_chunks(50, 16) == 4 and num_chunks(8192, 128) == 64


@pytest.mark.parametrize("bad", ["groups", "chunk"])
def test_shapes_it_cannot_scan_are_refused(bad):
    x, dt, a_head, b, c, d_head = _inputs(16)
    with pytest.raises(ValueError, match="no such scan"):
        if bad == "groups":
            ssd_scan(x, dt, a_head, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), d_head, 8)
        else:
            ssd_scan(x, dt, a_head, b, c, d_head, 0)
