"""The rotary embedding's rotate-half (``models/decoder.apply_rope``).

The product form (``decoder._rope_bf16``: a bfloat16 operand rotated
by a product with a signed permutation and differentiated by its own
backward) has to give, bit for bit, what the concatenated float32 form
below gives and what autodiff of it gives, at either head width;
``apply_rope`` takes it for a bfloat16 operand whose heads are a
multiple of 128 wide, and the concatenated form for any other. The
comparisons run op by op (no ``jit``): inside a fused loop XLA:CPU
contracts ``a * b + c * d`` into a fused multiply-add, and does so
differently for the two forms, so a jitted comparison on the CPU would
read the CPU compiler's contraction, not the rotation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.decoder import _rope_bf16, apply_rope, rope_tables

T = 256
TABLES = {  # the cells' kinds of table: default (Mellum2's window, LFM2) and YaRN (Mellum2's full)
    "default": {"rope_type": "default", "rope_theta": 500000},
    "yarn": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
             "attention_factor": 1.2772588722239782},
}
CASES = [(d, table, vmapped) for d in (64, 128) for table in TABLES for vmapped in (False, True)]
IDS = [f"d{d}-{table}-{'vmap' if vmapped else 'plain'}" for d, table, vmapped in CASES]


def concatenated(x, cos, sin):
    """The rotation as it was written before the product form: upcast,
    concatenate the negated halves, and round once."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None] + rotated * sin[None, :, None]).astype(x.dtype)


def _operands(d, table, vmapped, dtype=jnp.bfloat16):
    cos, sin = rope_tables(T, d, TABLES[table])
    shape = ((3,) if vmapped else ()) + (2, T, 4, d)
    kx, kg = jax.random.split(jax.random.PRNGKey(d))
    x = jax.random.normal(kx, shape).astype(dtype)
    g = jax.random.normal(kg, shape).astype(dtype)
    return x, g, cos, sin


def _vjp(form, x, g, cos, sin, vmapped):
    f = lambda x: form(x, cos, sin)
    out, pullback = jax.vjp(jax.vmap(f) if vmapped else f, x)
    return out, pullback(g)[0]


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("d,table,vmapped", CASES, ids=IDS)
def test_bf16_forward_is_bit_identical(d, table, vmapped):
    x, g, cos, sin = _operands(d, table, vmapped)
    want, _ = _vjp(concatenated, x, g, cos, sin, vmapped)
    got, _ = _vjp(_rope_bf16, x, g, cos, sin, vmapped)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d,table,vmapped", CASES, ids=IDS)
def test_bf16_cotangent_is_bit_identical(d, table, vmapped):
    x, g, cos, sin = _operands(d, table, vmapped)
    _, want = _vjp(concatenated, x, g, cos, sin, vmapped)
    _, got = _vjp(_rope_bf16, x, g, cos, sin, vmapped)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d", (64, 128))
def test_bf16_cotangent_with_unlike_halves(d):
    """Tables whose two halves differ (not ``rope_tables``'): the
    backward still carries each term to its own column."""
    x, g, _, _ = _operands(d, "default", False)
    kc, ks = jax.random.split(jax.random.PRNGKey(7))
    cos, sin = jax.random.normal(kc, (T, d)), jax.random.normal(ks, (T, d))
    _, want = _vjp(concatenated, x, g, cos, sin, False)
    _, got = _vjp(_rope_bf16, x, g, cos, sin, False)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d,table,vmapped", CASES, ids=IDS)
def test_float32_operand_keeps_concatenated_form(d, table, vmapped):
    """A one-pass product would round a float32 operand: it is rotated
    as before, and its results and cotangents are unchanged, jitted too."""
    x, g, cos, sin = _operands(d, table, vmapped, jnp.float32)
    for wrap in (lambda f: f, jax.jit):
        want = wrap(lambda x, g: _vjp(concatenated, x, g, cos, sin, vmapped))(x, g)
        got = wrap(lambda x, g: _vjp(apply_rope, x, g, cos, sin, vmapped))(x, g)
        for a, b in zip(got, want):
            assert a.dtype == jnp.float32
            np.testing.assert_array_equal(_bits(a), _bits(b))
    prims = _primitives(jax.make_jaxpr(lambda x: apply_rope(x, cos, sin))(x[0] if vmapped else x).jaxpr)
    assert "concatenate" in {p for p, _, _ in prims} and "dot_general" not in {p for p, _, _ in prims}


def _primitives(jaxpr):
    """``(primitive, output dtype, output shape)`` of every equation,
    sub-jaxprs (the custom rule's, a ``pjit``'s) included."""
    found = []
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, v.aval.dtype, v.aval.shape) for v in eqn.outvars]
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    found += _primitives(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    found += _primitives(sub)
    return found


def _vjp_primitives(d, table, vmapped):
    x, g, cos, sin = _operands(d, table, vmapped)
    jaxpr = jax.make_jaxpr(lambda x, g: _vjp(apply_rope, x, g, cos, sin, vmapped))(x, g).jaxpr
    return x, _primitives(jaxpr)


WIDE = [case for case in CASES if case[0] % 128 == 0]
NARROW = [case for case in CASES if case[0] % 128]


@pytest.mark.parametrize("d,table,vmapped", WIDE, ids=[IDS[CASES.index(c)] for c in WIDE])
def test_bf16_rotation_writes_no_float32_copy(d, table, vmapped):
    """Neither the rotation nor its VJP concatenates float32 halves of
    the operand: the one float32 concatenate there is swaps the
    [T, head_dim] sine table's halves in the backward."""
    x, prims = _vjp_primitives(d, table, vmapped)
    f32_concats = [shape for p, dtype, shape in prims if p == "concatenate" and dtype == jnp.float32]
    assert f32_concats == [(T, d)]
    # the rotate-half is the two products, forward and transposed
    products = [shape for p, dtype, shape in prims if p == "dot_general"]
    assert len(products) == 2 and all(s == x.shape for s in products)


@pytest.mark.parametrize("d,table,vmapped", NARROW, ids=[IDS[CASES.index(c)] for c in NARROW])
def test_narrow_bf16_head_keeps_concatenated_form(d, table, vmapped):
    """A head narrower than 128 is rotated as before: no product."""
    x, prims = _vjp_primitives(d, table, vmapped)
    assert (np.dtype(jnp.float32), x.shape) in {(dtype, shape) for p, dtype, shape in prims if p == "concatenate"}
    assert "dot_general" not in {p for p, _, _ in prims}


def test_attention_layer_at_head_128_is_unchanged(monkeypatch):
    """A whole attention layer (q/k norm, rotation, dense attention, the
    output projection) at head width 128 in bfloat16: its output and
    every gradient are what they were with the concatenated rotation."""
    from fedml_tpu.models import decoder

    attn = decoder.Attention(num_heads=2, num_kv_heads=1, head_dim=128, window=None, impl="full", eps=1e-6)
    cos, sin = rope_tables(32, 128, TABLES["yarn"])
    kx, kp = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (2, 32, 64)).astype(jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), attn.init(kp, x, cos, sin))

    def run():
        loss = lambda p, x: attn.apply(p, x, cos, sin).astype(jnp.float32).sum()
        return attn.apply(params, x, cos, sin), jax.grad(loss, argnums=(0, 1))(params, x)

    assert "custom_vjp_call" in str(jax.make_jaxpr(lambda x: attn.apply(params, x, cos, sin))(x))
    got = run()
    monkeypatch.setattr(decoder, "apply_rope", concatenated)
    want = run()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(_bits(a), _bits(b))
