"""Plain reference: a GPT-2 decoder trained with Adam, float32.

Independent of ``fedml_tpu``. Follows Radford et al. 2019 (GPT-2) for
the block -- pre-LayerNorm, fused QKV projection, causal softmax
attention with scale 1/sqrt(head_dim), a 4x MLP with GELU, learned
absolute positions, a final LayerNorm -- and Kingma & Ba 2015 for Adam
(b1 0.9, b2 0.999, eps 1e-8, bias-corrected, eps outside the root's
correction as optax and PyTorch have it).

Departures from the published model, shared with the program under test
and listed in the configuration file: the output head is a matrix of
its own with a bias (not the transposed embedding), GELU is the tanh
form, LayerNorm's epsilon is 1e-6, there is no dropout, and the weights
are random: N(0, 0.02) matrices and embeddings, unit scales, zero
biases, drawn here from the seed.

Attention is the dense [T, T] softmax, one sequence at a time, so no
kernel, cache or batching trick is shared with the program. Parameter
names mirror the flax tree of ``models/transformer.py`` because that
tree is the program's interface for handing weights over.

``quant`` is the control's hook (see ``controls.py``): every matrix
product in a lower precision (``quant.operand`` on its operands,
``quant.grad`` on its result). The reference passes None.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def param_shapes(m: dict) -> dict:
    c, v = m["n_embd"], m["vocab_size"]
    ln = {"scale": (c,), "bias": (c,)}
    tree = {"Embed_0": {"embedding": (v, c)}, "Embed_1": {"embedding": (m["n_positions"], c)}}
    for i in range(m["n_layer"]):
        tree[f"Block_{i}"] = {
            "LayerNorm_0": dict(ln),
            "Dense_0": {"kernel": (c, 3 * c), "bias": (3 * c,)},
            "Dense_1": {"kernel": (c, c), "bias": (c,)},
            "LayerNorm_1": dict(ln),
            "Dense_2": {"kernel": (c, 4 * c), "bias": (4 * c,)},
            "Dense_3": {"kernel": (4 * c, c), "bias": (c,)},
        }
    tree["LayerNorm_0"] = dict(ln)
    tree["Dense_0"] = {"kernel": (c, v), "bias": (v,)}
    return tree


def init_params(seed: int, m: dict):
    """All leaves in one jitted call from the seed, float32."""
    shapes = param_shapes(m)
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            leaf = path[-1].key
            if leaf in ("kernel", "embedding"):
                out.append(0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
            elif leaf == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        return out

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed % (2 ** 31))))


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p, quant):
    k = p["kernel"]
    if quant is not None:
        x, k = quant.operand(x), quant.operand(k)
    y = jnp.dot(x, k, precision=HIGHEST)
    return (y if quant is None else quant.grad(y)) + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward_row(params, tokens, m: dict, quant=None):
    """One sequence [T] of ids -> logits [T, vocab]."""
    t, c, h = tokens.shape[0], m["n_embd"], m["n_head"]
    x = params["Embed_0"]["embedding"][tokens] + params["Embed_1"]["embedding"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(m["n_layer"]):
        p = params[f"Block_{i}"]
        qkv = _dense(_layer_norm(x, p["LayerNorm_0"]), p["Dense_0"], quant)
        q, k, v = (a.reshape(t, h, c // h) for a in jnp.split(qkv, 3, axis=-1))
        if quant is not None:
            q, k = quant.operand(q), quant.operand(k)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * ((c // h) ** -0.5)
        if quant is not None:
            s = quant.grad(s)
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        if quant is not None:
            pr, v = quant.operand(pr), quant.operand(v)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
        if quant is not None:
            o = quant.grad(o)
        o = o.reshape(t, c)
        x = x + _dense(o, p["Dense_1"], quant)
        hdn = _gelu_tanh(_dense(_layer_norm(x, p["LayerNorm_1"]), p["Dense_2"], quant))
        x = x + _dense(hdn, p["Dense_3"], quant)
    return _dense(_layer_norm(x, params["LayerNorm_0"]), params["Dense_0"], quant)


def _row_loss_sum(params, tokens, targets, m, quant):
    logits = forward_row(params, tokens, m, quant)
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits), targets[:, None], axis=1)[:, 0]
    return -ll.sum()


@functools.lru_cache(maxsize=None)
def _step_fn(m_key, lr: float, quant, row_keep: int):
    m = dict(m_key)

    def step(params, mu, nu, count, x, y):
        """One Adam step on a batch x, y [B, T]: the mean token loss
        and its gradient, a row at a time."""
        rows = x.shape[0]
        if row_keep:
            # fault plant: every ``row_keep``-th row only, mean over those
            x, y = x[::row_keep], y[::row_keep]
            rows = x.shape[0]
        n_tok = rows * x.shape[1]

        def one(carry, row):
            ls, g = jax.value_and_grad(_row_loss_sum)(params, row[0], row[1], m, quant)
            return (carry[0] + ls, jax.tree.map(jnp.add, carry[1], g)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss_sum, g), _ = jax.lax.scan(one, (jnp.float32(0.0), zeros), (x, y))
        g = jax.tree.map(lambda a: a / n_tok, g)
        count = count + 1
        mu = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, mu, g)
        nu = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, nu, g)
        c1, c2 = 1 - B1 ** count, 1 - B2 ** count
        params = jax.tree.map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS), params, mu, nu)
        return params, mu, nu, count, loss_sum / n_tok

    return jax.jit(step, donate_argnums=(0, 1, 2))


def _freeze(m: dict):
    return tuple(sorted(m.items()))


def train_epoch(params, batches, m: dict, lr: float, quant=None, row_keep: int = 0):
    """Adam from a fresh state over ``batches`` = (x, y) [nb, B, T] in
    stored order. Returns the weights, Adam's first moment, and each
    step's loss (taken before its update)."""
    step = _step_fn(_freeze(m), float(lr), quant, int(row_keep))
    x, y = batches
    params = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.float32(0.0)
    losses = []
    for i in range(x.shape[0]):
        params, mu, nu, count, loss = step(params, mu, nu, count, x[i], y[i])
        losses.append(loss)
    return params, mu, [float(v) for v in losses]


@functools.lru_cache(maxsize=None)
def _eval_fn(m_key, quant):
    m = dict(m_key)

    def ev(params, x, y):
        def one(carry, row):
            return carry + _row_loss_sum(params, row[0], row[1], m, quant), None

        total, _ = jax.lax.scan(one, jnp.float32(0.0), (x, y))
        return total

    return jax.jit(ev)


def evaluate(params, batches, m: dict, quant=None) -> float:
    """Mean token loss over (x, y) [nb, B, T]."""
    fn = _eval_fn(_freeze(m), quant)
    x, y = batches
    total = sum(fn(params, x[i], y[i]) for i in range(x.shape[0]))
    return float(total) / float(x.shape[0] * x.shape[1] * x.shape[2])
