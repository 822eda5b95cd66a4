"""Plain reference: FedAvg rounds of a GroupNorm ResNet, float32.

Independent of ``fedml_tpu``: nothing here imports the program or takes
anything the program has made. It follows the published descriptions

- ResNet basic blocks (He et al. 2016) with GroupNorm for BatchNorm
  (Wu & He 2018; Hsieh et al. 2020 for the federated substitution), the
  CIFAR stem (3x3, stride 1, no max-pool), NHWC;
- FedAvg (McMahan et al. 2017): every sampled client runs E epochs of
  mini-batch SGD from the global weights over its own samples in their
  stored order, the server takes the sample-weighted mean;
- FedML's cohort rule (``FedAVGAggregator.client_sampling``):
  ``np.random.seed(round_idx)`` then ``choice(range(N), K, replace=False)``.

Departures from the papers, shared with the program under test and
listed in the configuration file: weights are random (lecun-normal
kernels, unit scales, zero biases) drawn here from the seed, the data is
a seeded stand-in, a client's last batch is the partial one (mean over
its real rows), and a client whose samples end before ``num_batches``
takes no further step.

Parameter names mirror the flax tree of ``models/resnet.py`` because
that tree is the program's interface for handing weights over; the
harness refuses to run when structure or shapes differ.

``quant`` is the hook the *control* uses: the same code with every
convolution and matrix product computed in a lower precision
(``quant.operand`` on its operands, ``quant.grad`` on its result; see
``controls.py``). The reference itself passes ``None``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
GN_EPS = 1e-6


# -- weights -----------------------------------------------------------
def _block_plan(model: dict):
    """[(name, in_ch, out_ch, stride, has_projection)] in forward order."""
    plan, cin, k = [], model["stage_channels"][0], 0
    for i, (size, ch) in enumerate(zip(model["stage_sizes"], model["stage_channels"])):
        for j in range(size):
            stride = 2 if (i > 0 and j == 0) else 1
            plan.append((f"BasicBlock_{k}", cin, ch, stride, stride != 1 or cin != ch))
            cin, k = ch, k + 1
    return plan


def param_shapes(model: dict) -> dict:
    """The tree of shapes, from the configuration's sizes alone."""
    ks, c0 = model["stem_kernel"], model["stage_channels"][0]
    gn = lambda c: {"scale": (c,), "bias": (c,)}
    tree = {
        "Conv_0": {"kernel": (ks, ks, model["image"][2], c0)},
        "GroupNorm_0": gn(c0),
    }
    for name, cin, ch, _, proj in _block_plan(model):
        blk = {
            "Conv_0": {"kernel": (3, 3, cin, ch)},
            "GroupNorm_0": gn(ch),
            "Conv_1": {"kernel": (3, 3, ch, ch)},
            "GroupNorm_1": gn(ch),
        }
        if proj:
            blk["Conv_2"] = {"kernel": (1, 1, cin, ch)}
            blk["GroupNorm_2"] = gn(ch)
        tree[name] = blk
    tree["Dense_0"] = {
        "kernel": (model["stage_channels"][-1], model["classes"]),
        "bias": (model["classes"],),
    }
    return tree


def init_params(seed: int, model: dict):
    """All leaves in one jitted call from the seed, float32 on the
    device: lecun-normal kernels (std 1/sqrt(fan_in)), GroupNorm scales
    ``model["init_gn_scale"]`` (1 where it is not given), biases 0."""
    gn_scale = float(model.get("init_gn_scale", 1.0))
    shapes = param_shapes(model)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [
        p for p, _ in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    ]

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            leaf = path[-1].key
            if leaf == "kernel":
                fan_in = int(np.prod(shape[:-1]))
                out.append(
                    jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                    * (fan_in ** -0.5)
                )
            elif leaf == "scale":
                out.append(jnp.full(shape, gn_scale, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        return out

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed % (2 ** 31))))


# -- forward -----------------------------------------------------------
def _conv(x, w, stride, quant):
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )
    return y if quant is None else quant.grad(y)


def _group_norm(x, p, groups):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + GN_EPS)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def forward(params, x, model: dict, quant=None):
    """Images [N, H, W, 3] -> logits [N, classes]."""
    groups = model["num_groups"]
    x = _conv(x.astype(jnp.float32), params["Conv_0"]["kernel"], 1, quant)
    x = jax.nn.relu(_group_norm(x, params["GroupNorm_0"], groups))
    for name, _, _, stride, proj in _block_plan(model):
        p = params[name]
        y = _conv(x, p["Conv_0"]["kernel"], stride, quant)
        y = jax.nn.relu(_group_norm(y, p["GroupNorm_0"], groups))
        y = _conv(y, p["Conv_1"]["kernel"], 1, quant)
        y = _group_norm(y, p["GroupNorm_1"], groups)
        if proj:
            x = _conv(x, p["Conv_2"]["kernel"], stride, quant)
            x = _group_norm(x, p["GroupNorm_2"], groups)
        x = jax.nn.relu(y + x)
    x = x.mean(axis=(1, 2))
    k, b = params["Dense_0"]["kernel"], params["Dense_0"]["bias"]
    if quant is not None:
        x, k = quant.operand(x), quant.operand(k)
    y = jnp.dot(x, k, precision=HIGHEST)
    return (y if quant is None else quant.grad(y)) + b


def _batch_loss(params, x, y, mask, model, quant):
    """Mean cross-entropy over the real rows, and their count."""
    logits = forward(params, x, model, quant)
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], axis=1)[:, 0]
    count = mask.sum()
    return -(ll * mask).sum() / jnp.maximum(count, 1.0), count


# -- one client, one round, one evaluation -----------------------------
@functools.lru_cache(maxsize=None)
def _client_update_fn(model_key, lr: float, epochs: int, quant, row_keep: int):
    model = dict(model_key)
    model = {k: (list(v) if isinstance(v, tuple) else v) for k, v in model.items()}

    def client_update(params, x, y, mask):
        """x [nb, bs, H, W, 3], y [nb, bs], mask [nb, bs]. Returns the
        client's weights after its epochs and the last epoch's summed
        loss and count (each batch's loss taken before its step)."""
        if row_keep:
            # fault plant (tests and limit readings only): keep every
            # ``row_keep``-th row of each batch, the mean over the rest
            keep = (jnp.arange(mask.shape[1]) % row_keep == 0).astype(mask.dtype)
            mask = mask * keep[None, :]

        def step(p, batch):
            bx, by, bm = batch

            def real(p):
                (loss, count), g = jax.value_and_grad(
                    _batch_loss, has_aux=True)(p, bx, by, bm, model, quant)
                return jax.tree.map(lambda a, b: a - lr * b, p, g), loss * count, count

            def empty(p):
                return p, jnp.float32(0.0), jnp.float32(0.0)

            p, ls, c = jax.lax.cond(bm.sum() > 0, real, empty, p)
            return p, (ls, c)

        for _ in range(epochs):
            params, (ls, c) = jax.lax.scan(step, params, (x, y, mask))
        return params, ls.sum(), c.sum()

    return jax.jit(client_update)


def _freeze(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in model.items()))


def sample_cohort(round_idx: int, clients: int, per_round: int) -> np.ndarray:
    if clients == per_round:
        return np.arange(clients, dtype=np.int32)
    rs = np.random.RandomState(round_idx)
    return np.asarray(rs.choice(range(clients), per_round, replace=False), np.int32)


def fedavg_round(params, packed, nsamples, cohort, model, fed, quant=None, row_keep=0):
    """One FedAvg round over ``cohort`` (client indices). ``packed`` is
    (x [C, nb, bs, ...], y, mask). Clients run one after another so
    only one client's activations live at a time. Returns the new
    global weights and the cohort's mean training loss."""
    update = _client_update_fn(
        _freeze(model), float(fed["lr"]), int(fed["epochs"]), quant, int(row_keep))
    x, y, mask = packed
    ns = np.asarray(nsamples, np.float64)[cohort]
    weights = ns / max(ns.sum(), 1.0)
    acc, loss_sum, count = None, 0.0, 0.0
    for w, c in zip(weights, cohort):
        new, ls, cnt = update(params, x[c], y[c], mask[c])
        term = jax.tree.map(lambda a: a * jnp.float32(w), new)
        acc = term if acc is None else jax.tree.map(jnp.add, acc, term)
        loss_sum, count = loss_sum + ls, count + cnt
    return acc, float(loss_sum) / max(float(count), 1.0)


@functools.lru_cache(maxsize=None)
def _eval_fn(model_key, quant):
    model = {k: (list(v) if isinstance(v, tuple) else v) for k, v in dict(model_key).items()}

    def evaluate(params, x, y, mask):
        def step(carry, batch):
            bx, by, bm = batch
            loss, count = _batch_loss(params, bx, by, bm, model, quant)
            return (carry[0] + loss * count, carry[1] + count), None

        (ls, c), _ = jax.lax.scan(
            step, (jnp.float32(0.0), jnp.float32(0.0)), (x, y, mask))
        return ls, c

    return jax.jit(evaluate)


def evaluate(params, packed, model, quant=None) -> float:
    """Mean loss over every real sample of a packed federation, a
    client's batches at a time."""
    fn = _eval_fn(_freeze(model), quant)
    x, y, mask = packed
    ls = c = 0.0
    for i in range(x.shape[0]):
        a, b = fn(params, x[i], y[i], mask[i])
        ls, c = ls + a, c + b
    return float(ls) / max(float(c), 1.0)
