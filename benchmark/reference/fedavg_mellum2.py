"""Plain reference: FedAvg rounds of a Mellum2-style MoE decoder, float32.

Independent of ``fedml_tpu``: nothing here imports the program or takes
anything the program has made. Plain ``jax.numpy`` in float32 (the
callers hold ``jax.default_matmul_precision("highest")``; every product
here also names it), dense masked attention, a loop over the held
experts, no kernel, no cache, no rematerialisation, no ``vmap`` over
clients. It follows

- the published ``config.json`` of Mellum2-12B-A2.5B-Instruct
  (``https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct``):
  pre-norm residual blocks, RMSNorm (eps 1e-6), separate bias-free
  q/k/v/o projections with ``head_dim`` 128, 32 query heads sharing 4
  KV heads, ``layer_types`` sliding (causal window 1024) and full, a
  rotary embedding per layer type (default; YaRN on full layers:
  Peng et al. 2023, arXiv:2309.00071), every MLP a router over 64
  gated-SiLU experts of width 896, top 8 renormalised, untied head;
- its lineage's conventions where the config has no key (listed under
  ``assumed`` in the configuration file): q and k RMS-normalised per
  head before the rotation, rotate-half rotary layout, router = softmax
  over all experts in float32, then top-k, then renormalise; no
  auxiliary loss;
- the chip's share of an expert-parallel deployment
  (``model["experts_held"] = [first, count]``): the router scores all
  ``num_experts``; only the held experts' terms of a token's weighted
  sum are computed, what the absent experts would add is left out, and
  that partial sum goes on. The vocabulary slice is a smaller
  vocabulary;
- FedAvg (McMahan et al. 2017) and FedML's cohort rule, as
  ``fedavg_resnet_gn.py`` does.

A batch's loss is the mean next-token cross-entropy over the tokens of
its real sequences; its gradient is accumulated **sequence by
sequence** (the sum of each sequence's gradient over the batch's token
count), so that only one sequence's activations live at a time: at the
cell's size a sequence's dense scores are 2 GB a layer. Dense masked
scores are computed a block of ``sliding_window`` queries at a time
against the keys that block can see — the same masked softmax, without
the blocks that are all mask. Between clients the running aggregate
lives on the host, and a client's update is computed in place on a copy
of the global weights handed over for it: the chip then holds one copy
of the weights, one gradient sum, one sequence's gradient and one
sequence's activations (12 GB at the cell's size).

Parameter names mirror the flax tree of ``models/decoder.py`` because
that tree is the program's interface for handing weights over.

``quant`` is the control's hook (``controls.py``): every weight product
(the four projections, the experts' three matrices, the head) takes its
operands through ``quant.operand`` and its result through
``quant.grad``; the router, the norms, the rotary tables, the softmaxes
and the loss stay float32, as fp8 training keeps them. ``row_keep`` and
``fault`` plant the faults the limits have to catch (tests and limit
readings only): every ``row_keep``-th sequence of a batch kept;
``"no_window"`` (sliding layers attend everything before them),
``"no_renorm"`` (the top-k weights left as the softmax gave them),
``"no_yarn"`` (full layers rotate by the default table).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"
NEG_INF = -1e30
FAULTS = (None, "no_window", "no_renorm", "no_yarn")


# -- weights -----------------------------------------------------------
def param_shapes(model: dict) -> dict:
    """The tree of shapes, from the configuration's sizes alone."""
    c, d = model["hidden_size"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    held, width = model["experts_held"][1], model["moe_intermediate_size"]
    layer = {
        "attn_norm": {"scale": (c,)},
        "attn": {
            "q_proj": {"kernel": (c, h * d)}, "k_proj": {"kernel": (c, kv * d)},
            "v_proj": {"kernel": (c, kv * d)}, "o_proj": {"kernel": (h * d, c)},
            "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
        },
        "ffn_norm": {"scale": (c,)},
        "moe": {
            "router": {"kernel": (c, model["num_experts"])},
            "gate_proj": (held, c, width), "up_proj": (held, c, width),
            "down_proj": (held, width, c),
        },
    }
    tree = {f"layer_{i}": layer for i in range(len(model["layer_types"]))}
    tree["embed"] = {"embedding": (model["vocab_size"], c)}
    tree["final_norm"] = {"scale": (c,)}
    tree["lm_head"] = {"kernel": (c, model["vocab_size"])}
    return tree


def init_params(seed: int, model: dict):
    """Seeded random weights on the device: normal kernels of standard
    deviation ``fan_in ** -0.5`` (the second-to-last axis), embedding
    rows of standard deviation 1, unit norm scales."""
    is_shape = lambda x: isinstance(x, tuple)
    shapes = param_shapes(model)
    paths = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))

    @jax.jit
    def make():
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            if name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
                continue
            std = 1.0 if name == "embedding" else shape[-2] ** -0.5
            out.append(std * jax.random.normal(jax.random.fold_in(root, i), shape, jnp.float32))
        return out

    return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), make())


# -- rotary embedding --------------------------------------------------
def rope_inv_freq(head_dim: int, rope: dict):
    """``(inv_freq [head_dim / 2], scale)``. YaRN as published: the
    dimension that turns ``r`` times over the original context is
    ``d ln(L / 2 pi r) / (2 ln theta)``; below ``beta_fast``'s the
    frequencies stay, above ``beta_slow``'s they are divided by
    ``factor``, with a linear ramp between; cos and sin are scaled by
    ``attention_factor`` (``0.1 ln(factor) + 1`` where unset)."""
    theta = float(rope["rope_theta"])
    freqs = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rope.get("rope_type", "default") == "default":
        return freqs.astype(np.float32), 1.0
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])
    dim_of = lambda turns: head_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), head_dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    inv_freq = freqs / factor * ramp + freqs * (1.0 - ramp)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def _rope(x, inv_freq, scale):
    """``x`` [T, heads, D], rotate-half layout."""
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)) * scale


# -- one sequence's forward pass ---------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _linear(x, w, quant):
    """A weight product: the control's hook sits here."""
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return y if quant is None else quant.grad(y)


def _masked_softmax_attention(q, k, v, q0: int, k0: int, window):
    """Queries at positions ``q0 + i`` against keys at ``k0 + j``:
    [Tq, H, D] x [Tk, KV, D] -> [Tq, H, D]."""
    tq, h, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(tq, kv, h // kv, d)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) * d ** -0.5
    i = q0 + jnp.arange(tq)[:, None]
    j = k0 + jnp.arange(k.shape[0])[None]
    keep = i >= j
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST).reshape(tq, h, d)


def _attention(q, k, v, window, block: int):
    """Dense masked attention, ``block`` queries at a time against the
    keys that block can see: everything up to its own end on a full
    layer, its own block of keys and the ones its band reaches back to
    on a sliding one. The same masked softmax, without the blocks that
    are all mask (at T = 4,096 a full layer's saved probabilities are
    1.3 GB this way and 2.1 GB whole)."""
    t = q.shape[0]
    if t <= block or t % block:
        return _masked_softmax_attention(q, k, v, 0, 0, window)
    out = []
    for b in range(t // block):
        q0, hi = b * block, (b + 1) * block
        lo = 0 if window is None else max(q0 - window + 1, 0) // block * block
        out.append(_masked_softmax_attention(q[q0:hi], k[lo:hi], v[lo:hi], q0, lo, window))
    return jnp.concatenate(out, axis=0)


def _experts(x, p, model, quant, fault):
    """The held experts' part of the routed sum, [T, C] -> [T, C]."""
    first, held = model["experts_held"]
    probs = jax.nn.softmax(jnp.dot(x, p["router"]["kernel"], precision=HIGHEST), axis=-1)
    weight, expert = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"] and fault != "no_renorm":
        weight = weight / weight.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)  # [T]
        gate = _linear(x, p["gate_proj"][e], quant)
        up = _linear(x, p["up_proj"][e], quant)
        y = y + w_e[:, None] * _linear(jax.nn.silu(gate) * up, p["down_proj"][e], quant)
    return y


def forward(params, tokens, model: dict, quant=None, fault=None):
    """One sequence of token ids [T] -> logits [T, vocab]."""
    eps, d = model["rms_norm_eps"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    t = tokens.shape[0]
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(model["layer_types"]):
        p = params[f"layer_{i}"]
        rope = model["rope_parameters"][SLIDING if (kind == FULL and fault == "no_yarn") else kind]
        inv_freq, scale = rope_inv_freq(d, rope)
        a = _rms(x, p["attn_norm"]["scale"], eps)
        q = _linear(a, p["attn"]["q_proj"]["kernel"], quant).reshape(t, h, d)
        k = _linear(a, p["attn"]["k_proj"]["kernel"], quant).reshape(t, kv, d)
        v = _linear(a, p["attn"]["v_proj"]["kernel"], quant).reshape(t, kv, d)
        q = _rope(_rms(q, p["attn"]["q_norm"]["scale"], eps), inv_freq, scale)
        k = _rope(_rms(k, p["attn"]["k_norm"]["scale"], eps), inv_freq, scale)
        window = model["sliding_window"] if (kind == SLIDING and fault != "no_window") else None
        o = _attention(q, k, v, window, model["sliding_window"]).reshape(t, h * d)
        x = x + _linear(o, p["attn"]["o_proj"]["kernel"], quant)
        x = x + _experts(_rms(x, p["ffn_norm"]["scale"], eps), p["moe"], model, quant, fault)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _linear(x, params["lm_head"]["kernel"], quant)


def _sequence_loss_sum(params, tokens, targets, model, quant, fault):
    """Summed next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(forward(params, tokens, model, quant, fault), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1).sum()


# -- one client, one round, one evaluation -----------------------------
def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    return tuple(_freeze(v) for v in obj) if isinstance(obj, (list, tuple)) else obj


def _thaw(obj):
    if isinstance(obj, tuple) and obj and all(
            isinstance(kv, tuple) and len(kv) == 2 and isinstance(kv[0], str) for kv in obj):
        return {k: _thaw(v) for k, v in obj}
    return [_thaw(v) for v in obj] if isinstance(obj, tuple) else obj


@functools.lru_cache(maxsize=None)
def _client_update_fn(model_key, lr: float, epochs: int, quant, row_keep: int, fault):
    model = _thaw(model_key)

    def client_update(params, x, y, mask):
        """x, y [nb, bs, T] token ids and next tokens, mask [nb, bs].
        Returns the client's weights after its epochs and the last
        epoch's summed loss and token count (each batch's loss taken
        before its step)."""
        if row_keep:
            keep = (jnp.arange(mask.shape[1]) % row_keep == 0).astype(mask.dtype)
            mask = mask * keep[None, :]
        tokens_in = x.shape[-1]

        def step(p, batch):
            bx, by, bm = batch
            count = bm.sum() * tokens_in

            def one(acc, seq):
                sx, sy, sm = seq
                loss, g = jax.value_and_grad(_sequence_loss_sum)(p, sx, sy, model, quant, fault)
                return (acc[0] + sm * loss, jax.tree.map(lambda a, b: a + sm * b, acc[1], g)), None

            zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, p))
            (loss_sum, g), _ = jax.lax.scan(one, zero, (bx, by, bm))
            scale = lr / jnp.maximum(count, 1.0)
            return jax.tree.map(lambda a, b: a - scale * b, p, g), (loss_sum, count)

        for _ in range(epochs):
            params, (ls, c) = jax.lax.scan(step, params, (x, y, mask))
        return params, ls.sum(), c.sum()

    return jax.jit(client_update, donate_argnums=0)


def sample_cohort(round_idx: int, clients: int, per_round: int) -> np.ndarray:
    if clients == per_round:
        return np.arange(clients, dtype=np.int32)
    rs = np.random.RandomState(round_idx)
    return np.asarray(rs.choice(range(clients), per_round, replace=False), np.int32)


def fedavg_round(params, packed, nsamples, cohort, model, fed, quant=None, row_keep=0,
                 fault=None):
    """One FedAvg round over ``cohort`` (client indices). ``packed`` is
    (x [C, nb, bs, T], y, mask [C, nb, bs]). Clients run one after
    another. Returns the new global weights (float32 numpy arrays on
    the host) and the cohort's mean training loss a token."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    update = _client_update_fn(
        _freeze(model), float(fed["lr"]), int(fed["epochs"]), quant, int(row_keep), fault)
    x, y, mask = packed
    ns = np.asarray(nsamples, np.float64)[cohort]
    weights = ns / max(ns.sum(), 1.0)
    start = jax.device_get(params)  # the global weights, on the host
    acc, loss_sum, count = None, 0.0, 0.0
    for w, c in zip(weights, cohort):
        # a fresh device copy a client, updated in place
        new, ls, cnt = update(jax.device_put(start), x[c], y[c], mask[c])
        term = jax.tree.map(lambda a: np.asarray(a) * np.float32(w), new)
        del new
        acc = term if acc is None else jax.tree.map(np.add, acc, term)
        loss_sum, count = loss_sum + float(ls), count + float(cnt)
    return acc, loss_sum / max(count, 1.0)


@functools.lru_cache(maxsize=None)
def _eval_fn(model_key, quant, fault):
    model = _thaw(model_key)

    def evaluate(params, x, y, mask):
        def one(carry, seq):
            sx, sy, sm = seq
            loss = _sequence_loss_sum(params, sx, sy, model, quant, fault)
            return (carry[0] + sm * loss, carry[1] + sm * sx.shape[0]), None

        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        (ls, c), _ = jax.lax.scan(
            one, (jnp.float32(0.0), jnp.float32(0.0)), (flat(x), flat(y), flat(mask)))
        return ls, c

    return jax.jit(evaluate)


def evaluate(params, packed, model, quant=None, fault=None) -> float:
    """Mean loss a token over every real sequence of a packed
    federation, a client's sequences at a time."""
    fn = _eval_fn(_freeze(model), quant, fault)
    params = jax.device_put(params)  # once, not once a client
    x, y, mask = packed
    ls = c = 0.0
    for i in range(x.shape[0]):
        a, b = fn(params, x[i], y[i], mask[i])
        ls, c = ls + float(a), c + float(b)
    return ls / max(c, 1.0)
