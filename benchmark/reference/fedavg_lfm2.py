"""Plain reference: FedAvg rounds of an LFM2-MoE-style decoder, float32.

Independent of ``fedml_tpu``: nothing here imports the program or takes
anything the program has made. Plain ``jax.numpy`` in float32 (the
callers hold ``jax.default_matmul_precision("highest")``; every product
here also names it), dense masked attention, the convolution as shifted
slices, a loop over the held experts with a mask, no kernel, no cache,
no rematerialisation, no ``vmap`` over clients. It follows

- the published ``config.json`` of LFM2-8B-A1B
  (``https://huggingface.co/LiquidAI/LFM2-8B-A1B``) and the layer as
  ``transformers``' ``modeling_lfm2_moe`` writes it: ``x = x +
  op(operator_norm(x)); x = x + ffn(ffn_norm(x))``, RMSNorm (eps 1e-5)
  both. ``op`` on a ``conv`` layer is the gated short convolution: ``B,
  C, u = split3(in_proj(h))``, ``z_t = sum_{j<L} w_j * (B * u)_{t - (L -
  1) + j}`` (depthwise, causal, zeros before the sequence, ``L =
  conv_L_cache`` = 3 taps, no bias, no activation), ``out_proj(C *
  z)``. On a ``full_attention`` layer: bias-free q/k/v/o projections,
  32 query heads sharing 8 KV heads, RMSNorm over each head's 64 dims
  on q and k, rotate-half RoPE (theta 1e6), causal softmax attention
  scaled ``64 ** -0.5``. ``ffn`` on the first ``num_dense_layers``
  layers is ``w2(silu(w1 x) * w3 x)`` at ``intermediate_size``; on
  every other layer ``s = sigmoid(router(x))`` over all 32 experts in
  float32, the top 4 chosen by ``s + expert_bias``, the weights the
  chosen experts' *unbiased* ``s`` divided by their sum + 1e-6
  (``norm_topk_prob``) times ``routed_scaling_factor``, each expert a
  gated-SiLU MLP of ``moe_intermediate_size``. After the last layer
  RMSNorm, then the head, tied to the embedding;
- what the config has no key for, listed under ``assumed`` in the
  configuration file: head width = hidden / heads, the tied head, the
  renormaliser's 1e-6, rotate-half rotary layout, q/k norm before the
  rotation, the split order (B, C, u), no auxiliary loss, the expert
  bias drawn from the seed;
- the chip's share of an expert-parallel deployment
  (``model["experts_held"] = [first, count]``): the router scores all
  ``num_experts``; only the held experts' terms of a token's weighted
  sum are computed, what the absent experts would add is left out, and
  that partial sum goes on. The vocabulary slice is a smaller
  vocabulary;
- FedAvg (McMahan et al. 2017) and FedML's cohort rule, as
  ``fedavg_resnet_gn.py`` does.

Departures from the published description, each also in the
configuration's ``departures``: the held share above; the cut in depth
(the leading dense layer once, then one period); dense masked scores
computed a block of ``ATTN_BLOCK`` queries at a time against the keys
up to that block's end -- the same masked softmax, without the blocks
that are all mask.

A batch's loss is the mean next-token cross-entropy over the tokens of
its real sequences; its gradient is accumulated **sequence by
sequence** (the sum of each sequence's gradient over the batch's token
count), so that only one sequence's activations live at a time. Between
clients the running aggregate lives on the host, and a client's update
is computed in place on a copy of the global weights handed over for it.

Parameter names mirror the flax tree of ``models/decoder.py`` because
that tree is the program's interface for handing weights over.

``quant`` is the control's hook (``controls.py``): every weight product
(the projections of either operator, the dense MLP's and the experts'
three matrices, the head) takes its operands through ``quant.operand``
and its result through ``quant.grad``; the router, the norms, the
rotary tables, the softmax, the convolution's gates and taps and the
loss stay float32, as fp8 training keeps them. ``row_keep`` and
``fault`` plant the faults the limits have to catch (tests and limit
readings only): every ``row_keep``-th sequence of a client kept (at an
even batch size half of each batch, ``fedavg_mellum2.py``'s fault; at a
batch of one every other step finds its batch empty);
``"no_bias"`` (the selection bias ignored), ``"acausal_conv"`` (the
convolution reads one token ahead: its taps sit on t - 1, t, t + 1),
``"no_c_gate"`` (``out_proj(z)``), ``"no_renorm"`` (the top-k weights
left as the sigmoid gave them), ``"dense_width"`` (the dense layer cut
to an expert's width: the first ``moe_intermediate_size`` columns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FULL, CONV = "full_attention", "conv"
NEG_INF = -1e30
ATTN_BLOCK = 1024
FAULTS = (None, "no_bias", "acausal_conv", "no_c_gate", "no_renorm", "dense_width")


# -- weights -----------------------------------------------------------
def param_shapes(model: dict) -> dict:
    """The tree of shapes, from the configuration's sizes alone."""
    c, d = model["hidden_size"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    held, width = model["experts_held"][1], model["moe_intermediate_size"]
    dense = model["intermediate_size"]
    operator = {
        CONV: {
            "conv_norm": {"scale": (c,)},
            "conv": {"in_proj": {"kernel": (c, 3 * c)}, "conv_kernel": (model["conv_L_cache"], c),
                     "out_proj": {"kernel": (c, c)}},
        },
        FULL: {
            "attn_norm": {"scale": (c,)},
            "attn": {
                "q_proj": {"kernel": (c, h * d)}, "k_proj": {"kernel": (c, kv * d)},
                "v_proj": {"kernel": (c, kv * d)}, "o_proj": {"kernel": (h * d, c)},
                "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
            },
        },
    }
    mlp = {"mlp": {"gate_proj": {"kernel": (c, dense)}, "up_proj": {"kernel": (c, dense)},
                   "down_proj": {"kernel": (dense, c)}}}
    moe = {"moe": {
        "router": {"kernel": (c, model["num_experts"])},
        "gate_proj": (held, c, width), "up_proj": (held, c, width), "down_proj": (held, width, c),
    }}
    if model["use_expert_bias"]:
        moe["moe"]["expert_bias"] = (model["num_experts"],)
    tree = {
        f"layer_{i}": {**operator[kind], "ffn_norm": {"scale": (c,)},
                       **(mlp if i < model["num_dense_layers"] else moe)}
        for i, kind in enumerate(model["layer_types"])}
    tree["embed"] = {"embedding": (model["vocab_size"], c)}
    tree["final_norm"] = {"scale": (c,)}
    return tree


def init_params(seed: int, model: dict):
    """Seeded random weights on the device: normal kernels of standard
    deviation ``fan_in ** -0.5`` (the second-to-last axis: the taps of
    the convolution), embedding rows of standard deviation ``hidden **
    -0.5`` (the head is tied to them: logits of about unit size), unit
    norm scales, and the experts' selection bias of standard deviation
    ``model["expert_bias_std"]``, centred within each contiguous share
    of ``experts_held[1]`` experts. (The source trains this bias to
    even the experts' loads; of that, a random draw can state that no
    chip's share is favoured: every share's mean bias is 0, so a share
    takes ``k * held / E`` choices a token whatever the seed, which is
    what ``flops/`` counts. Left uncentred, the held share's mean --
    standard deviation 0.007 at 0.02 -- moved its load by ~6% from seed
    to seed.)"""
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
            std = {"embedding": shape[-1] ** -0.5,
                   "expert_bias": float(model.get("expert_bias_std", 0.0))}.get(name)
            std = shape[-2] ** -0.5 if std is None else std
            leaf = std * jax.random.normal(jax.random.fold_in(root, i), shape, jnp.float32)
            if name == "expert_bias":
                share = leaf.reshape(-1, model["experts_held"][1])
                leaf = (share - share.mean(axis=1, keepdims=True)).reshape(shape)
            out.append(leaf)
        return out

    return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), make())


# -- one sequence's forward pass ---------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _linear(x, w, quant):
    """A weight product: the control's hook sits here."""
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return y if quant is None else quant.grad(y)


def _rope(x, theta: float):
    """``x`` [T, heads, D], rotate-half layout, the default table."""
    d = x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _causal_attention(q, k, v):
    """Dense masked grouped-KV attention, ``ATTN_BLOCK`` queries at a
    time against the keys up to their block's end: [T, H, D] x [T, KV,
    D] -> [T, H, D]."""
    t, h, d = q.shape
    kv = k.shape[1]
    block = ATTN_BLOCK if t % ATTN_BLOCK == 0 else t
    out = []
    for q0 in range(0, t, block):
        hi = q0 + block
        qg = q[q0:hi].reshape(block, kv, h // kv, d)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k[:hi], precision=HIGHEST) * d ** -0.5
        keep = (q0 + jnp.arange(block))[:, None] >= jnp.arange(hi)[None]
        p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        out.append(jnp.einsum("hgqk,khd->qhgd", p, v[:hi], precision=HIGHEST).reshape(block, h, d))
    return jnp.concatenate(out, axis=0)


def _attention_op(a, p, model, quant):
    t, d = a.shape[0], model["head_dim"]
    h, kv, eps = model["num_attention_heads"], model["num_key_value_heads"], model["norm_eps"]
    q = _linear(a, p["q_proj"]["kernel"], quant).reshape(t, h, d)
    k = _linear(a, p["k_proj"]["kernel"], quant).reshape(t, kv, d)
    v = _linear(a, p["v_proj"]["kernel"], quant).reshape(t, kv, d)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), model["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), model["rope_theta"])
    return _linear(_causal_attention(q, k, v).reshape(t, h * d), p["o_proj"]["kernel"], quant)


def _conv_op(a, p, quant, fault):
    """The gated short convolution, [T, C] -> [T, C]."""
    t, c = a.shape
    gate_in, gate_out, u = jnp.split(_linear(a, p["in_proj"]["kernel"], quant), 3, axis=-1)
    taps = p["conv_kernel"]  # [L, C]; the last tap sits on the token itself
    ahead = 1 if fault == "acausal_conv" else 0
    padded = jnp.pad(gate_in * u, ((taps.shape[0] - 1 - ahead, ahead), (0, 0)))
    z = sum(taps[j] * padded[j:j + t] for j in range(taps.shape[0]))
    return _linear(z if fault == "no_c_gate" else gate_out * z, p["out_proj"]["kernel"], quant)


def _gated_mlp(x, gate, up, down, quant):
    return _linear(jax.nn.silu(_linear(x, gate, quant)) * _linear(x, up, quant), down, quant)


def _experts(x, p, model, quant, fault):
    """The held experts' part of the routed sum, [T, C] -> [T, C]."""
    first, held = model["experts_held"]
    k = model["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"], precision=HIGHEST))
    if model["use_expert_bias"] and fault != "no_bias":
        _, expert = jax.lax.top_k(score + p["expert_bias"], k)
        weight = jnp.take_along_axis(score, expert, axis=-1)
    else:
        weight, expert = jax.lax.top_k(score, k)
    if model["norm_topk_prob"] and fault != "no_renorm":
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * model["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)  # [T]
        y = y + w_e[:, None] * _gated_mlp(
            x, p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e], quant)
    return y


def forward(params, tokens, model: dict, quant=None, fault=None):
    """One sequence of token ids [T] -> logits [T, vocab]."""
    eps = model["norm_eps"]
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(model["layer_types"]):
        p = params[f"layer_{i}"]
        if kind == CONV:
            x = x + _conv_op(_rms(x, p["conv_norm"]["scale"], eps), p["conv"], quant, fault)
        else:
            x = x + _attention_op(_rms(x, p["attn_norm"]["scale"], eps), p["attn"], model, quant)
        h = _rms(x, p["ffn_norm"]["scale"], eps)
        if i < model["num_dense_layers"]:
            m = p["mlp"]
            cut = model["moe_intermediate_size"] if fault == "dense_width" else None
            x = x + _gated_mlp(h, m["gate_proj"]["kernel"][:, :cut], m["up_proj"]["kernel"][:, :cut],
                               m["down_proj"]["kernel"][:cut], quant)
        else:
            x = x + _experts(h, p["moe"], model, quant, fault)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _linear(x, params["embed"]["embedding"].T, quant)  # the tied head


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
        before its step). A batch that holds no sequence has a zero
        gradient and leaves the weights as they are."""
        if row_keep:
            # by a sequence's place in the client's store, not in its
            # batch: at a batch of one sequence a row's index is always 0
            place = jnp.arange(mask.size).reshape(mask.shape)
            mask = mask * (place % row_keep == 0).astype(mask.dtype)
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
    the host: the sample-weighted mean of the clients') and the
    cohort's mean training loss a token."""
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
