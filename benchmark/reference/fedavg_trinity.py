"""Plain reference: FedAvg rounds of an AFMoE-style decoder (Trinity-Mini:
gated attention output, sandwich norms, window and full attention, a
shared expert beside sigmoid-routed experts), float32.

Independent of ``fedml_tpu``: nothing here imports the program or takes
anything the program has made. Plain ``jax.numpy`` in float32 (the
callers hold ``jax.default_matmul_precision("highest")``; every product
here also names it), dense masked attention, every held expert on
every token weighted by a mask, no kernel, no cache, no ``vmap`` over
clients. It follows

- the published ``config.json`` of Trinity-Mini (``model_type: afmoe``;
  ``https://huggingface.co/arcee-ai/Trinity-Mini``) and the layer as the
  afmoe modelling code writes it. With ``x`` the residual and ``a =
  RMSNorm_in(x)`` (RMSNorm, eps 1e-5):
  attention: ``q = RMSNorm_q(a W_q)`` (32 heads of 128), ``k =
  RMSNorm_k(a W_k)``, ``v = a W_v`` (4 heads of 128); the rotary
  embedding (theta 1e4, rotate-half) on ``sliding_attention`` layers
  only; ``o = softmax(q k^T / sqrt(128) + mask) v``, causal, with a
  window of ``sliding_window`` keys (``q - k < 2048``) on sliding
  layers; the gate ``g = sigmoid(a W_g)``; ``x <- x +
  RMSNorm_post_attn((o * g) W_o)``.
  feed-forward: ``b = RMSNorm_pre_mlp(x)``, ``x <- x +
  RMSNorm_post_mlp(F(b))`` -- on the first ``num_dense_layers`` layers a
  gated-SiLU MLP of ``intermediate_size``; elsewhere ``s = sigmoid(b
  W_r)`` over all ``num_experts``, the top ``num_experts_per_tok`` by
  ``s + expert_bias``, weights the chosen experts' unbiased ``s`` over
  their sum + ``norm_topk_eps`` (``route_norm``) times ``route_scale``;
  ``F(b) = sum w_e SwiGLU_e(b)`` at ``moe_intermediate_size``, plus one
  shared SwiGLU expert of the same width on every token.
  embedding: ``x_0 = E[t] * embedding_multiplier`` (muP's
  ``sqrt(hidden)``); after the last layer RMSNorm, then the untied head;
- what the config has no key for, listed under ``assumed`` in the
  configuration file, among them the expert bias as the source's
  balancing rule leaves it at the seed's weights (``balance_biases``,
  the fit of ``fedavg_twotower.py``);
- the chip's share of an expert-parallel deployment
  (``model["experts_held"] = [first, count]``): the router scores all
  ``num_experts``; only the held experts' terms of a token's weighted
  sum are computed and the shared expert's whole; what the absent
  experts would add is left out, and that partial sum goes on. The
  vocabulary slice is a smaller vocabulary;
- FedAvg (McMahan et al. 2017) and FedML's cohort rule, as
  ``fedavg_resnet_gn.py`` does.

Departures from the published description, each also in the
configuration's ``departures``: the held share; the cut in depth; the
masked products computed a block of ``BLOCK`` queries at a time, in
one loop (``lax.map``), against as many keys as the block's widest
query can reach -- the window's reach on a sliding layer, every key on
a full one --, each block recomputed in the backward pass
(``jax.checkpoint``) so that one block's ``[heads, BLOCK, keys]``
arrays live at a time: the same sums. Loops and batched products in
place of a Python loop over blocks and experts keep the compiled
executable small (~50 MB on a v5e against ~120), so that the machine's
compile cache holds it beside the program's.

A batch's loss is the mean next-token cross-entropy over the tokens of
its real sequences; its gradient is accumulated **sequence by
sequence**. Between clients the running aggregate lives on the host.

Parameter names mirror the flax tree of ``models/decoder.py`` because
that tree is the program's interface for handing weights over.

``quant`` is the control's hook (``controls.py``): every weight product
(q/k/v/o and the gate, the dense MLP's, the experts' and the shared
expert's three matrices, the head) takes its operands through
``quant.operand`` and its result through ``quant.grad``; the router,
the norms, the rotation, the masked products, the softmax and the loss
stay float32. ``row_keep`` and ``fault`` plant the faults the limits
have to catch (tests and limit readings only): every ``row_keep``-th
sequence of a client kept; ``"no_gate"`` (the attention output
ungated), ``"no_post_norms"`` (both sandwich norms left out),
``"no_mup"`` (the embedding multiplier dropped), ``"rotate_full"`` (the
full layers rotated too), ``"no_window"`` (sliding layers see every
key before them), ``"no_bias"`` (the choice without the expert bias),
``"no_route_scale"`` (``route_scale`` dropped), ``"no_shared"`` (the
shared expert left out).
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.append(HERE)
from fedavg_twotower import BALANCE_TOKENS, _balanced_bias  # noqa: E402  (the bias fit, shared)

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"
NEG_INF = -1e30
BLOCK = 1024
FAULTS = (None, "no_gate", "no_post_norms", "no_mup", "rotate_full", "no_window", "no_bias",
          "no_route_scale", "no_shared")


# -- weights -----------------------------------------------------------
def param_shapes(model: dict) -> dict:
    """The tree of shapes, from the configuration's sizes alone."""
    c, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    held, width = model["experts_held"][1], model["moe_intermediate_size"]
    shared = model["num_shared_experts"] * width
    mlp = lambda w: {"gate_proj": {"kernel": (c, w)}, "up_proj": {"kernel": (c, w)},
                     "down_proj": {"kernel": (w, c)}}
    attn = {
        "q_proj": {"kernel": (c, heads * d)}, "k_proj": {"kernel": (c, kv * d)},
        "v_proj": {"kernel": (c, kv * d)}, "gate_proj": {"kernel": (c, heads * d)},
        "o_proj": {"kernel": (heads * d, c)}, "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
    }
    norms = {name: {"scale": (c,)} for name in ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")}
    moe = {
        "router": {"kernel": (c, model["num_experts"])}, "expert_bias": (model["num_experts"],),
        "gate_proj": (held, c, width), "up_proj": (held, c, width), "down_proj": (held, width, c),
        "shared": mlp(shared),
    }
    tree = {
        f"layer_{i}": {**norms, "attn": attn, **(
            {"mlp": mlp(model["intermediate_size"])} if i < model["num_dense_layers"] else {"moe": moe})}
        for i in range(len(model["layer_types"]))
    }
    tree["embed"] = {"embedding": (model["vocab_size"], c)}
    tree["final_norm"] = {"scale": (c,)}
    tree["lm_head"] = {"kernel": (c, model["vocab_size"])}
    return tree


def init_params(seed: int, model: dict):
    """Seeded random weights on the device (the configuration's
    ``assumed``): normal kernels of standard deviation ``fan_in ** -0.5``,
    embedding rows of standard deviation ``hidden ** -0.5`` (times the
    multiplier: rows of about unit size), unit norm scales (the sandwich
    norms' too); the experts' bias fitted to those weights by
    ``balance_biases``."""
    is_shape = lambda x: isinstance(x, tuple)
    shapes = param_shapes(model)
    paths = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))

    @jax.jit
    def make(root):  # the key an argument, so that one executable serves every seed
        out = []
        for i, (path, shape) in enumerate(paths):
            name = path[-1].key
            if name in ("scale", "expert_bias"):
                out.append((jnp.ones if name == "scale" else jnp.zeros)(shape, jnp.float32))
                continue
            std = shape[-1] ** -0.5 if name == "embedding" else shape[-2] ** -0.5
            out.append(std * jax.random.normal(jax.random.fold_in(root, i), shape, jnp.float32))
        return out

    tree = jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), make(root))
    return balance_biases(tree, model, jax.random.fold_in(root, len(paths)))


def balance_biases(params, model: dict, key):
    """``params`` with every expert layer's bias set as the source's
    balancing leaves it -- the experts' loads even at these weights --
    layer after layer (a layer's bias moves what the later routers see),
    over ``BALANCE_TOKENS`` uniform token ids drawn from ``key``, by
    ``fedavg_twotower._balanced_bias``'s rule."""
    tokens = jax.random.randint(key, BALANCE_TOKENS, 0, model["vocab_size"])
    eps = model["rms_norm_eps"]

    @jax.jit
    def fit(params, tokens):
        x = params["embed"]["embedding"][tokens] * model["embedding_multiplier"]  # [S, T, C]
        for i, kind in enumerate(model["layer_types"]):
            p = params[f"layer_{i}"]
            x = jax.lax.map(lambda seq: _attention_half(seq, p, kind, model, None, None), x)
            if "moe" in p:
                b = _rms(x, p["ffn_norm"]["scale"], eps).reshape(-1, x.shape[-1])
                score = jax.nn.sigmoid(jnp.dot(b, p["moe"]["router"]["kernel"], precision=HIGHEST))
                bias = _balanced_bias(score, model["num_experts_per_tok"])
                p = {**p, "moe": {**p["moe"], "expert_bias": bias}}
                params = {**params, f"layer_{i}": p}
            x = jax.lax.map(lambda seq: _ffn_half(seq, p, i, model, None, None), x)
        return params

    return fit(params, tokens)


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


def _attention(q, k, v, window):
    """Dense masked grouped-KV attention, ``BLOCK`` queries at a time
    (one loop body for every block) against the ``reach`` keys before
    a block's end that its widest query can see -- every key on a full
    layer, the window's reach on a sliding one --, the keys padded in
    front so that every block takes as many; each block recomputed in
    the backward pass: [T, H, D] x [T, KV, D] -> [T, H, D]."""
    t, h, d = q.shape
    kv = k.shape[1]
    block = BLOCK if t % BLOCK == 0 else t
    pad = t - block if window is None else min(-(-(window - 1) // block) * block, t - block)
    reach = pad + block
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        qb, q0 = args
        kb = jax.lax.dynamic_slice_in_dim(kp, q0, reach)  # keys q0 - pad .. q0 + block - 1
        vb = jax.lax.dynamic_slice_in_dim(vp, q0, reach)
        i = q0 + jnp.arange(block)[:, None]
        j = q0 - pad + jnp.arange(reach)[None]
        keep = (j >= 0) & (i >= j)
        if window is not None:
            keep = keep & (i - j < window)
        qg = qb.reshape(block, kv, h // kv, d)
        s = jnp.einsum("qhgd,khd->hgqk", qg, kb, precision=HIGHEST) * d ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vb, precision=HIGHEST).reshape(block, h, d)

    starts = jnp.arange(0, t, block, dtype=jnp.int32)
    out = jax.lax.map(one, (q.reshape(t // block, block, h, d), starts))
    return out.reshape(t, h, d)


def _attention_op(a, p, kind, model, quant, fault):
    """The gated attention, [T, C] (normed) -> [T, C]."""
    t, d = a.shape[0], model["head_dim"]
    h, kv, eps = model["num_attention_heads"], model["num_key_value_heads"], model["rms_norm_eps"]
    q = _rms(_linear(a, p["q_proj"]["kernel"], quant).reshape(t, h, d), p["q_norm"]["scale"], eps)
    k = _rms(_linear(a, p["k_proj"]["kernel"], quant).reshape(t, kv, d), p["k_norm"]["scale"], eps)
    v = _linear(a, p["v_proj"]["kernel"], quant).reshape(t, kv, d)
    if kind == SLIDING or fault == "rotate_full":
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    window = model["sliding_window"] if (kind == SLIDING and fault != "no_window") else None
    o = _attention(q, k, v, window).reshape(t, h * d)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(_linear(a, p["gate_proj"]["kernel"], quant))
    return _linear(o, p["o_proj"]["kernel"], quant)


def _gated_mlp(x, gate, up, down, quant):
    return _linear(jax.nn.silu(_linear(x, gate, quant)) * _linear(x, up, quant), down, quant)


def _experts(x, p, model, quant, fault):
    """The held experts' part of the routed sum and the shared expert's
    whole, [T, C] -> [T, C]."""
    first, held = model["experts_held"]
    k = model["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"], precision=HIGHEST))
    if fault == "no_bias":
        weight, expert = jax.lax.top_k(score, k)
    else:
        _, expert = jax.lax.top_k(score + p["expert_bias"], k)
        weight = jnp.take_along_axis(score, expert, axis=-1)
    if model["route_norm"]:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + model["norm_topk_eps"])
    if fault != "no_route_scale":
        weight = weight * model["route_scale"]
    # every held expert on every token, one batched product a matrix
    out = jax.vmap(lambda gate, up, down: _gated_mlp(x, gate, up, down, quant))(
        p["gate_proj"], p["up_proj"], p["down_proj"])  # [held, T, C]
    y = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)  # [T]
        y = y + w_e[:, None] * out[e]
    if fault != "no_shared":
        s = p["shared"]
        y = y + _gated_mlp(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"], s["down_proj"]["kernel"], quant)
    return y


def _post(h, scale, eps, fault):
    """The sandwich norm on a sublayer's output."""
    return h if fault == "no_post_norms" else _rms(h, scale, eps)


def _attention_half(x, p, kind, model, quant, fault):
    """``x + RMSNorm_post(attention(RMSNorm_in(x)))``, [T, C] -> [T, C]."""
    eps = model["rms_norm_eps"]
    h = _attention_op(_rms(x, p["attn_norm"]["scale"], eps), p["attn"], kind, model, quant, fault)
    return x + _post(h, p["attn_post_norm"]["scale"], eps, fault)


def _ffn_half(x, p, i: int, model: dict, quant, fault):
    """``x + RMSNorm_post(F(RMSNorm_pre(x)))``, [T, C] -> [T, C]."""
    eps = model["rms_norm_eps"]
    b = _rms(x, p["ffn_norm"]["scale"], eps)
    if i < model["num_dense_layers"]:
        m = p["mlp"]
        h = _gated_mlp(b, m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"], quant)
    else:
        h = _experts(b, p["moe"], model, quant, fault)
    return x + _post(h, p["ffn_post_norm"]["scale"], eps, fault)


def forward(params, tokens, model: dict, quant=None, fault=None):
    """One sequence of token ids [T] -> logits [T, vocab]."""
    x = params["embed"]["embedding"][tokens]
    if fault != "no_mup":
        x = x * model["embedding_multiplier"]
    for i, kind in enumerate(model["layer_types"]):
        p = params[f"layer_{i}"]
        x = _ffn_half(_attention_half(x, p, kind, model, quant, fault), p, i, model, quant, fault)
    x = _rms(x, params["final_norm"]["scale"], model["rms_norm_eps"])
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
