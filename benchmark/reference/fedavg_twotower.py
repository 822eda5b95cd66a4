"""Plain reference: FedAvg rounds of a Nemotron-H-style hybrid decoder
(state-space, attention and expert sublayers), float32.

Independent of ``fedml_tpu``: nothing here imports the program or takes
anything the program has made. Plain ``jax.numpy`` in float32 (the
callers hold ``jax.default_matmul_precision("highest")``; every product
here also names it), the state-space layer as its dense dual form, dense
masked attention, a loop over the held experts with a mask, no kernel,
no chunked scan, no cache, no ``vmap`` over clients. It follows

- the published ``config.json`` of
  Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 (``model_type: nemotron_h``;
  ``https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16``)
  and the layer as ``transformers``' ``modeling_nemotron_h`` writes it:
  every layer is **one** sublayer, ``x = x + mixer(norm(x))``, RMSNorm
  (eps 1e-5), the mixer by the pattern's letter.
  ``M`` (here ``mamba``), Mamba-2: ``z, xBC, dt = split(in_proj(u),
  [d_inner, d_inner + 2 G N, H])``; ``xBC = silu(conv(xBC) + bias)``, a
  causal depthwise convolution of ``conv_kernel`` = 4 taps (zeros
  before the sequence); ``x, B, C = split(xBC, [d_inner, G N, G N])``,
  ``x`` as H = 64 heads of P = 64, ``B`` / ``C`` as G = 8 groups of N =
  128 (head ``h`` reads group ``h // 8``); ``dt = softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``, a scalar a head. Per head ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (zero before the sequence),
  ``y_t = S_t C_t + D x_t``, computed here as the **dense dual form**
  ``Y = (L o C B^T)(dt x) + D x`` with ``L_ij = exp(a_i - a_j)`` for
  ``i >= j`` over the whole sequence, ``a`` the running sum of ``dt A``.
  Then ``RMSNorm(y * silu(z)) * w`` with the statistics over each of the
  G groups of ``d_inner / G`` = 512 channels, and ``out_proj``. No bias
  on the projections.
  ``*`` (``full_attention``): bias-free q/k/v/o, 32 query heads sharing
  2 KV heads of 128, causal softmax attention scaled ``128 ** -0.5``,
  no rotation, no q/k norm.
  ``E`` (``moe``): ``s = sigmoid(router(x))`` over all 128 experts; the
  top 6 by ``s + e_score_correction_bias``; weights the chosen experts'
  unbiased ``s`` over their sum + ``norm_topk_eps`` (1e-20) times
  ``routed_scaling_factor`` (2.5); an expert is ``down(relu(up x) **
  2)`` at width 1,856; plus the shared expert, the same form at 3,712,
  on every token. After the last layer RMSNorm, then the untied head;
- what the config has no key for, listed under ``assumed`` in the
  configuration file: ``d_inner`` = heads x head width, the split
  orders, no rotation, the gated norm's order and groups, the draws of
  ``A_log``, ``dt_bias``, ``D`` and the convolution's bias, and the
  correction bias as the source's balancing rule leaves it at the
  seed's weights (``balance_biases``);
- the chip's share of an expert-parallel deployment
  (``model["experts_held"] = [first, count]``): the router scores all
  ``n_routed_experts``; only the held experts' terms of a token's
  weighted sum are computed and the shared expert's whole; what the
  absent experts would add is left out, and that partial sum goes on.
  The vocabulary slice is a smaller vocabulary;
- FedAvg (McMahan et al. 2017) and FedML's cohort rule, as
  ``fedavg_resnet_gn.py`` does.

Departures from the published description, each also in the
configuration's ``departures``: **the second (denoiser) tower and the
block-diffusion loss are left out** -- ``config.json`` gives no shape
of them; the tower it describes is trained causally on next-token
loss; the held share; the cut in depth (published layers 0..6, the
pattern's repeating unit); the dual form's and the attention's masked
products computed a block of ``BLOCK`` queries at a time against the
keys up to that block's end (the state-space one a group of heads at a
time), each block recomputed in the backward pass (``jax.checkpoint``)
so that one block's ``[heads, BLOCK, keys]`` arrays live at a time --
the same sums, without the blocks that are all mask.

A batch's loss is the mean next-token cross-entropy over the tokens of
its real sequences; its gradient is accumulated **sequence by
sequence**. Between clients the running aggregate lives on the host.

Parameter names mirror the flax tree of ``models/decoder.py`` because
that tree is the program's interface for handing weights over.

``quant`` is the control's hook (``controls.py``): every weight product
(``in_proj`` / ``out_proj``, q/k/v/o, the experts' and the shared
expert's two matrices, the head) takes its operands through
``quant.operand`` and its result through ``quant.grad``; the router,
the norms, the convolution, ``dt``, the decays, the dual form's masked
products, the softmax and the loss stay float32. ``row_keep`` and
``fault`` plant the faults the limits have to catch (tests and limit
readings only): every ``row_keep``-th sequence of a client kept (at a
batch of one every other step finds its batch empty); ``"state_reset"``
(the carried state dropped at every ``chunk_size``-token chunk's start:
``L`` masked to its diagonal blocks), ``"no_d_skip"`` (``D x`` left
out), ``"norm_all_channels"`` (the gated norm's statistics over all
``d_inner`` channels), ``"no_scaling"`` (``routed_scaling_factor``
dropped), ``"no_shared"`` (the shared expert left out), ``"relu"``
(``relu`` for ``relu ** 2``, routed and shared alike).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FULL, SSM, EXPERTS = "full_attention", "mamba", "moe"
NEG_INF = -1e30
BLOCK = 1024
# the correction bias's fit (``balance_biases``): sequences x tokens drawn
# from the seed, and the steps of the balancing rule
BALANCE_TOKENS, BALANCE_STEPS = (16, 2048), 400
FAULTS = (None, "state_reset", "no_d_skip", "norm_all_channels", "no_scaling", "no_shared", "relu")


def _ssm_sizes(model: dict):
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    return h, p, g, n, h * p, g * n


# -- weights -----------------------------------------------------------
def param_shapes(model: dict) -> dict:
    """The tree of shapes, from the configuration's sizes alone."""
    c, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    held, width = model["experts_held"][1], model["moe_intermediate_size"]
    shared = model["moe_shared_expert_intermediate_size"]
    h, _, _, _, inner, bc = _ssm_sizes(model)
    sublayer = {
        SSM: {
            "ssm_norm": {"scale": (c,)},
            "ssm": {
                "in_proj": {"kernel": (c, 2 * inner + 2 * bc + h)},
                "conv_kernel": (model["conv_kernel"], inner + 2 * bc), "conv_bias": (inner + 2 * bc,),
                "A_log": (h,), "D": (h,), "dt_bias": (h,), "norm_scale": (inner,),
                "out_proj": {"kernel": (inner, c)},
            },
        },
        FULL: {
            "attn_norm": {"scale": (c,)},
            "attn": {
                "q_proj": {"kernel": (c, heads * d)}, "k_proj": {"kernel": (c, kv * d)},
                "v_proj": {"kernel": (c, kv * d)}, "o_proj": {"kernel": (heads * d, c)},
            },
        },
        EXPERTS: {
            "ffn_norm": {"scale": (c,)},
            "moe": {
                "router": {"kernel": (c, model["n_routed_experts"])},
                "expert_bias": (model["n_routed_experts"],),
                "up_proj": (held, c, width), "down_proj": (held, width, c),
                "shared": {"up_proj": {"kernel": (c, shared)}, "down_proj": {"kernel": (shared, c)}},
            },
        },
    }
    tree = {f"layer_{i}": sublayer[kind] for i, kind in enumerate(model["layer_types"])}
    tree["embed"] = {"embedding": (model["vocab_size"], c)}
    tree["final_norm"] = {"scale": (c,)}
    tree["lm_head"] = {"kernel": (c, model["vocab_size"])}
    return tree


def init_params(seed: int, model: dict):
    """Seeded random weights on the device (the configuration's
    ``assumed``): normal kernels of standard deviation ``fan_in ** -0.5``
    (the second-to-last axis: the taps of the convolution), unit-variance
    embedding rows, unit norm scales and ``D``; the convolution's bias
    uniform in +-``taps ** -0.5``; ``A_log = log U(1, 16)``; ``dt_bias``
    the inverse softplus of a draw log-uniform in [``time_step_min``,
    ``time_step_max``] floored at ``time_step_floor``; the experts'
    correction bias fitted to those weights by ``balance_biases``."""
    is_shape = lambda x: isinstance(x, tuple)
    shapes = param_shapes(model)
    paths = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=is_shape)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))
    lo, hi, floor = (float(model[k]) for k in ("time_step_min", "time_step_max", "time_step_floor"))

    @jax.jit
    def make():
        out = []
        for i, (path, shape) in enumerate(paths):
            name, key = path[-1].key, jax.random.fold_in(root, i)
            if name in ("scale", "norm_scale", "D"):
                leaf = jnp.ones(shape, jnp.float32)
            elif name == "A_log":
                leaf = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(lo), np.log(hi)))
                dt = jnp.maximum(dt, floor)
                leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus(leaf) == dt
            elif name == "conv_bias":
                bound = model["conv_kernel"] ** -0.5
                leaf = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
            elif name == "expert_bias":
                leaf = jnp.zeros(shape, jnp.float32)
            else:
                std = 1.0 if name == "embedding" else shape[-2] ** -0.5
                leaf = std * jax.random.normal(key, shape, jnp.float32)
            out.append(leaf)
        return out

    tree = jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=is_shape), make())
    return balance_biases(tree, model, jax.random.fold_in(root, len(paths)))


def _balanced_bias(score, k: int):
    """The bias that evens the experts' loads over ``score`` [N, E]
    (sigmoid scores): from zero, ``BALANCE_STEPS`` steps of the source's
    rule ``b_e += gamma * sign(mean load - load_e)``, the loads counted
    from the top ``k`` of ``score + b``, ``gamma`` falling from 0.03 to
    3e-4; centred (a constant moves no choice)."""
    n, e = score.shape

    def step(b, gamma):
        _, chosen = jax.lax.top_k(score + b, k)
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return b + gamma * jnp.sign(n * k / e - load), None

    b, _ = jax.lax.scan(step, jnp.zeros((e,), jnp.float32), jnp.geomspace(3e-2, 3e-4, BALANCE_STEPS))
    return b - b.mean()


def balance_biases(params, model: dict, key):
    """``params`` with every expert layer's correction bias set as the
    source's balancing leaves it -- the experts' loads even at these
    weights -- layer after layer (a layer's bias moves what the later
    routers see), over ``BALANCE_TOKENS`` uniform token ids drawn from
    ``key``. The source trains this bias, outside the loss, to that end;
    a bias drawn at random leaves the loads to the router's draw: the
    hidden state's part that all tokens share (SiLU's and squared
    ReLU's positive means) shifts every expert's score by its own
    offset, a share of 8 of 128 experts then takes 0.76 to 1.40 of an
    even load a layer by the seed, and a run's time followed it (PERF.md
    section 6, PR 34)."""
    tokens = jax.random.randint(key, BALANCE_TOKENS, 0, model["vocab_size"])

    @jax.jit
    def fit(params):
        x = params["embed"]["embedding"][tokens]  # [S, T, C]
        for i, kind in enumerate(model["layer_types"]):
            p = params[f"layer_{i}"]
            if kind == EXPERTS:
                h = _rms(x, p["ffn_norm"]["scale"], model["norm_eps"]).reshape(-1, x.shape[-1])
                score = jax.nn.sigmoid(jnp.dot(h, p["moe"]["router"]["kernel"], precision=HIGHEST))
                p = {**p, "moe": {**p["moe"], "expert_bias": _balanced_bias(score, model["num_experts_per_tok"])}}
                params = {**params, f"layer_{i}": p}
            x = jax.lax.map(lambda seq: _sublayer(seq, p, kind, model, None, None), x)
        return params

    return fit(params)


# -- one sequence's forward pass ---------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _linear(x, w, quant):
    """A weight product: the control's hook sits here."""
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return y if quant is None else quant.grad(y)


def _blocks(t: int):
    block = BLOCK if t % BLOCK == 0 else t
    return [(q0, q0 + block) for q0 in range(0, t, block)]


def _ssm_dual(x, dt, a_head, b, c, d_head, chunk: int, fault):
    """The state-space layer's dense dual form: ``x`` [T, H, P], ``dt``
    [T, H], ``a_head`` / ``d_head`` [H], ``b`` / ``c`` [T, G, N] ->
    [T, H, P]. ``a`` is the running sum of ``dt A`` over the *whole*
    sequence; a block of queries meets the keys up to its end, one
    group of heads at a time."""
    t, h, p = x.shape
    g = b.shape[1]
    per = h // g
    a = jnp.cumsum(dt * a_head, axis=0)  # [T, H]
    by_group = lambda v: jnp.moveaxis(v.reshape((t, g, per) + v.shape[2:]), 1, 0)  # [G, T, per, ...]
    xg, ag, dtg = by_group(x), by_group(a), by_group(dt)
    bg, cg = jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0)  # [G, T, N]
    out = []
    for q0, hi in _blocks(t):
        rows, cols = jnp.arange(q0, hi)[:, None], jnp.arange(hi)[None]
        keep = rows >= cols
        if fault == "state_reset":
            keep = keep & (rows // chunk == cols // chunk)

        @jax.checkpoint
        def one_group(xs, as_, dts, bs, cs):
            scores = jnp.dot(cs[q0:hi], bs[:hi].T, precision=HIGHEST)  # [blk, hi]
            diff = as_[q0:hi].T[:, :, None] - as_[:hi].T[:, None, :]  # [per, blk, hi]
            decay = jnp.exp(jnp.where(keep, diff, -jnp.inf))
            masked = decay * scores[None] * dts[:hi].T[:, None, :]
            return jnp.einsum("rqk,krp->qrp", masked, xs[:hi], precision=HIGHEST)

        y = jax.lax.map(lambda v: one_group(*v), (xg, ag, dtg, bg, cg))  # [G, blk, per, P]
        out.append(jnp.moveaxis(y, 0, 1).reshape(hi - q0, h, p))
    y = jnp.concatenate(out, axis=0)
    return y if fault == "no_d_skip" else y + d_head[:, None] * x


def _ssm_op(u, p, model, quant, fault):
    """The Mamba-2 mixer, [T, C] -> [T, C]."""
    t = u.shape[0]
    h, hp, g, n, inner, bc = _ssm_sizes(model)
    z, xbc, dt = jnp.split(_linear(u, p["in_proj"]["kernel"], quant), [inner, 2 * inner + 2 * bc], axis=-1)
    taps = p["conv_kernel"]  # [taps, channels]; the last tap sits on the token itself
    padded = jnp.pad(xbc, ((taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[j] * padded[j:j + t] for j in range(taps.shape[0])) + p["conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    y = _ssm_dual(
        x.reshape(t, h, hp), jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(t, g, n), c.reshape(t, g, n), p["D"], model["chunk_size"], fault)
    gated = y.reshape(t, inner) * jax.nn.silu(z)
    groups = 1 if fault == "norm_all_channels" else g
    gated = gated.reshape(t, groups, inner // groups)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + model["norm_eps"])
    return _linear(gated.reshape(t, inner) * p["norm_scale"], p["out_proj"]["kernel"], quant)


def _causal_attention(q, k, v):
    """Dense masked grouped-KV attention, ``BLOCK`` queries at a time
    against the keys up to their block's end: [T, H, D] x [T, KV, D] ->
    [T, H, D]."""
    t, h, d = q.shape
    kv = k.shape[1]
    out = []
    for q0, hi in _blocks(t):
        keep = jnp.arange(q0, hi)[:, None] >= jnp.arange(hi)[None]

        @jax.checkpoint
        def block(qb, kb, vb):
            qg = qb.reshape(hi - q0, kv, h // kv, d)
            s = jnp.einsum("qhgd,khd->hgqk", qg, kb, precision=HIGHEST) * d ** -0.5
            p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
            return jnp.einsum("hgqk,khd->qhgd", p, vb, precision=HIGHEST).reshape(hi - q0, h, d)

        out.append(block(q[q0:hi], k[:hi], v[:hi]))
    return jnp.concatenate(out, axis=0)


def _attention_op(a, p, model, quant):
    t, d = a.shape[0], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    q = _linear(a, p["q_proj"]["kernel"], quant).reshape(t, h, d)
    k = _linear(a, p["k_proj"]["kernel"], quant).reshape(t, kv, d)
    v = _linear(a, p["v_proj"]["kernel"], quant).reshape(t, kv, d)
    return _linear(_causal_attention(q, k, v).reshape(t, h * d), p["o_proj"]["kernel"], quant)


def _relu2_mlp(x, up, down, quant, fault):
    h = jax.nn.relu(_linear(x, up, quant))
    return _linear(h if fault == "relu" else h * h, down, quant)


def _experts(x, p, model, quant, fault):
    """The held experts' part of the routed sum and the shared expert's
    whole, [T, C] -> [T, C]."""
    first, held = model["experts_held"]
    k = model["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"], precision=HIGHEST))
    _, expert = jax.lax.top_k(score + p["expert_bias"], k)
    weight = jnp.take_along_axis(score, expert, axis=-1)
    if model["norm_topk_prob"]:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + model["norm_topk_eps"])
    if fault != "no_scaling":
        weight = weight * model["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(expert == first + e, weight, 0.0), axis=-1)  # [T]
        y = y + w_e[:, None] * _relu2_mlp(x, p["up_proj"][e], p["down_proj"][e], quant, fault)
    if fault != "no_shared":
        s = p["shared"]
        y = y + _relu2_mlp(x, s["up_proj"]["kernel"], s["down_proj"]["kernel"], quant, fault)
    return y


def _sublayer(x, p, kind: str, model: dict, quant, fault):
    """One layer, [T, C] -> [T, C]: ``x + mixer(norm(x))``."""
    eps = model["norm_eps"]
    if kind == SSM:
        return x + _ssm_op(_rms(x, p["ssm_norm"]["scale"], eps), p["ssm"], model, quant, fault)
    if kind == FULL:
        return x + _attention_op(_rms(x, p["attn_norm"]["scale"], eps), p["attn"], model, quant)
    return x + _experts(_rms(x, p["ffn_norm"]["scale"], eps), p["moe"], model, quant, fault)


def forward(params, tokens, model: dict, quant=None, fault=None):
    """One sequence of token ids [T] -> logits [T, vocab]."""
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(model["layer_types"]):
        x = _sublayer(x, params[f"layer_{i}"], kind, model, quant, fault)
    x = _rms(x, params["final_norm"]["scale"], model["norm_eps"])
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
