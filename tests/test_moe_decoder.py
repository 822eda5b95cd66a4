"""The config-driven MoE decoder (``models/decoder.py``), the windowed
grouped-KV flash kernel and the held-share expert layer, each against
the plain reference the benchmark compares with
(``benchmark/reference/fedavg_mellum2.py``, loaded by path: float32,
dense masked attention, a loop over experts, no kernel, no vmap).

Small sizes on the CPU: hidden 64, 4 query / 2 KV heads of 16, 8 experts
top-2 of width 32, window 8, T 32, vocabulary 64, layers S S S F. What a
scope or a kernel *costs* is the chip's to say.

A second configuration of the same block goes through the cases whose
assertion is the same (``case``): gated short-convolution layers beside
one full-attention layer (C F C C C), a dense leading layer of 96, a
sigmoid router with a selection bias, a tied head, a federation that
leaves whole batches empty -- against its own plain reference
(``benchmark/reference/fedavg_lfm2.py``). A third, one sublayer a
layer (state-space mixers, squared-ReLU and shared experts), against
``fedavg_twotower.py``. ``test_gated_decoder.py`` puts a fourth through
the same cases.
"""

import argparse
import functools
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import freeze

import fedml_tpu
from fedml_tpu import data, models
from fedml_tpu.arguments import Arguments
from fedml_tpu.models.decoder import (
    CONV, EXPERTS, FULL, SLIDING, SSM, DecoderBlock, GatedShortConv, HeldExperts, dense_attention,
    rope_inv_freq,
)
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.parallel.expert import ep_specs, experts_held

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/fedavg_mellum2.py", "ref_fedavg_mellum2")
ref_conv = _load("benchmark/reference/fedavg_lfm2.py", "ref_fedavg_lfm2")
ref_ssm = _load("benchmark/reference/fedavg_twotower.py", "ref_fedavg_twotower")

ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
           "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
MODEL = {  # the reference's keys are the published config's
    "vocab_size": 64, "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [SLIDING, SLIDING, SLIDING, FULL],
    "sliding_window": 8, "rope_parameters": ROPE, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "experts_held": [4, 4],
}
T = 32


def _args(**over):
    flat = dict(
        model="moe_decoder", dataset="token_stream", vocab_size=64, seq_len=T, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8, experts_per_token=2,
        expert_dim=32, sliding_window=8, layer_types=list(MODEL["layer_types"]),
        rope_parameters=ROPE, expert_parallel=2, expert_rank=1, attention_impl="full",
    )
    flat.update(over)
    return argparse.Namespace(**flat)


# the second configuration: conv layers, a dense leading layer, a biased
# sigmoid router, a tied head
MODEL_CONV = {
    "vocab_size": 64, "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [CONV, FULL, CONV, CONV, CONV], "num_dense_layers": 1,
    "intermediate_size": 96, "conv_L_cache": 3, "rope_theta": 1e6, "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32, "norm_topk_prob": True,
    "use_expert_bias": True, "expert_bias_std": 0.05, "routed_scaling_factor": 1.0, "norm_eps": 1e-5,
    "experts_held": [4, 4],
}


def _args_conv(**over):
    flat = dict(
        model="moe_decoder", dataset="token_stream", vocab_size=64, seq_len=T, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8, experts_per_token=2,
        expert_dim=32, layer_types=list(MODEL_CONV["layer_types"]), num_dense_layers=1,
        intermediate_size=96, conv_L_cache=3,
        rope_parameters={FULL: {"rope_type": "default", "rope_theta": 1e6}}, router_scoring="sigmoid",
        use_expert_bias=True, norm_topk_eps=1e-6, rms_norm_eps=1e-5,
        tie_word_embeddings=True, expert_parallel=2, expert_rank=1, attention_impl="full",
    )
    flat.update(over)
    return argparse.Namespace(**flat)


# the third configuration: one sublayer a layer -- state-space mixers,
# unrotated attention without q/k norm, squared-ReLU experts beside a
# shared expert behind a biased sigmoid router with a scaling factor
MODEL_SSM = {
    "vocab_size": 64, "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [SSM, EXPERTS, SSM, EXPERTS, SSM, FULL, EXPERTS],
    "num_dense_layers": 4,  # the sublayers without routed experts
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
    "chunk_size": 8, "time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "norm_topk_eps": 1e-20, "norm_eps": 1e-5, "experts_held": [4, 4],
}


def _args_ssm(**over):
    flat = dict(
        model="moe_decoder", dataset="token_stream", vocab_size=64, seq_len=T, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, layer_types=list(MODEL_SSM["layer_types"]),
        sublayers=True, qk_norm=False, rope_parameters={}, ssm_num_heads=8, ssm_head_dim=8,
        ssm_groups=2, ssm_state_size=16, ssm_conv_kernel=4, ssm_chunk_size=8, num_experts=8,
        experts_per_token=2, expert_dim=32, router_scoring="sigmoid", use_expert_bias=True,
        norm_topk_eps=1e-20, expert_activation="relu2", shared_expert_dim=48,
        routed_scaling_factor=2.5, rms_norm_eps=1e-5, expert_parallel=2, expert_rank=1,
        attention_impl="full",
    )
    flat.update(over)
    return argparse.Namespace(**flat)


class Case:
    """One configuration of the block with its plain reference; the
    seeded weights are drawn on first use. ``ragged``: its federated
    round runs lane after lane (``one_round``)."""

    def __init__(self, ref, model, args, faults, scopes, counters, ragged=False):
        self.ref, self.model, self.args = ref, model, args
        self.faults, self.scopes, self.counters, self.ragged = faults, scopes, counters, ragged

    @functools.cached_property
    def weights(self):
        return self.ref.init_params(7, self.model)


LM_SCOPES = ("lm.embed", "moe.route", "moe.experts", "moe.combine", "lm.head_loss")
MOE_COUNTERS = ("moe_local_hits", "moe_expert_tokens_max", "moe_expert_tokens_mean", "moe_dropped",
                "moe_kernel_rows")
CASES = {
    "window_softmax": Case(
        ref, MODEL, _args, ("no_window", "no_renorm", "no_yarn"),
        LM_SCOPES + ("blk.attn.window", "blk.attn.full"), MOE_COUNTERS),
    "conv_sigmoid": Case(
        ref_conv, MODEL_CONV, _args_conv,
        ("no_bias", "acausal_conv", "no_c_gate", "no_renorm", "dense_width"),
        LM_SCOPES + ("blk.attn.full", "blk.conv", "blk.mlp.dense"), MOE_COUNTERS + ("moe_bias_moved",),
        ragged=True),
    "ssm_relu2": Case(
        ref_ssm, MODEL_SSM, _args_ssm, tuple(f for f in ref_ssm.FAULTS if f),
        LM_SCOPES + ("blk.attn.full", "blk.ssm", "blk.ssm.scan", "moe.shared"),
        MOE_COUNTERS + ("moe_bias_moved", "ssm_chunks"), ragged=True),
}


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    return CASES[request.param]


@pytest.fixture(scope="module")
def weights():
    return CASES["window_softmax"].weights


@pytest.fixture(scope="module")
def weights_conv():
    return CASES["conv_sigmoid"].weights


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, T + 1), 0, 64)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


# -- the model against the reference -----------------------------------
def test_parameter_tree_is_the_references(case):
    m = models.create(case.args(), 64)
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), m.init(jax.random.PRNGKey(0)))
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), case.weights)
    assert have == want


def test_the_softmax_configuration_grew_no_leaf_and_no_counter(weights, tokens):
    """What the second configuration added is off by default: no
    ``expert_bias`` leaf, no ``mlp``, an untied head, and the five
    counters there were (the fifth, ``moe_kernel_rows``, every expert
    layer sows)."""
    m = models.create(_args(), 64)
    tree = m.init(jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "final_norm", "lm_head"} | {f"layer_{i}" for i in range(4)}
    assert all(set(tree[f"layer_{i}"]) == {"attn_norm", "attn", "ffn_norm", "moe"} for i in range(4))
    assert set(tree["layer_0"]["moe"]) == {"router", "gate_proj", "up_proj", "down_proj"}
    assert set(m.apply_counted(weights, tokens[:, :-1])[1]) == set(MOE_COUNTERS)


@pytest.mark.parametrize("remat", [False, True])
def test_forward_matches_reference(case, tokens, remat):
    m = models.create(case.args(remat=remat), 64)
    with jax.default_matmul_precision("highest"):
        got = m.apply(case.weights, tokens[:, :-1])
        want = jnp.stack([case.ref.forward(case.weights, t[:-1], case.model) for t in tokens])
    assert got.dtype == jnp.float32 and got.shape == (3, T, 64)
    assert _close(got, want, 2e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_gradients_match_reference(case, tokens, remat):
    m = models.create(case.args(remat=remat), 64)
    x, y = tokens[:, :-1], tokens[:, 1:]

    def prog(p):
        logp = jax.nn.log_softmax(m.apply(p, x), axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).sum()

    def plain(p):
        return sum(case.ref._sequence_loss_sum(p, a, b, case.model, None, None) for a, b in zip(x, y))

    with jax.default_matmul_precision("highest"):
        (lp, gp), (lr, gr) = jax.value_and_grad(prog)(case.weights), jax.value_and_grad(plain)(case.weights)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    bad = [jax.tree_util.keystr(k) for (k, a), b in zip(
        jax.tree_util.tree_leaves_with_path(gp), jax.tree.leaves(gr)) if not _close(a, b, 2e-4)]
    assert not bad, bad


@pytest.mark.parametrize("name,fault", [(n, f) for n in CASES for f in CASES[n].faults])
def test_each_planted_fault_moves_the_reference(tokens, name, fault):
    """What the benchmark's limits have to catch is not a no-op at this
    size: the reference with a fault planted disagrees with itself."""
    c = CASES[name]
    with jax.default_matmul_precision("highest"):
        good = c.ref.forward(c.weights, tokens[0, :-1], c.model)
        bad = c.ref.forward(c.weights, tokens[0, :-1], c.model, fault=fault)
    assert not _close(bad, good, 1e-3)


def test_flash_path_matches_dense_path(case):
    """attention_impl flash (the interpreter here) and full agree on a
    sequence the kernel can tile; a window wider than T is every key."""
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0, 64)
    over = dict(seq_len=128, sliding_window=40)
    with jax.default_matmul_precision("highest"):
        dense = models.create(case.args(**over), 64).apply(case.weights, toks)
        flash = models.create(case.args(attention_impl="flash", **over), 64).apply(case.weights, toks)
    assert _close(flash, dense, 2e-5)


def test_unknown_layer_kind_and_scoring_are_refused(weights_conv, tokens):
    with pytest.raises(ValueError, match="layer type"):
        models.create(_args_conv(layer_types=["conv", "recurrent"]), 64).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="scoring"):
        models.create(_args_conv(router_scoring="tanh"), 64).init(jax.random.PRNGKey(0))


def test_flash_refuses_a_sequence_it_cannot_tile(weights, tokens):
    m = models.create(_args(attention_impl="flash"), 64)
    with pytest.raises(ValueError, match="multiple"):
        m.apply(weights, tokens[:, :-1])  # T = 32: never a quiet dense fallback


# -- the kernel against dense masked attention -------------------------
FLASH_CASES = [  # H, KV, window, T, block
    (4, 4, None, 256, 128), (4, 2, None, 256, 128), (8, 2, 100, 384, 128),
    (4, 1, 128, 256, 128), (4, 2, 300, 512, 256), (4, 2, 1, 256, 128),
]


def _qkv(h, kv, t, seed=0, b=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, h, d)), jax.random.normal(ks[1], (b, t, kv, d)),
            jax.random.normal(ks[2], (b, t, kv, d)), jax.random.normal(ks[3], (b, t, h, d)))


@pytest.mark.parametrize("h,kv,window,t,block", FLASH_CASES)
def test_flash_window_gqa_forward(h, kv, window, t, block):
    q, k, v, _ = _qkv(h, kv, t)
    got = flash_attention(q, k, v, True, None, block, block, window)
    assert _close(got, dense_attention(q, k, v, window), 1e-5)


def test_flash_forward_32_query_8_kv_heads_of_64():
    """The second configuration's attention layer: a group of 4 at half
    a lane tile's head width."""
    q, k, v, _ = _qkv(32, 8, 256, b=1, d=64)
    got = flash_attention(q, k, v, True, None, 128, 128, None)
    assert _close(got, dense_attention(q, k, v, None), 1e-5)


# the backward's cases: the forward's (window 1 left out: it sees only
# itself, every gradient of q and k is exactly 0) at D 16 in float32, then
# a group of 8 on one KV head, a window that no tile divides, a window
# wider than the sequence, D = 64 (half a lane tile) and D = 128, and
# bfloat16 inputs -- H, KV, window, T, block, D, dtype
BACKWARD_CASES = [c + (16, jnp.float32) for c in FLASH_CASES[:-1]] + [
    (8, 1, None, 384, 128, 16, jnp.float32), (2, 1, 1000, 2048, 512, 16, jnp.float32),
    (4, 2, 512, 384, 128, 16, jnp.float32), (2, 2, None, 384, 128, 64, jnp.float32),
    (4, 2, 100, 384, 128, 128, jnp.float32), (8, 1, 100, 384, 128, 64, jnp.bfloat16),
    (4, 2, None, 256, 256, 128, jnp.bfloat16),
    # 32 query heads on 8 KV heads of 64, no window: the second configuration's layer
    (32, 8, None, 256, 128, 64, jnp.float32), (32, 8, None, 256, 128, 64, jnp.bfloat16),
]  # (T 384 walks 3 x 3 tiles of 128, T 2,048 4 x 4 of 512, T 256 and 512 one)


@pytest.mark.parametrize("h,kv,window,t,block,d,dtype", BACKWARD_CASES)
def test_flash_window_gqa_backward(h, kv, window, t, block, d, dtype):
    """Against dense attention's gradient: float32 inputs to 2e-5 of the
    largest entry; bfloat16 inputs against dense float32 attention on the
    same rounded inputs to one bfloat16 rounding, 2**-8, in norm."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, w = (a.astype(dtype) for a in _qkv(h, kv, t, seed=1, b=1 if t > 512 else 2, d=d))
    f = lambda q, k, v: (f32(flash_attention(q, k, v, True, None, block, block, window)) * f32(w)).sum()
    dense = lambda q, k, v: (dense_attention(f32(q), f32(k), f32(v), window) * f32(w)).sum()
    got = jax.grad(f, (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(dense, (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape  # a KV head's gradient, summed over its group
    assert all(a.dtype == dtype for a in got)
    if dtype == jnp.float32:
        assert all(_close(a, b, 2e-5) for a, b in zip(got, want))
    else:
        errors = [float(jnp.linalg.norm(f32(a) - f32(b)) / jnp.linalg.norm(f32(b))) for a, b in zip(got, want)]
        assert all(e < 2.0 ** -8 for e in errors), errors


def test_flash_backward_bfloat16_operands_cost_one_rounding():
    """bfloat16 inputs: the backward's products take ``p`` and ``ds`` in
    bfloat16 (float32 accumulation), where the GPT-2 block's backward
    before PR 28 cast every operand up to float32. Against dense float32
    attention on the same rounded inputs, 12 heads of 64 (the GPT-2
    block), the gradients' relative error stays near one bfloat16
    rounding, 2**-8: at 1,024 tokens this backward reads 2.8e-3 / 2.7e-3
    / 2.5e-3 (dq / dk / dv) where the float32-operand one read 1.4e-3 /
    1.5e-3 / 3e-5 -- on the CPU, whose float32 products are exact; the
    chip's default precision rounds float32 operands to bfloat16 too."""
    q, k, v, w = (a.astype(jnp.bfloat16) for a in _qkv(12, 12, 512, seed=3, b=1, d=64))
    f32 = lambda a: a.astype(jnp.float32)
    f = lambda q, k, v: (f32(flash_attention(q, k, v, True, None, 128, 128)) * f32(w)).sum()
    d = lambda q, k, v: (dense_attention(f32(q), f32(k), f32(v), None) * f32(w)).sum()
    got = jax.grad(f, (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(d, (0, 1, 2))(q, k, v)
    errors = [float(jnp.linalg.norm(f32(a) - f32(b)) / jnp.linalg.norm(f32(b))) for a, b in zip(got, want)]
    assert all(a.dtype == jnp.bfloat16 for a in got)
    assert all(e < 2.0 ** -8 for e in errors), errors


def test_flash_vmapped_lanes():
    q, k, v, _ = _qkv(4, 2, 256, seed=2, b=3)
    lanes = lambda a: a[:, None]  # three lanes of batch 1
    f = lambda q, k, v: (flash_attention(q, k, v, True, None, 128, 128, 100) ** 2).sum()
    d = lambda q, k, v: (dense_attention(q, k, v, 100) ** 2).sum()
    got = jax.vmap(jax.grad(f, (0, 1, 2)))(lanes(q), lanes(k), lanes(v))
    want = jax.vmap(jax.grad(d, (0, 1, 2)))(lanes(q), lanes(k), lanes(v))
    assert all(_close(a, b, 2e-5) for a, b in zip(got, want))


@pytest.mark.parametrize("bad", ["heads", "window_noncausal", "window_zero", "kv_shape"])
def test_flash_shape_rules(bad):
    q, k, v, _ = _qkv(4, 2, 256)
    with pytest.raises(ValueError):
        if bad == "heads":
            flash_attention(q, jnp.tile(k, (1, 1, 2, 1))[:, :, :3], jnp.tile(v, (1, 1, 2, 1))[:, :, :3])
        elif bad == "window_noncausal":
            flash_attention(q, k, v, False, None, 128, 128, 64)
        elif bad == "window_zero":
            flash_attention(q, k, v, True, None, 128, 128, 0)
        else:
            flash_attention(q, k[:, :128], v[:, :128])


# -- rotary tables -----------------------------------------------------
def test_default_rope_closed_form():
    inv_freq, scale = rope_inv_freq(128, {"rope_type": "default", "rope_theta": 500000})
    want = [500000.0 ** (-2 * i / 128) for i in range(64)]
    assert scale == 1.0 and np.allclose(inv_freq, want, rtol=1e-6)


def test_yarn_closed_form():
    """The published full-attention parameters: the dimensions that turn
    32 times and once over 8,192 positions are 18.08 and 34.99, so the
    ramp runs from pair 18 to pair 35; below it the frequencies stay,
    above it they are divided by 16."""
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782}
    inv_freq, scale = rope_inv_freq(128, rope)
    base = np.array([500000.0 ** (-2 * i / 128) for i in range(64)])
    dim = lambda turns: 128 * math.log(8192 / (turns * 2 * math.pi)) / (2 * math.log(500000))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    assert np.allclose(inv_freq[:19], base[:19], rtol=1e-6)
    assert np.allclose(inv_freq[35:], base[35:] / 16, rtol=1e-6)
    mid = 25
    ramp = (mid - 18) / (35 - 18)
    assert np.isclose(inv_freq[mid], base[mid] / 16 * ramp + base[mid] * (1 - ramp), rtol=1e-6)
    assert scale == pytest.approx(1.2772588722239782)
    # unset, the factor is 0.1 ln(16) + 1: the published number
    del rope["attention_factor"]
    assert rope_inv_freq(128, rope)[1] == pytest.approx(0.1 * math.log(16) + 1)
    assert np.allclose(ref.rope_inv_freq(128, rope)[0], inv_freq, rtol=1e-6)


def test_unknown_rope_type_is_refused():
    with pytest.raises(ValueError, match="rope_type"):
        rope_inv_freq(16, {"rope_type": "linear", "rope_theta": 1e4})


# -- the held share ----------------------------------------------------
def _layer(first, held, **kw):
    return HeldExperts(num_experts=8, experts_per_token=2, expert_dim=32,
                       experts_held=(first, held), **kw)


def _uncut(weights):
    """Layer 0's expert layer with all 8 experts: router from the
    fixture, stacks drawn here."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    return {
        "router": weights["layer_0"]["moe"]["router"],
        "gate_proj": jax.random.normal(ks[0], (8, 64, 32)) / 8,
        "up_proj": jax.random.normal(ks[1], (8, 64, 32)) / 8,
        "down_proj": jax.random.normal(ks[2], (8, 32, 64)) / 6,
    }


def _share(p, first, held):
    cut = lambda a: a[first:first + held]
    return {"router": p["router"], "gate_proj": cut(p["gate_proj"]),
            "up_proj": cut(p["up_proj"]), "down_proj": cut(p["down_proj"])}


@pytest.mark.parametrize("ep", [1, 2, 4, 8])
def test_the_shares_add_up(weights, ep):
    """The partial outputs of all ``ep`` shares sum to the uncut
    reference layer: nothing is computed twice, nothing is left out."""
    p = _uncut(weights)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))
    whole = dict(MODEL, experts_held=[0, 8])
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._experts(s, p, whole, None, None) for s in x])
        got = sum(
            _layer(*experts_held(8, ep, r)).apply({"params": _share(p, *experts_held(8, ep, r))}, x)
            for r in range(ep))
        # and each share is the reference's share
        one = ref._experts(x[0], _share(p, 4, 4), MODEL, None, None)
        mine = _layer(4, 4).apply({"params": _share(p, 4, 4)}, x[:1])[0]
    assert _close(got, want, 2e-5)
    assert _close(mine, one, 2e-5)


@pytest.mark.parametrize("held_first", [0, 6])
def test_no_token_dropped_when_every_token_picks_one_expert(weights, held_first):
    """The worst imbalance: every token's first choice is one held
    expert. All N rows land in its group, none is dropped."""
    p = _uncut(weights)
    hot = held_first + 1
    router = np.zeros((64, 8), np.float32)
    router[:, hot] = 1.0  # x is positive below: expert `hot` wins everywhere
    p = dict(p, router={"kernel": jnp.asarray(router)})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (1, T, 64))) + 0.1
    layer = _layer(held_first, 2)
    with jax.default_matmul_precision("highest"):
        got, state = layer.apply({"params": _share(p, held_first, 2)}, x, mutable=["counters"])
        want = ref._experts(x[0], _share(p, held_first, 2), dict(MODEL, experts_held=[held_first, 2]),
                            None, None)
    c = {k: float(v) for k, v in state["counters"].items()}
    assert c["moe_dropped"] == 0.0
    assert c["moe_expert_tokens_max"] == T  # every token is in the hot expert's group
    assert T <= c["moe_local_hits"] <= 2 * T
    assert _close(got[0], want, 2e-5)
    # more than one chunk (an even load's rows and a quarter is 20 of
    # these 32), the last one part empty: the written-out backward too
    share = _share(p, held_first, 2)
    g = jax.random.normal(jax.random.PRNGKey(8), (T, 64))
    with jax.default_matmul_precision("highest"):
        mine = jax.grad(lambda q, x: jnp.sum(layer.apply({"params": q}, x)[0] * g), (0, 1))(share, x)
        plain = jax.grad(lambda q, x: jnp.sum(ref._experts(
            x[0], q, dict(MODEL, experts_held=[held_first, 2]), None, None) * g), (0, 1))(share, x)
    assert all(_close(a, b, 2e-4) for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(plain)))


@pytest.mark.parametrize("broken", ["one_chunk_short", "groups_too_small"])
def test_moe_dropped_counts_what_the_chunk_loop_left_out(weights, monkeypatch, broken):
    """``moe_dropped`` is the choices on held experts less the rows the
    grouped product really ran on: a chunk loop that stops early, or
    that hands the product smaller groups, reads above 0."""
    from fedml_tpu.models import decoder

    real = decoder._chunks

    def chunks(tok, weight, sizes, rows):
        count, slice_of = real(tok, weight, sizes, rows)
        if broken == "one_chunk_short":
            return count - 1, slice_of

        def fewer(c):
            t, w, inside, valid = slice_of(c)
            return t, w, inside // 2, valid
        return count, fewer

    p = _share(_uncut(weights), 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))
    _, state = _layer(0, 4).apply({"params": p}, x, mutable=["counters"])
    assert float(state["counters"]["moe_dropped"]) == 0.0
    monkeypatch.setattr(decoder, "_chunks", chunks)
    _, state = _layer(0, 4).apply({"params": p}, x, mutable=["counters"])
    c = {k: float(v) for k, v in state["counters"].items()}
    assert 0 < c["moe_dropped"] <= c["moe_local_hits"]


def test_bad_share_is_refused():
    with pytest.raises(ValueError, match="share"):
        experts_held(8, 3)
    with pytest.raises(ValueError, match="share"):
        _layer(6, 4).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 64)))
    assert tuple(experts_held(64, 8, 1)) == (8, 8)


def test_ep_specs_find_the_held_stacks(weights):
    specs = ep_specs(weights)
    moe = specs["layer_2"]["moe"]
    assert all(moe[k][0] == "ep" for k in ("gate_proj", "up_proj", "down_proj"))
    assert tuple(moe["router"]["kernel"]) == () and tuple(specs["lm_head"]["kernel"]) == ()


def test_vmapped_lanes_carry_their_own_experts(weights, tokens):
    """The round engine's shape: a cohort vmapped over lanes, every lane
    its own weights; the grouped product runs lane after lane."""
    m = models.create(_args(remat=True), 64)
    x = tokens[:, :-1]

    def loss(p, x):
        logits, counters = m.apply_counted(p, x)
        return (logits ** 2).mean(), counters

    stacked = jax.tree.map(lambda a: jnp.stack([a, a * 1.01]), weights)
    grads, counters = jax.jit(jax.vmap(jax.grad(loss, has_aux=True)))(stacked, jnp.stack([x, x]))
    for lane in range(2):
        g, c = jax.grad(loss, has_aux=True)(jax.tree.map(lambda a: a[lane], stacked), x)
        assert all(_close(a[lane], b, 1e-4) for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g)))
        assert {k: float(v[lane]) for k, v in counters.items()} == {k: float(v) for k, v in c.items()}


# -- the gated short convolution ----------------------------------------
def _conv_params(seed=0, c=16, taps=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"in_proj": {"kernel": jax.random.normal(ks[0], (c, 3 * c)) / 4},
            "conv_kernel": jax.random.normal(ks[1], (taps, c)),
            "out_proj": {"kernel": jax.random.normal(ks[2], (c, c)) / 4}}


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_gated_short_conv_against_a_per_token_loop(taps):
    """``B, C, u = split3(in_proj(h))``; ``z_t = sum_j w_j (B u)_{t-(L-1)+j}``
    over ``L`` taps, zeros before the sequence; ``out_proj(C z)``: token
    by token in numpy, and the reference's shifted slices. Three taps
    are the configurations'; the filter's length is an argument, so
    another length (and a single tap: no neighbour read) is held to the
    same loop."""
    p = _conv_params(taps=taps)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 16))
    with jax.default_matmul_precision("highest"):
        got = GatedShortConv(taps).apply({"params": p}, x)
        plain = jnp.stack([ref_conv._conv_op(s, p, None, None) for s in x])
    w_in, w, w_out = (np.asarray(a, np.float64) for a in (
        p["in_proj"]["kernel"], p["conv_kernel"], p["out_proj"]["kernel"]))
    want = np.zeros((2, 12, 16))
    for b in range(2):
        bcu = np.asarray(x[b], np.float64) @ w_in
        gate_in, gate_out, u = bcu[:, :16], bcu[:, 16:32], bcu[:, 32:]
        for t in range(12):
            back = range(t - (taps - 1), t + 1)  # the tokens the taps sit on, the last on t itself
            z = sum(w[j] * gate_in[i] * u[i] for j, i in enumerate(back) if i >= 0)
            want[b, t] = (gate_out[t] * z) @ w_out
    assert _close(got, want, 1e-5) and _close(plain, want, 1e-5)


def test_gated_short_conv_is_causal():
    """Token t's output does not move when token t + 1 does -- and the
    reference's acausal fault does move it."""
    p = _conv_params(1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 10, 16))
    moved = x.at[0, 6].add(1.0)
    a, b = (GatedShortConv(3).apply({"params": p}, v)[0] for v in (x, moved))
    assert np.array_equal(np.asarray(a[:6]), np.asarray(b[:6])) and not _close(a[6:9], b[6:9], 1e-3)
    assert _close(a[9:], b[9:], 1e-6)  # three taps reach two tokens back
    fa, fb = (ref_conv._conv_op(v[0], p, None, "acausal_conv") for v in (x, moved))
    assert not _close(fa[5], fb[5], 1e-3)


# -- the selection bias ---------------------------------------------------
def _biased_layer(first=0, held=8, **kw):
    return HeldExperts(num_experts=8, experts_per_token=2, expert_dim=32, experts_held=(first, held),
                       scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-6, **kw)


def test_selection_bias_has_zero_gradient_and_changes_the_choices(weights_conv):
    """The bias enters the choice only: its gradient is exactly zero
    (nothing stops it: it is a leaf like any other), the weights are the
    unbiased scores, and leaving it out picks other experts."""
    p = dict(weights_conv["layer_1"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))
    layer = _biased_layer(4, 4)
    g = jax.grad(lambda q: jnp.sum(layer.apply({"params": q}, x) ** 2))(p)
    assert g["expert_bias"].shape == (8,) and not np.any(np.asarray(g["expert_bias"]))
    assert float(jnp.abs(g["router"]["kernel"]).max()) > 0
    y, state = layer.apply({"params": p}, x, mutable=["counters"])
    moved = float(state["counters"]["moe_bias_moved"])
    assert 0 < moved < 2 * 2 * T  # some of the 128 tokens' two choices, not all
    flat = dict(p, expert_bias=jnp.zeros(8))
    y0, state0 = layer.apply({"params": flat}, x, mutable=["counters"])
    assert float(state0["counters"]["moe_bias_moved"]) == 0.0 and not _close(y0, y, 1e-3)
    # the count is the choices that differ from the unbiased top-k's
    score = jax.nn.sigmoid(x.reshape(-1, 64) @ p["router"]["kernel"])
    with_bias = np.asarray(jax.lax.top_k(score + p["expert_bias"], 2)[1])
    without = np.asarray(jax.lax.top_k(score, 2)[1])
    assert moved == sum(len(set(a) - set(b)) for a, b in zip(with_bias, without))
    with jax.default_matmul_precision("highest"):
        want = ref_conv._experts(x[0], p, MODEL_CONV, None, None)
        unbiased = ref_conv._experts(x[0], p, MODEL_CONV, None, "no_bias")
    assert _close(y[0], want, 2e-5) and _close(y0[0], unbiased, 2e-5)


@pytest.mark.parametrize("ep", [1, 2, 4])
def test_the_shares_of_a_conv_layer_add_up(ep):
    """The second configuration's layer, uncut: the operator and the
    residual counted once, the partial outputs of all ``ep`` shares of
    the biased sigmoid experts on top give the reference's whole layer."""
    whole = dict(MODEL_CONV, layer_types=[CONV], num_dense_layers=0, experts_held=[0, 8])
    p = ref_conv.init_params(3, whole)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T, 64))
    with jax.default_matmul_precision("highest"):
        mid = x[0] + ref_conv._conv_op(ref_conv._rms(x[0], p["conv_norm"]["scale"], 1e-5), p["conv"], None, None)
        want = mid + ref_conv._experts(ref_conv._rms(mid, p["ffn_norm"]["scale"], 1e-5), p["moe"], whole, None, None)
        got = mid
        for r in range(ep):
            first, held = experts_held(8, ep, r)
            experts = freeze(dict(
                num_experts=8, experts_per_token=2, expert_dim=32, experts_held=(first, held),
                norm_topk_prob=True, scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-6))
            block = DecoderBlock(CONV, 4, 2, 16, None, "full", 1e-5, 3, 0, experts)
            cut = lambda a: a[first:first + held]
            share = dict(p, moe=dict(p["moe"], **{k: cut(p["moe"][k]) for k in ("gate_proj", "up_proj", "down_proj")}))
            got = got + (block.apply({"params": share}, x, None, None)[0] - mid)
    assert _close(got, want, 2e-5)


# -- data: a stated vocabulary -----------------------------------------
def test_token_data_at_a_real_vocabulary():
    """PERF.md section 7's open item: a dense [V, V] chain is 1.2 GB at
    12,288; the sparse chain draws at any vocabulary, in range, with
    y the next token of x."""
    from fedml_tpu.data.synthetic import synthetic_sequences

    x, y = synthetic_sequences(6, 512, 98304, seed=3)
    assert x.shape == y.shape == (6, 512) and (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and 50000 < x.max() < 98304
    x2, _ = synthetic_sequences(6, 512, 98304, seed=3)
    assert (x == x2).all()
    # a chain, not noise: a token's successors are few
    xs, ys = synthetic_sequences(64, 2048, 4096, seed=0)
    follows = {}
    for a, b in zip(xs.ravel(), ys.ravel()):
        follows.setdefault(int(a), set()).add(int(b))
    assert max(len(v) for v in follows.values()) <= 16


def _fed_args(args=_args, **over):
    flat = dict(vars(args(
        client_num_in_total=4, client_num_per_round=2, comm_round=1, epochs=1, batch_size=2,
        learning_rate=0.05, client_optimizer="sgd", federated_optimizer="FedAvg",
        partition_method="homo", synthetic_train_size=16, synthetic_test_size=8, shuffle=False,
        random_seed=0, frequency_of_the_test=1, backend="single_process", data_cache_dir="",
        matmul_precision="highest", remat=True)))
    flat.update(over)
    return fedml_tpu.init(Arguments(argparse.Namespace(**flat), training_type="simulation"))


def test_sliced_vocabulary_draws_scores_and_loses_over_the_slice(weights):
    args = _fed_args()
    ds = data.load(args)
    x, y = np.asarray(ds.packed_train.x), np.asarray(ds.packed_train.y)
    assert ds.class_num == 64 and x.dtype == np.int32 and x.shape == (4, 2, 2, T)
    assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) < 64
    m = models.create(args, ds.class_num)
    logits = m.apply(weights, x[0, 0])
    assert logits.shape == (2, T, 64)  # scored over the slice
    loss, metrics = m.loss_fn(logits, y[0, 0], jnp.ones((2,)))
    with jax.default_matmul_precision("highest"):
        want = sum(ref._sequence_loss_sum(weights, a, b, MODEL, None, None)
                   for a, b in zip(x[0, 0], y[0, 0])) / (2 * T)
    assert float(metrics["count"]) == 2 * T  # counted in tokens
    assert abs(float(loss) - float(want)) <= 1e-4 * float(want)


# -- one federated round through the normal path -----------------------
@pytest.fixture(scope="module")
def one_round(case):
    """``window_softmax``: 4 silos of 4 sequences, the vmapped static
    scan. The ``ragged`` cases: 10 sequences over 4 silos (3, 3, 2, 2) at
    batch 2, so two silos leave the second batch empty and -- with the
    engine's floor on a lane step's work lowered for this tiny model --
    the cohort runs lane after lane (``lax.map``)."""
    from fedml_tpu.simulation import fedavg_api

    ragged = case.ragged
    args = _fed_args(case.args, **({"synthetic_train_size": 10} if ragged else {}))
    ds = data.load(args)
    heavy = fedavg_api._HEAVY_LANE_STEP
    fedavg_api._HEAVY_LANE_STEP = 0 if ragged else heavy
    try:
        api = fedavg_api.FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    finally:
        fedavg_api._HEAVY_LANE_STEP = heavy
    assert api._round_exec_name() == ("simulation.round_fn_ragged" if ragged else "simulation.round_fn")
    api.global_params = jax.tree.map(jnp.copy, case.weights)
    api.train()
    return args, ds, api


def test_one_round_matches_the_references_round(case, one_round):
    args, ds, api = one_round
    packed = (ds.packed_train.x, ds.packed_train.y, ds.packed_train.mask)
    cohort = case.ref.sample_cohort(0, 4, 2)
    with jax.default_matmul_precision("highest"):
        want, loss = case.ref.fedavg_round(
            case.weights, packed, ds.packed_num_samples, cohort, case.model, {"lr": 0.05, "epochs": 1})
        test_loss = case.ref.evaluate(
            want, (ds.packed_test.x, ds.packed_test.y, ds.packed_test.mask), case.model)
    rec = api.history[-1]
    assert abs(rec["train_loss_cohort"] - loss) <= 1e-5 * loss
    assert abs(rec["test_loss"] - test_loss) <= 1e-5 * test_loss
    moved = [float(jnp.linalg.norm(a - b)) for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(case.weights))]
    gap = [float(jnp.linalg.norm(a - b)) for a, b in zip(
        jax.tree.leaves(api.global_params), jax.tree.leaves(want))]
    # a leaf that no gradient reaches (the selection bias) moved by nothing at all
    assert max(g / m if m else g for g, m in zip(gap, moved)) < 2e-3


def test_the_rounds_record_carries_the_counters(case, one_round):
    """Fetched with the round's other metrics: token-choices on held
    experts, the fullest expert, the mean, nothing dropped -- and the
    lane-steps the engine ran of those it packed."""
    _, ds, api = one_round
    rec = api.history[-1]
    assert set(case.counters) | {"steps_run", "steps_packed"} <= set(rec)
    sparse = len(case.model["layer_types"]) - case.model.get("num_dense_layers", 0)
    # 2 sequences of 32 tokens a step, k choices a token (at most min(k, held) of them
    # here), over the steps run and the sparse layers
    calls, n = rec["steps_run"] * sparse, 2 * T
    k = case.model["num_experts_per_tok"]
    here = min(k, case.model["experts_held"][1])
    assert rec["moe_dropped"] == 0.0
    assert 0 < rec["moe_local_hits"] <= calls * n * here
    assert rec["moe_expert_tokens_mean"] == pytest.approx(rec["moe_local_hits"] / 4)
    assert rec["moe_expert_tokens_mean"] <= rec["moe_expert_tokens_max"] <= calls * n
    assert rec["steps_packed"] == 2 * ds.packed_train.mask.shape[1]
    if "ssm_chunks" in case.counters:
        # every mixer's scan, a step: 2 sequences of T / chunk_size chunks
        assert rec["ssm_chunks"] == case.model["layer_types"].count(SSM) * rec["steps_run"] * 2 * (
            T // case.model["chunk_size"])
    if case.ragged:
        # silos 0 and 1 hold 3 sequences (2 steps), 2 and 3 hold 2 (1 step of their 2)
        cohort = case.ref.sample_cohort(0, 4, 2)
        assert rec["steps_run"] == sum(2 if c < 2 else 1 for c in cohort) < rec["steps_packed"] + (
            1 if all(c < 2 for c in cohort) else 0)
        assert 0 < rec["moe_bias_moved"] < rec["steps_run"] * sparse * n * k
    else:
        assert rec["steps_run"] == rec["steps_packed"] and "moe_bias_moved" not in rec


def test_scopes_name_the_round_executables_parts(case, one_round):
    """Every scope the per-layer readers look for is a component of
    some op_name in the lowered round executable and in the
    evaluation's, inside ``fed.local_train`` where it trains. Lane after
    lane nothing is vmapped, so the expert layer's operations keep
    ``fed.local_train`` around their own scope."""
    args, ds, api = one_round
    packed = ds.packed_train
    idx = jnp.asarray([0, 1], jnp.int32)
    lowered = api._round_fn.lower(
        api.global_params, api.server_state, packed, jnp.asarray(ds.packed_num_samples, jnp.float32),
        idx, jax.random.PRNGKey(0))
    text = lowered.as_text(debug_info=True)
    for scope in case.scopes:
        assert f"fed.local_train/" in text and f"/{scope}/" in text, scope
    if case.ragged:
        import re

        # composed names are the compiled executable's (the lowered text nests its locations)
        names = re.findall(r'op_name="([^"]*moe\.experts[^"]*)"', lowered.compile().as_text())
        inside = [n for n in names if "fed.local_train" in n]
        assert len(inside) > 0.9 * len(names) > 0, (len(inside), len(names))
    ev = api._eval_all.lower(api.global_params, packed).as_text(debug_info=True)
    for scope in case.scopes:
        assert f"/{scope}/" in ev, scope


# -- every operation of the round and the evaluation under a layer scope --
# op names with no layer scope of ``core/scopes.py`` that may stay so: what
# a construct or a transformation emits for itself, and a few scalars
UNSCOPED_OK = tuple(re.compile(p) for p in (
    # a loop's, a call's or remat's own copies and carries: they borrow the
    # construct's name, and a scope round the construct would name its body
    r"/(while|closed_call|cond|remat2)$",
    # a map's or a scan's per-lane slices and stacked outputs, and vmap's
    # broadcast of the weights to the lanes: the batching's, at the call
    r"/while/body/dynamic_(update_)?slice$",
    r"fed\.local_train/(vmap\(\)/)?broadcast_in_dim$",
    r"/closed_call/transpose$",
    # sums the chip fuses into a neighbour: a block's residual after its
    # feed-forward, the expert layer input's cotangents from its three users
    r"/layer_\d+/add$",
    r"/moe/add_any$",
    r"MoEDecoderLM\)?/(cos|sin|concatenate|mul)$",  # the rotary tables, from positions
    # the loss's mask broadcast and its metric's product, outside
    # ``lm.head_loss`` as before the registry (head_loss_device_ms's source)
    r"fwd_bwd/jvp\(\)/(broadcast_in_dim|mul)$",
))
_NOT_RUN = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
# an instruction of at most this many elements is bookkeeping (a loop's
# bounds, the lanes' last real steps, a step's metric sums, the counters)
_FEW = 16


def _op_names(hlo_text):
    """The op name of every instruction a compiled module runs or fuses:
    reducers' bodies (``to_apply``, names relative to nothing), what runs
    nothing (parameters, tuples, bitcasts) and bookkeeping of a few
    elements left out."""
    reducers = set(re.findall(r"to_apply=%?([\w.\-]+)", hlo_text))
    names, comp = [], None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        kind = re.search(r"= (?:\(.*?\)|\S+) ([\w\-]+)\(", line)
        if not (op and kind) or comp in reducers or kind.group(1) in _NOT_RUN:
            continue
        dims = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]", line)
        if dims and math.prod(int(d) for d in dims.group(1).split(",") if d) <= _FEW:
            continue
        names.append(op.group(1))
    return names


@pytest.fixture(scope="module")
def compiled(case, one_round):
    """The case's round and evaluation executables compiled (CPU): their op names."""
    args, ds, api = one_round
    packed = ds.packed_train
    round_text = api._round_fn.lower(
        api.global_params, api.server_state, packed, jnp.asarray(ds.packed_num_samples, jnp.float32),
        jnp.asarray([0, 1], jnp.int32), jax.random.PRNGKey(0)).compile().as_text()
    eval_text = api._eval_all.lower(api.global_params, packed).compile().as_text()
    return {"round_fn": _op_names(round_text), "eval_all": _op_names(eval_text)}


@pytest.mark.parametrize("executable", ["round_fn", "eval_all"])
def test_every_operation_sits_under_a_layer_scope(case, compiled, executable):
    """What the benchmark's coverage reader counts as unnamed is, on the
    program's side, only the allowlist: every other op name of the round
    (the vmapped cohort; lane after lane, with a ``mamba`` sublayer) and
    of the evaluation carries a layer scope of ``core/scopes.py``."""
    from fedml_tpu.core import scopes

    names = compiled[executable]
    assert len(names) > 200
    stray = sorted({
        n for n in names
        if not set(re.findall(r"[^/():]+", n)) & set(scopes.LAYERS)
        and not any(p.search(n) for p in UNSCOPED_OK)})
    assert not stray, "\n".join(stray)


def test_the_expert_loop_keeps_the_scopes_around_it(case, compiled):
    """PERF.md's hole 1, closed: the grouped product's lane loop
    (``sequential_vmap``; batched over a 2-lane cohort in the
    ``window_softmax`` case, and in every evaluation, which vmaps its
    lanes) carries ``fed.local_train`` / ``fed.eval`` around its own
    scopes, so a container's time holds the expert layer."""
    for executable, container in (("round_fn", "fed.local_train/"), ("eval_all", "fed.eval/")):
        moe = [n for n in compiled[executable] if "/moe." in n]
        assert moe and all(container in n for n in moe), executable
        assert any("/moe.loop/" in n and "/moe.experts/" in n for n in moe), executable
    grads = [n for n in compiled["round_fn"] if "/moe.grad_sum/" in n]
    assert grads and all("fed.local_train/" in n for n in grads)


# -- the sublayer form: shared expert, scaling factor, three rounds ------
def _relu2_layer(first, held, experts=16, **kw):
    return HeldExperts(
        num_experts=experts, experts_per_token=3, expert_dim=32, experts_held=(first, held),
        scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-20, activation="relu2",
        routed_scaling_factor=2.5, **kw)


@pytest.mark.parametrize("ep", [1, 4, 16])
def test_the_shares_of_a_relu2_layer_and_the_shared_expert_once_add_up(ep):
    """The held parts of all ``ep`` shares, with what every chip
    computes alike -- the shared expert -- counted once, are the uncut
    reference's expert layer."""
    ks = jax.random.split(jax.random.PRNGKey(13), 6)
    bias = 0.05 * jax.random.normal(ks[0], (16,))
    p = {
        "router": {"kernel": jax.random.normal(ks[1], (64, 16)) / 8}, "expert_bias": bias - bias.mean(),
        "up_proj": jax.random.normal(ks[2], (16, 64, 32)) / 8,
        "down_proj": jax.random.normal(ks[3], (16, 32, 64)) / 6,
        "shared": {"up_proj": {"kernel": jax.random.normal(ks[4], (64, 48)) / 8},
                   "down_proj": {"kernel": jax.random.normal(ks[5], (48, 64)) / 7}},
    }
    whole = dict(MODEL_SSM, n_routed_experts=16, num_experts_per_tok=3, experts_held=[0, 16])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))

    def share(first, held, shared):
        cut = {k: (v[first:first + held] if k in ("up_proj", "down_proj") else v)
               for k, v in p.items() if shared or k != "shared"}
        layer = _relu2_layer(first, held, shared_dim=48 if shared else 0)
        return layer.apply({"params": cut}, x)

    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref_ssm._experts(s, p, whole, None, None) for s in x])
        # rank 0 brings the shared expert, the other shares their held experts' part alone
        got = sum(share(*experts_held(16, ep, r), shared=(r == 0)) for r in range(ep))
        # and one share with the shared expert is the reference's share
        one = ref_ssm._experts(x[0], {**p, "up_proj": p["up_proj"][4:8], "down_proj": p["down_proj"][4:8]},
                               dict(whole, experts_held=[4, 4]), None, None)
    assert _close(got, want, 2e-5)
    assert _close(share(4, 4, shared=True)[0], one, 2e-5)


@pytest.mark.parametrize("key,value,over", [
    ("routed_scaling_factor", 1.0, {}), ("norm_topk_eps", 0.5, {}),
    ("routed_scaling_factor", 2.5, {"routed_scaling_factor": 1.0}),
    ("norm_topk_eps", 1e-20, {"norm_topk_eps": 0.5})])
def test_the_reference_reads_the_scaling_factor_and_the_renormalisers_epsilon(tokens, key, value, over):
    """The reference takes both from the configuration: told another
    value it disagrees with the program, and a program told another
    (one that ignored the configuration's) disagrees with the
    reference."""
    c = CASES["ssm_relu2"]
    with jax.default_matmul_precision("highest"):
        got = models.create(c.args(**over), 64).apply(c.weights, tokens[:1, :-1])[0]
        want = c.ref.forward(c.weights, tokens[0, :-1], dict(c.model, **{key: value}))
    assert not _close(got, want, 1e-3)


def test_attention_without_rotation_or_qk_norm_is_a_matter_of_the_arguments(tokens):
    """``rope_parameters`` {} and ``qk_norm`` false: no leaf, no
    rotation; an entry for the layer's type brings the rotation back."""
    c = CASES["ssm_relu2"]
    plain = models.create(c.args(), 64)
    assert set(plain.init(jax.random.PRNGKey(0))["layer_5"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    rotated = models.create(c.args(rope_parameters={FULL: {"rope_type": "default", "rope_theta": 1e4}}), 64)
    normed = models.create(c.args(qk_norm=True), 64)
    assert set(normed.init(jax.random.PRNGKey(0))["layer_5"]["attn"]) >= {"q_norm", "k_norm"}
    with jax.default_matmul_precision("highest"):
        a, b = plain.apply(c.weights, tokens[:, :-1]), rotated.apply(c.weights, tokens[:, :-1])
    assert not _close(a, b, 1e-3)
    with pytest.raises(ValueError, match="layer type"):  # a sublayer kind outside the sublayer form
        models.create(_args(layer_types=[FULL, SSM]), 64).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="activation"):
        models.create(c.args(expert_activation="gelu"), 64).init(jax.random.PRNGKey(0))


def test_three_rounds_lane_after_lane_match_the_reference():
    """The check's drive at a tiny size: three FedAvg rounds (cohorts of
    rounds 0, 1, 2) through the lane-after-lane engine against the
    reference's, losses and final weights; ``ssm_chunks`` reaches every
    round's record."""
    from fedml_tpu.simulation import fedavg_api

    c = CASES["ssm_relu2"]
    args = _fed_args(c.args, synthetic_train_size=10, comm_round=3)
    ds = data.load(args)
    heavy, fedavg_api._HEAVY_LANE_STEP = fedavg_api._HEAVY_LANE_STEP, 0
    try:
        api = fedavg_api.FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    finally:
        fedavg_api._HEAVY_LANE_STEP = heavy
    assert api._round_exec_name() == "simulation.round_fn_ragged"
    api.global_params = jax.tree.map(jnp.copy, c.weights)
    api.train()
    packed = (ds.packed_train.x, ds.packed_train.y, ds.packed_train.mask)
    want, losses = c.weights, []
    with jax.default_matmul_precision("highest"):
        for r in range(3):
            want, loss = c.ref.fedavg_round(
                want, packed, ds.packed_num_samples, c.ref.sample_cohort(r, 4, 2), c.model,
                {"lr": 0.05, "epochs": 1})
            losses.append(loss)
    assert len(api.history) == 3
    for rec, loss in zip(api.history, losses):
        assert abs(rec["train_loss_cohort"] - loss) <= 2e-5 * loss
        assert rec["ssm_chunks"] == 3 * rec["steps_run"] * 2 * (T // 8) and rec["moe_dropped"] == 0.0
    moved = [float(jnp.linalg.norm(a - b)) for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(c.weights))]
    gap = [float(jnp.linalg.norm(a - b)) for a, b in zip(
        jax.tree.leaves(api.global_params), jax.tree.leaves(want))]
    assert max(g / m if m else g for g, m in zip(gap, moved)) < 5e-3
