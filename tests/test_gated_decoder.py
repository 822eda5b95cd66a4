"""The fourth configuration of ``models/decoder.py``'s block through the
cases of ``test_moe_decoder.py`` (imported from there: the same
assertions, this configuration's ``case``), against its plain reference
``benchmark/reference/fedavg_trinity.py`` -- window and full attention
(3 : 1, the full layer unrotated) with a gated output, sandwich norms, a
dense leading layer, an embedding multiplier, a gated-SiLU shared expert
beside top 8 of 32 sigmoid-routed experts with a selection bias and a
route scale -- and the tests of what it added: the shares of its expert
layer, each new argument against the reference's fault of its name, and
the arguments off in the three configurations before it.

Small sizes on the CPU (hidden 64, 4 query / 2 KV heads of 16, window
8, T 32, vocabulary 64, layers S S F S S); a file of its own, so that it
runs beside ``test_moe_decoder.py`` and not after it.
"""

import argparse
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import models
from fedml_tpu.models.decoder import FULL, SLIDING, HeldExperts
from fedml_tpu.parallel.expert import experts_held
from test_moe_decoder import (  # noqa: F401  (fixtures and the shared cases)
    CASES, LM_SCOPES, MOE_COUNTERS, T, Case, _close, _load, compiled, one_round, tokens,
    test_every_operation_sits_under_a_layer_scope,
    test_flash_path_matches_dense_path, test_forward_matches_reference,
    test_gradients_match_reference, test_one_round_matches_the_references_round,
    test_parameter_tree_is_the_references, test_scopes_name_the_round_executables_parts,
    test_the_expert_loop_keeps_the_scopes_around_it, test_the_rounds_record_carries_the_counters,
)

ref_gated = _load("benchmark/reference/fedavg_trinity.py", "ref_fedavg_trinity")

# the fourth configuration: window and full attention with an output
# gate and sandwich norms, the full layer unrotated, a dense leading
# layer, an embedding multiplier, top 8 of 32 sigmoid-routed experts with
# a selection bias and a route scale beside a gated-SiLU shared expert
MODEL_GATED = {
    "vocab_size": 64, "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": [SLIDING, SLIDING, FULL, SLIDING, SLIDING],
    "num_dense_layers": 1, "intermediate_size": 96, "sliding_window": 8, "rope_theta": 1e4,
    "num_experts": 32, "num_experts_per_tok": 8, "moe_intermediate_size": 32, "num_shared_experts": 1,
    "route_norm": True, "route_scale": 2.826, "norm_topk_eps": 1e-20, "rms_norm_eps": 1e-5,
    "embedding_multiplier": 8.0, "experts_held": [4, 4],
}


def _args_gated(**over):
    flat = dict(
        model="moe_decoder", dataset="token_stream", vocab_size=64, seq_len=T, hidden_size=64,
        num_heads=4, num_kv_heads=2, head_dim=16, layer_types=list(MODEL_GATED["layer_types"]),
        sliding_window=8, rope_parameters={SLIDING: {"rope_type": "default", "rope_theta": 1e4}},
        output_gate=True, post_norms=True, embedding_multiplier=8.0, num_dense_layers=1,
        intermediate_size=96, num_experts=32, experts_per_token=8, expert_dim=32,
        router_scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-20, shared_expert_dim=32,
        routed_scaling_factor=2.826, rms_norm_eps=1e-5, expert_parallel=8, expert_rank=1,
        attention_impl="full",
    )
    flat.update(over)
    return argparse.Namespace(**flat)


GATED = Case(
    ref_gated, MODEL_GATED, _args_gated, tuple(f for f in ref_gated.FAULTS if f),
    LM_SCOPES + ("blk.attn.window", "blk.attn.full", "blk.attn.gate", "blk.post_norm",
                 "blk.mlp.dense", "moe.shared"),
    MOE_COUNTERS + ("moe_bias_moved",), ragged=True)


@pytest.fixture(scope="module")
def case():
    """The imported cases' configuration, here this file's alone."""
    return GATED


@pytest.mark.parametrize("fault", GATED.faults)
def test_each_planted_fault_moves_the_gated_reference(tokens, fault):
    """What the benchmark's limits have to catch is not a no-op at this
    size: the reference with a fault planted disagrees with itself."""
    with jax.default_matmul_precision("highest"):
        good = GATED.ref.forward(GATED.weights, tokens[0, :-1], GATED.model)
        bad = GATED.ref.forward(GATED.weights, tokens[0, :-1], GATED.model, fault=fault)
    assert not _close(bad, good, 1e-3)


# -- the shares, each field against its fault, defaults off ---------
@pytest.mark.parametrize("ep", [1, 4, 16])
def test_the_shares_of_a_gated_silu_layer_and_its_shared_expert_once_add_up(ep):
    """The fourth configuration's expert layer uncut (32 experts, top 8):
    the held parts of all ``ep`` shares -- 16 shares of 2 experts at the
    most -- with the shared expert counted once are the reference's
    whole layer, and one share with the shared expert is the reference's
    share."""
    ks = jax.random.split(jax.random.PRNGKey(17), 8)
    bias = 0.05 * jax.random.normal(ks[0], (32,))
    mlp = lambda k, c, w: {
        "gate_proj": {"kernel": jax.random.normal(k[0], (c, w)) / 8},
        "up_proj": {"kernel": jax.random.normal(k[1], (c, w)) / 8},
        "down_proj": {"kernel": jax.random.normal(k[2], (w, c)) / 6}}
    p = {
        "router": {"kernel": jax.random.normal(ks[1], (64, 32)) / 8}, "expert_bias": bias - bias.mean(),
        "gate_proj": jax.random.normal(ks[2], (32, 64, 32)) / 8,
        "up_proj": jax.random.normal(ks[3], (32, 64, 32)) / 8,
        "down_proj": jax.random.normal(ks[4], (32, 32, 64)) / 6,
        "shared": mlp(ks[5:8], 64, 32),
    }
    stacks = ("gate_proj", "up_proj", "down_proj")
    whole = dict(MODEL_GATED, experts_held=[0, 32])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))

    def share(first, held, shared):
        cut = {k: (v[first:first + held] if k in stacks else v)
               for k, v in p.items() if shared or k != "shared"}
        layer = HeldExperts(
            num_experts=32, experts_per_token=8, expert_dim=32, experts_held=(first, held),
            scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-20,
            routed_scaling_factor=2.826, shared_dim=32 if shared else 0)
        return layer.apply({"params": cut}, x)

    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref_gated._experts(s, p, whole, None, None) for s in x])
        got = sum(share(*experts_held(32, ep, r), shared=(r == 0)) for r in range(ep))
        one = ref_gated._experts(x[0], {**p, **{k: p[k][4:8] for k in stacks}},
                                 dict(whole, experts_held=[4, 4]), None, None)
    assert _close(got, want, 2e-5)
    assert _close(share(4, 4, shared=True)[0], one, 2e-5)


def _without(tree, *names):
    """``tree`` with every leaf or subtree under one of ``names`` left out."""
    if not isinstance(tree, dict):
        return tree
    return {k: _without(v, *names) for k, v in tree.items() if k not in names}


# a field of the program turned off, and the leaves it takes with it, against
# the reference's fault of the same name
FIELD_OFF = {
    "no_gate": ({"output_gate": False}, ("gate_proj",)),
    "no_post_norms": ({"post_norms": False}, ("attn_post_norm", "ffn_post_norm")),
    "no_mup": ({"embedding_multiplier": 1.0}, ()),
    "rotate_full": ({"rope_parameters": {k: {"rope_type": "default", "rope_theta": 1e4} for k in (SLIDING, FULL)}}, ()),
    "no_window": ({"sliding_window": T}, ()),
    "no_bias": ({"use_expert_bias": False}, ("expert_bias",)),
    "no_route_scale": ({"routed_scaling_factor": 1.0}, ()),
    "no_shared": ({"shared_expert_dim": 0}, ("shared",)),
}


@pytest.mark.parametrize("fault", list(FIELD_OFF))
def test_each_field_off_is_the_references_fault(tokens, fault):
    """What each argument adds is the reference's: the program with the
    argument off (and the leaves it brings left out) computes the
    reference with that fault planted, and the whole program does not."""
    c = GATED
    over, leaves = FIELD_OFF[fault]
    weights = dict(c.weights)
    for i in range(len(MODEL_GATED["layer_types"])):
        layer = dict(weights[f"layer_{i}"])
        if "gate_proj" in leaves:
            layer["attn"] = _without(layer["attn"], "gate_proj")
        weights[f"layer_{i}"] = _without(layer, *(n for n in leaves if n != "gate_proj"))
    with jax.default_matmul_precision("highest"):
        got = models.create(c.args(**over), 64).apply(weights, tokens[:1, :-1])[0]
        whole = models.create(c.args(), 64).apply(c.weights, tokens[:1, :-1])[0]
        want = c.ref.forward(c.weights, tokens[0, :-1], c.model, fault=fault)
    assert _close(got, want, 2e-5)
    assert not _close(whole, want, 1e-3)


@pytest.mark.parametrize("name", ["window_softmax", "conv_sigmoid", "ssm_relu2"])
def test_the_three_fields_off_add_no_leaf_and_no_operation(name, tokens):
    """Without the three arguments a configuration traces what it traced
    before them: no gate or post-norm leaf, no gate, post-norm or
    multiplier operation, and the same program text and outputs as with
    the three named off."""
    c = CASES[name]
    plain = models.create(c.args(), 64)
    off = models.create(c.args(output_gate=False, post_norms=False, embedding_multiplier=1.0), 64)
    paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(
        plain.init(jax.random.PRNGKey(0)))]
    assert not [p for p in paths if "post_norm" in p or ("'attn'" in p and "gate_proj" in p)]
    x = tokens[:, :-1]
    lower = lambda m: jax.jit(m.apply).lower(c.weights, x)
    text = lower(plain).as_text(debug_info=True)
    assert "blk.attn.gate" not in text and "blk.post_norm" not in text
    assert not re.findall(r'"[^"]*lm\.embed/(mul|convert_element_type)"', text)
    assert lower(plain).as_text() == lower(off).as_text()
    a, b = plain.apply(c.weights, x), off.apply(c.weights, x)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("loads", [1.25, 2.5])
def test_the_chunk_in_even_loads_reaches_the_model(loads, tokens):
    """``expert_chunk_loads`` goes the way of the other fields: the module
    carries it, and named at its default (an even load and a quarter) the
    program text is what it was without it."""
    c = CASES["window_softmax"]
    named = models.create(c.args(expert_chunk_loads=loads), 64)
    assert named.module.expert_chunk_loads == loads
    if loads == 1.25:
        lower = lambda m: jax.jit(m.apply).lower(c.weights, tokens[:, :-1]).as_text()
        assert lower(models.create(c.args(), 64)) == lower(named)


@pytest.mark.parametrize("field", ["output_gate", "post_norms"])
def test_the_sublayer_form_refuses_the_gate_and_the_sandwich_norms(field):
    """One sublayer a layer has no place for either: refused, never
    quietly dropped."""
    with pytest.raises(ValueError, match="sublayer form"):
        models.create(CASES["ssm_relu2"].args(**{field: True}), 64).init(jax.random.PRNGKey(0))


def test_a_convolution_layer_refuses_the_sandwich_norms():
    """No configuration norms a convolution's output: refused, not
    given a norm that no reference checks."""
    with pytest.raises(ValueError, match="convolution layer"):
        models.create(CASES["conv_sigmoid"].args(post_norms=True), 64).init(jax.random.PRNGKey(0))
