"""``ops/grouped_matmul.py``: the experts' grouped product as the shipped
Pallas grouped matmuls, run by the Pallas interpreter on the CPU,
against ``jax.lax.ragged_dot`` and its autodiff (the form they replace,
and the path every other platform and shape keeps): the forward, ``dx``
and every stack's gradient; the expert layer with the kernel path
forced, lane after lane and in more than one chunk; the counter that says
how many rows the kernels ran on; and what one expert layer's lowering
for the TPU holds. What the kernels *cost* is the chip's to say.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.decoder import HeldExperts, held_experts
from fedml_tpu.ops import grouped_matmul as gm


def _kernel_path(kernel, reference, *operands):
    """``_on_platform`` as on the TPU, the kernels in the interpreter."""
    return kernel(*operands, interpret=True)


@pytest.fixture
def forced(monkeypatch):
    """The kernel path on the CPU: the dispatch itself patched, jitted
    entries traced anew."""
    monkeypatch.setattr(gm, "_on_platform", _kernel_path)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def _operands(m, k, n, sizes, dtype, gated=True, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    g = len(sizes)
    xs = jax.random.normal(ks[0], (m, k)).astype(dtype)
    shapes = [(g, k, n)] * (2 if gated else 1) + [(g, n, k)]
    stacks = [(jax.random.normal(key, s) / 8).astype(dtype) for key, s in zip(ks[1:4], shapes)]
    dout = jax.random.normal(ks[4], (m, k)).astype(dtype)
    return xs, jnp.asarray(sizes, jnp.int32), dout, stacks


# (m, k, n, sizes, gated): rows in 128-row tiles, groups that start and end inside tiles
CASES = {
    "empty_groups": (512, 256, 384, (100, 0, 250, 0, 30), True),
    "slack_past_the_sum": (640, 256, 256, (70, 130, 33, 200), True),  # 207 rows belong to no group
    "one_group_every_row": (384, 256, 256, (0, 384, 0), True),
    "irregular_widths": (256, 200, 136, (90, 3, 120, 43), True),  # neither k nor n a multiple of 128
    "relu2": (512, 256, 136, (128, 128, 0, 200), False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_forward_dx_and_dw_against_ragged_dot(case):
    """Float32 operands under ``highest``: the kernels and the reference
    agree to rounding on every row that belongs to a group and on every
    stack's gradient (an empty group's is 0)."""
    m, k, n, sizes, gated = CASES[case]
    xs, sizes, dout, stacks = _operands(m, k, n, sizes, jnp.float32, gated)
    valid = np.arange(m) < int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        fwd = gm._kernel_forward(xs, sizes, *stacks, interpret=True)
        want_fwd = gm._reference_forward(xs, sizes, *stacks)
        out, dxs, dstacks = gm._kernel_backward(xs, sizes, dout, *stacks, interpret=True)
        want_out, want_dxs, want_dstacks = gm._reference_backward(xs, sizes, dout, *stacks)
    assert fwd.shape == (m, k) and dxs.dtype == jnp.float32
    assert _gap(fwd[valid], want_fwd[valid]) < 1e-5
    assert _gap(out[valid], want_out[valid]) < 1e-5
    assert _gap(dxs[valid], want_dxs[valid]) < 1e-5
    for got, want in zip(dstacks, want_dstacks):
        assert got.dtype == jnp.float32 and got.shape == want.shape
        assert _gap(got, want) < 1e-5
        empty = np.asarray(sizes) == 0
        assert not np.any(np.asarray(got)[empty])


def test_bfloat16_operands_float32_accumulation():
    """bf16 rows and stacks: the product of each pair is exact in float32
    and the sums over 2,048 inputs are float32's, so a group's rows
    match a float64 product of the same bf16 numbers to float32 rounding
    -- a bf16 sum would be ~100x further off."""
    m, k, n = 256, 2048, 256
    sizes = jnp.asarray([100, 0, 156], jnp.int32)
    xs, _, _, stacks = _operands(m, k, n, (100, 0, 156), jnp.bfloat16)
    got = gm._gmm(xs, stacks[0], sizes, jnp.float32, interpret=True)
    x64, w64 = np.asarray(xs, np.float64), np.asarray(stacks[0], np.float64)
    want = np.concatenate([x64[:100] @ w64[0], x64[100:] @ w64[2]])
    assert got.dtype == jnp.float32
    assert _gap(got, want) < 1e-6
    # the stacks' gradient too: float32 sums over a group's rows
    d = jax.random.normal(jax.random.PRNGKey(3), (m, n)).astype(jnp.bfloat16)
    dw = gm._tgmm(xs, d, sizes, interpret=True)
    d64 = np.asarray(d, np.float64)
    assert dw.dtype == jnp.float32
    assert _gap(dw[0], x64[:100].T @ d64[:100]) < 1e-6 and _gap(dw[2], x64[100:].T @ d64[100:]) < 1e-6
    assert not np.any(np.asarray(dw[1]))


def test_bfloat16_backward_stays_near_the_reference():
    """The kernels round a float32 cotangent to bf16 before a product
    (the chip's default precision does so inside ``ragged_dot``); on the
    CPU the reference keeps it in float32: the two stay within bf16's
    rounding."""
    m, k, n, sizes, gated = CASES["empty_groups"]
    xs, sizes, dout, stacks = _operands(m, k, n, sizes, jnp.bfloat16, gated)
    valid = np.arange(m) < int(sizes.sum())
    out, dxs, dstacks = gm._kernel_backward(xs, sizes, dout, *stacks, interpret=True)
    want_out, want_dxs, want_dstacks = gm._reference_backward(xs, sizes, dout, *stacks)
    assert out.dtype == jnp.bfloat16 and dxs.dtype == jnp.float32
    assert _gap(out[valid], want_out[valid]) < 1e-2
    assert _gap(dxs[valid], want_dxs[valid]) < 2e-2
    assert all(a.dtype == jnp.float32 and _gap(a, b) < 2e-2 for a, b in zip(dstacks, want_dstacks))


@pytest.mark.parametrize("m,k,n,takes", [
    (20480, 2304, 896, True), (10240, 2048, 1792, True), (3840, 2688, 1856, True),
    (40, 64, 32, False),  # a test-sized model's chunk
    (200, 256, 256, False),  # rows that are not whole tiles
    (256, 64, 256, False),  # inputs narrower than a lane tile
])
def test_the_shape_rule(m, k, n, takes):
    """By shape alone: no knob, no environment; every tiling the rule
    gives divides the rows and fits the scoped VMEM budget."""
    assert gm.takes(m, k, n) == takes
    if takes:
        for form, kk, nn in (("gmm", k, n), ("gmm", n, k), ("tgmm", k, n), ("tgmm", n, k)):
            tm, tk, tn = gm.tiling(form, m, kk, nn)
            assert m % tm == 0 and tk <= kk and tn <= nn
            assert tk == kk or tk % 128 == 0
            assert tn == nn or tn % 128 == 0


# -- the expert layer, kernel path forced ------------------------------------

def _held(m_tokens=256, c=128, i=256, held=4, gated=True, seed=0):
    """``held_experts``' operands: a sorted list of 600 choices over 4
    held experts (one empty), chunks of 256 rows (three chunks, the last
    part empty)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (m_tokens, c))
    sizes = jnp.asarray([250, 0, 200, 150], jnp.int32)
    tok = jax.random.randint(ks[1], (600,), 0, m_tokens).astype(jnp.int32)
    weight = jax.random.uniform(ks[2], (600,))
    shapes = [(held, c, i)] * (2 if gated else 1) + [(held, i, c)]
    stacks = [jax.random.normal(k, s) / 10 for k, s in zip(ks[3:], shapes)]
    return x, tok, weight, sizes, stacks


def _layer_loss(rows):
    def loss(x, weight, stacks, tok, sizes):
        y, done = held_experts(rows, x, tok, weight, sizes, *stacks)
        return jnp.sum(jnp.sin(y)), done
    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)


@pytest.mark.parametrize("gated", [True, False])
def test_held_experts_on_the_kernels_is_the_ragged_dot_path(forced, monkeypatch, gated):
    """Two lanes vmapped (``sequential_vmap`` runs them one after
    another), three chunks a lane: the output, every cotangent and the
    rows handed over are the ``ragged_dot`` path's."""
    lanes = [_held(gated=gated, seed=s) for s in (0, 1)]
    stack = lambda *a: jax.tree.map(lambda *v: jnp.stack(v), *a)
    x, tok, weight, sizes, stacks = stack(*lanes)
    fn = jax.vmap(_layer_loss(256))
    with jax.default_matmul_precision("highest"):
        (_, done), grads = fn(x, weight, stacks, tok, sizes)
        monkeypatch.setattr(gm, "_on_platform", lambda kernel, reference, *ops: reference(*ops))
        jax.clear_caches()
        (_, want_done), want = fn(x, weight, stacks, tok, sizes)
    np.testing.assert_array_equal(done, want_done)
    assert float(done[0]) == 600.0
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert _gap(got, ref) < 1e-5


def _layer(**kw):
    """A kernel-sized expert layer: 8 experts, 4 held, top 2 of 512
    tokens; an even load's rows and a quarter is 640 = a chunk, the list
    room 1,024."""
    return HeldExperts(num_experts=8, experts_per_token=2, expert_dim=256, experts_held=(4, 4), **kw)


@pytest.mark.parametrize("activation", ["gated_silu", "relu2"])
def test_the_layer_counts_the_rows_the_kernels_ran(forced, monkeypatch, activation):
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 512, 128))
    layer = _layer(activation=activation)
    params = {"params": layer.init(jax.random.PRNGKey(0), x)["params"]}
    assert gm.takes(640, 128, 256)
    with jax.default_matmul_precision("highest"):
        y, state = layer.apply(params, x, mutable=["counters"])
        c = {k: float(v) for k, v in state["counters"].items()}
        monkeypatch.setattr(gm, "_on_platform", lambda kernel, reference, *ops: reference(*ops))
        jax.clear_caches()
        want, ref_state = layer.apply(params, x, mutable=["counters"])
    assert c["moe_dropped"] == 0.0 and c["moe_kernel_rows"] == c["moe_local_hits"] > 0
    assert float(ref_state["counters"]["moe_kernel_rows"]) == 0.0
    assert _gap(y, want) < 1e-5


@pytest.mark.parametrize("loads, rows", [(None, 640), (0.5, 256), (2.0, 1024), (4.0, 1024)])
def test_chunk_loads_sets_the_chunk_and_leaves_the_layer_as_it_was(monkeypatch, loads, rows):
    """``chunk_loads`` even loads' rows a chunk (512 tokens, top 2, 4 of
    8 held: an even load is 512 rows, the list's room 1,024), an even
    load and a quarter by default; the layer's output and gradients are
    the default's whatever the chunk, and nothing is dropped."""
    from fedml_tpu.models import decoder

    x = jax.random.normal(jax.random.PRNGKey(4), (1, 512, 128))
    base = _layer()
    layer = base if loads is None else _layer(chunk_loads=loads)
    params = {"params": base.init(jax.random.PRNGKey(0), x)["params"]}
    seen = []
    monkeypatch.setattr(decoder, "held_experts", lambda r, *a: (seen.append(r), held_experts(r, *a))[1])
    run = lambda m, p: jax.value_and_grad(lambda p: jnp.sum(m.apply(p, x, mutable=["counters"])[0] ** 2))(p)
    with jax.default_matmul_precision("highest"):
        (y, grads), (want, want_grads) = run(layer, params), run(base, params)
        _, state = layer.apply(params, x, mutable=["counters"])
    assert seen[0] == rows == decoder.expert_chunk_rows(512, 2, 4, 8, 1.25 if loads is None else loads)
    assert float(state["counters"]["moe_dropped"]) == 0.0
    assert _gap(y, want) < 1e-6
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _gap(got, ref) < 1e-5


def test_a_test_sized_layer_stays_on_ragged_dot(forced):
    """Rows that are not whole 128-row tiles never reach the kernels,
    wherever they run: the counter reads 0."""
    layer = HeldExperts(num_experts=8, experts_per_token=2, expert_dim=32, experts_held=(4, 4))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    params = {"params": layer.init(jax.random.PRNGKey(0), x)["params"]}
    _, state = layer.apply(params, x, mutable=["counters"])
    assert float(state["counters"]["moe_kernel_rows"]) == 0.0 < float(state["counters"]["moe_local_hits"])


# -- the counter, through one lane-after-lane round ---------------------------

def _round_record(**over):
    """One round of ``test_moe_decoder.py``'s ``conv_sigmoid`` case
    through the lane-after-lane engine; its record."""
    from fedml_tpu import data, models
    from fedml_tpu.simulation import fedavg_api
    from test_moe_decoder import _args_conv, _fed_args  # tests/ is on sys.path under pytest

    args = _fed_args(lambda **kw: _args_conv(**{**kw, **over}), synthetic_train_size=10, frequency_of_the_test=5)
    ds = data.load(args)
    heavy, fedavg_api._HEAVY_LANE_STEP = fedavg_api._HEAVY_LANE_STEP, 0
    try:
        api = fedavg_api.FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    finally:
        fedavg_api._HEAVY_LANE_STEP = heavy
    assert api._round_exec_name() == "simulation.round_fn_ragged"
    api.train()
    return api.history[-1]


def test_moe_kernel_rows_through_a_lane_after_lane_round(forced):
    """Kernel-sized experts (hidden 128, width 128; 2 sequences of 256
    tokens a step, 2 of 8 experts a token, 4 held: chunks of 640 rows):
    every row the expert layers took, in every step of both lanes, ran
    on the kernels -- summed into the round's record with the other
    counters, and nothing dropped."""
    rec = _round_record(seq_len=256, hidden_size=128, expert_dim=128, intermediate_size=128)
    assert rec["moe_dropped"] == 0.0
    assert rec["moe_kernel_rows"] == rec["moe_local_hits"] > 0


def test_moe_kernel_rows_off_the_kernel_path():
    rec = _round_record()
    assert rec["moe_local_hits"] > 0 and rec["moe_kernel_rows"] == 0.0


# -- what one expert layer's lowering for the TPU holds -----------------------

# the TwoTower cell's expert layer (benchmark/configs/nemotron_twotower_30b_a3b_fedavg_ep16.json)
TWOTOWER = dict(num_experts=128, experts_per_token=6, expert_dim=1856, experts_held=(0, 8),
                scoring="sigmoid", use_expert_bias=True, norm_topk_eps=1e-20, activation="relu2",
                routed_scaling_factor=2.5)
HIDDEN, SEQ = 2688, 8192
# the kernels' distinct bodies: forward, the up and down products; behind
# the gradient the forward again, two transposed products and two stack
# gradients -- one call site each where the shapes and tilings agree
MAX_CUSTOM_CALLS, MAX_TEXT_BYTES = 10, 400_000
HOST_CALLBACKS = re.compile(r"callback|xla_python|xla_ffi_python|host_compute|SendToHost|RecvFromHost|outfeed|infeed", re.I)


def test_an_expert_layers_lowering_for_the_tpu_stays_small_and_cacheable():
    """One ``HeldExperts`` at the TwoTower cell's widths, forward and
    gradient in one module, lowered for the TPU from shapes alone
    (nothing runs): the grouped product is Mosaic calls inside
    ``moe.experts``, no ``ragged_dot`` is left, and nothing calls back to
    the host."""
    layer = HeldExperts(**TWOTOWER)
    x = jax.ShapeDtypeStruct((1, SEQ, HIDDEN), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"]

    def both(params, x):
        forward = lambda p, v: layer.apply({"params": p}, v, mutable=["counters"])[0]
        loss = lambda p, v: jnp.sum(forward(p, v).astype(jnp.float32))
        return forward(params, x), jax.grad(loss, argnums=(0, 1))(params, x)

    lowered = jax.jit(both).trace(params, x).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    calls = text.count("tpu_custom_call")
    print("expert layer lowering:", calls, "tpu_custom_call sites,", len(text), "bytes")
    assert 0 < calls <= MAX_CUSTOM_CALLS, calls
    assert len(text) <= MAX_TEXT_BYTES, len(text)
    assert "ragged_dot" not in text
    debug = lowered.as_text(debug_info=True)
    assert "moe.experts" in debug  # the scope the trace's readers find the kernels under
    found = HOST_CALLBACKS.findall(text)
    assert not found, found


def test_a_test_sized_layer_lowers_to_ragged_dot_on_the_tpu():
    layer = HeldExperts(num_experts=8, experts_per_token=2, expert_dim=32, experts_held=(4, 4))
    x = jnp.zeros((2, 32, 64))
    params = layer.init(jax.random.PRNGKey(0), x)
    text = jax.jit(lambda p, v: layer.apply(p, v, mutable=["counters"])[0]).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text and "ragged_dot" in text
