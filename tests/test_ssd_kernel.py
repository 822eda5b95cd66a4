"""``ops/ssd_kernel.py``: the chunked scan's Pallas kernels, run by the
Pallas interpreter on the CPU, against ``ssd_scan_jnp`` (the ``jnp``
form they replace at kernel-sized shapes), the step-by-step recurrence
and the plain reference's dense dual form (``test_ssd_scan.py``'s
helpers), values and every cotangent; the dispatch by shape; the
counter that says how often the kernels engage; and what a mixer's
lowering for the TPU holds (PR 35 was refused for 34 s more set-up:
a kernel body that unrolls heads or chunks, or a host callback that
keeps an executable out of the persistent cache, fails here without a
clock). What the kernels *cost* is the chip's to say.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import ssd_kernel
from fedml_tpu.ops.ssd import kernel_chunks, num_chunks, ssd_scan, ssd_scan_jnp
from test_ssd_scan import dual, recurrence  # tests/ is on sys.path under pytest

BT, H, P, G, N, CHUNK = 2, 8, 64, 2, 128, 128
# two whole chunks; a T that 128 does not divide (the tail padded with steps of dt = 0)
LENGTHS = [256, 300]


def _inputs(t, seed=0, dtype=jnp.float32, bt=BT, h=H, p=P, g=G, n=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (bt, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (bt, t, h)) - 2.0)
    a_head = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    b = (jax.random.normal(k[3], (bt, t, g, n)) / 4).astype(dtype)
    c = (jax.random.normal(k[4], (bt, t, g, n)) / 4).astype(dtype)
    d_head = 1.0 + 0.1 * jax.random.normal(k[5], (h,))
    return x, dt, a_head, b, c, d_head


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12)


def _jnp_form(*args):
    return ssd_scan_jnp(*args, CHUNK)


OTHERS = {"jnp_form": _jnp_form, "recurrence": recurrence, "dual_form": dual}
# the dual form sums dt A over the whole sequence in float32: its own rounding, at T 300, is 2.7e-5
VALUE_TOL = {"jnp_form": 2e-5, "recurrence": 2e-5, "dual_form": 5e-5}
_loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))))
_kernel = lambda *a: ssd_scan(*a, CHUNK)


@pytest.mark.parametrize("other", list(OTHERS))
@pytest.mark.parametrize("t", LENGTHS)
def test_values(t, other):
    args = _inputs(t)
    assert kernel_chunks(t, H, P, G, N, CHUNK) == num_chunks(t, CHUNK) == -(-t // CHUNK)
    with jax.default_matmul_precision("highest"):
        got, want = _kernel(*args), OTHERS[other](*args)
    assert got.shape == want.shape == (BT, t, H, P) and got.dtype == jnp.float32
    assert _gap(got, want) <= VALUE_TOL[other]


@pytest.mark.parametrize("other", list(OTHERS))
@pytest.mark.parametrize("t", LENGTHS)
def test_gradients(t, other):
    """Every cotangent: ``x``, ``dt``, ``A``, ``B``, ``C``, ``D``."""
    args = _inputs(t, seed=1)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(_loss(_kernel), argnums=range(6))(*args)
        want = jax.grad(_loss(OTHERS[other]), argnums=range(6))(*args)
    assert [a.shape for a in got] == [a.shape for a in args]
    bad = {i: _gap(a, b) for i, (a, b) in enumerate(zip(got, want)) if not _gap(a, b) <= 2e-4}
    assert not bad, bad


def test_bfloat16_operands_float32_state():
    """bfloat16 products on the float32 state: the kernels are as near
    the float32 recurrence as the ``jnp`` form is (the same casts), in
    values and in every cotangent, to bfloat16's rounding."""
    args = _inputs(256, seed=2, dtype=jnp.bfloat16)
    exact = tuple(v.astype(jnp.float32) for v in args)
    got, form = _kernel(*args), _jnp_form(*args)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*exact)
        dwant = jax.grad(_loss(recurrence), argnums=range(6))(*exact)
    assert got.dtype == jnp.bfloat16
    assert _gap(got, want) <= 2e-2 and _gap(got, form) <= 2e-2
    dgot = jax.grad(_loss(_kernel), argnums=range(6))(*args)
    dform = jax.grad(_loss(_jnp_form), argnums=range(6))(*args)
    assert [a.dtype for a in dgot] == [a.dtype for a in args]
    for i, (a, f, w) in enumerate(zip(dgot, dform, dwant)):
        assert _gap(a, w) <= max(3e-2, 2 * _gap(f, w)), (i, _gap(a, w), _gap(f, w))


def test_the_carried_state_crosses_chunks():
    """An input in the first chunk is felt in the last, forward and
    backward: the state in VMEM outlives a grid step, and so does its
    cotangent in the reverse sweep."""
    x, dt, a_head, b, c, d_head = _inputs(384)
    a_head = a_head * 0.01  # slow decay
    full = _kernel(x, dt, a_head, b, c, d_head)
    cut = _kernel(x.at[:, :CHUNK].set(0.0), dt, a_head, b, c, d_head)
    assert _gap(cut[:, :CHUNK], full[:, :CHUNK]) > 1e-3  # the skip and the chunk's own products
    assert _gap(cut[:, 2 * CHUNK:], full[:, 2 * CHUNK:]) > 1e-3
    dx = jax.grad(lambda x: jnp.sum(_kernel(x, dt, a_head, b, c, d_head)[:, 2 * CHUNK:]))(x)
    assert float(jnp.max(jnp.abs(dx[:, :CHUNK]))) > 1e-3


def test_vmapped_lanes():
    """The evaluation's form: lanes vmapped over the kernels (a grid
    axis, not a loop), values and gradients."""
    args = _inputs(256, seed=3)
    per_lane = lambda f: jax.vmap(
        lambda x, dt, b, c: f(x[None], dt[None], args[2], b[None], c[None], args[5])[0])
    lanes = (args[0], args[1], args[3], args[4])
    assert _gap(per_lane(_kernel)(*lanes), _kernel(*args)) <= 1e-6
    got = jax.grad(lambda *v: jnp.sum(jnp.sin(per_lane(_kernel)(*v))), argnums=range(4))(*lanes)
    want = jax.grad(lambda *v: jnp.sum(jnp.sin(per_lane(_jnp_form)(*v))), argnums=range(4))(*lanes)
    assert max(_gap(a, b) for a, b in zip(got, want)) <= 2e-4


@pytest.mark.parametrize("h,p,g,n", [(4, 128, 2, 128), (8, 32, 1, 256)], ids=["one_head_a_tile", "four_heads_a_tile"])
def test_other_widths_the_kernels_take(h, p, g, n):
    args = _inputs(256, seed=4, bt=1, h=h, p=p, g=g, n=n)
    assert kernel_chunks(256, h, p, g, n, CHUNK) == 2
    with jax.default_matmul_precision("highest"):
        assert _gap(_kernel(*args), _jnp_form(*args)) <= 2e-5
        got = jax.grad(_loss(_kernel), argnums=range(6))(*args)
        want = jax.grad(_loss(_jnp_form), argnums=range(6))(*args)
    assert max(_gap(a, b) for a, b in zip(got, want)) <= 2e-4


def _custom_calls(fn, *args):
    return jax.jit(fn).lower(*args).as_text().count("custom_call")


@pytest.mark.parametrize("t,h,p,g,n,chunk,why", [
    (64, 4, 8, 2, 16, 16, "the tier-1 tests' widths"),
    (256, 8, 64, 2, 128, 64, "a chunk that is no whole lane tile"),
    (256, 8, 64, 2, 64, 128, "a state that is no whole lane tile"),
    (256, 8, 64, 8, 128, 128, "a group of one head of 64: half a lane tile"),
    (256, 8, 48, 1, 128, 128, "a head that is no share of a lane tile"),
    (100, 8, 64, 2, 128, 128, "a sequence shorter than a chunk"),
])
def test_shapes_the_kernels_leave_to_the_jnp_form(t, h, p, g, n, chunk, why):
    """By shape alone: no knob, no environment. What ``ssd_scan`` gives
    is then ``ssd_scan_jnp``'s, and its lowering holds no custom call."""
    assert kernel_chunks(t, h, p, g, n, chunk) == 0, why
    args = _inputs(t, bt=1, h=h, p=p, g=g, n=n)
    assert _custom_calls(lambda *a: ssd_scan(*a, chunk), *args) == 0
    np.testing.assert_array_equal(ssd_scan(*args, chunk), ssd_scan_jnp(*args, chunk))


def test_a_kernel_sized_shape_lowers_to_the_kernels():
    args = _inputs(256)
    geom = ssd_kernel.Geometry(H, P, G, N, CHUNK)
    assert ssd_kernel.takes(256, geom) and (geom.width, geom.heads_per_tile, geom.tiles) == (128, 2, 4)
    # on the CPU the interpreter's loop, not a Mosaic call; for the TPU one kernel forward ...
    text = jax.jit(_kernel).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1 and "ssd_scan_fwd" in text
    # ... and the forward again (with the chunks' entry states) and the reverse sweep behind a gradient
    text = jax.jit(jax.grad(_loss(_kernel), argnums=range(6))).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2 and "ssd_scan_bwd" in text


@pytest.mark.parametrize("bad", ["groups", "chunk"])
def test_shapes_it_cannot_scan_are_refused(bad):
    x, dt, a_head, b, c, d_head = _inputs(256)
    with pytest.raises(ValueError, match="no such scan"):
        if bad == "groups":
            ssd_scan(x, dt, a_head, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), d_head, CHUNK)
        else:
            ssd_scan(x, dt, a_head, b, c, d_head, 0)


# -- the counter, through one lane-after-lane round -------------------------

def _round_record(**over):
    """One round of ``test_moe_decoder.py``'s ``ssm_relu2`` case through
    the lane-after-lane engine; its record."""
    from fedml_tpu import data, models
    from fedml_tpu.simulation import fedavg_api
    from test_moe_decoder import _args_ssm, _fed_args

    args = _fed_args(lambda **kw: _args_ssm(**{**kw, **over}), synthetic_train_size=10, frequency_of_the_test=5)
    ds = data.load(args)
    heavy, fedavg_api._HEAVY_LANE_STEP = fedavg_api._HEAVY_LANE_STEP, 0
    try:
        api = fedavg_api.FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    finally:
        fedavg_api._HEAVY_LANE_STEP = heavy
    assert api._round_exec_name() == "simulation.round_fn_ragged"
    api.train()
    return api.history[-1], args


def test_ssm_kernel_chunks_off_the_kernel_path():
    rec, _ = _round_record()
    assert rec["ssm_chunks"] > 0 and rec["ssm_kernel_chunks"] == 0.0


def test_ssm_kernel_chunks_on_the_kernel_path():
    """The same model at a kernel-sized mixer (4 heads of 64 over 2
    groups, state 128, chunks of 128 in sequences of 256): every chunk
    of every mixer's scan, a step, ran as the kernels -- and the round
    trained (a finite loss under the uniform's log 64)."""
    rec, args = _round_record(
        seq_len=256, ssm_num_heads=4, ssm_head_dim=64, ssm_groups=2, ssm_state_size=128, ssm_chunk_size=128)
    assert rec["ssm_kernel_chunks"] == rec["ssm_chunks"] == 3 * rec["steps_run"] * 2 * 2
    assert rec["moe_dropped"] == 0.0 and 0 < rec["train_loss_cohort"] < np.log(64) + 0.5


# -- what a mixer's lowering for the TPU holds ------------------------------

# the TwoTower cell's mixer (benchmark/configs/nemotron_twotower_30b_a3b_fedavg_ep16.json)
CELL = dict(num_heads=64, head_dim=64, groups=8, state_size=128, conv_taps=4, chunk_size=128, eps=1e-5)
HIDDEN, SEQ = 2688, 8192
# What the final tree of PR 36 reads (sandbox, jax 0.9.0): 3 call sites
# (forward; behind the gradient the forward again, with the chunks'
# entry states, and the reverse sweep) in 371,027 bytes of StableHLO
# text. The kernels unroll a chunk's 8 groups x 4 lane tiles on purpose
# (``ssd_kernel._unrolled``: 0.7 s of lowering for the three, measured);
# the limits leave room for a change of JAX's printing and none for a
# body unrolled over the 64 chunks as well, or for a fourth kernel.
MAX_CUSTOM_CALLS, MAX_TEXT_BYTES = 3, 600_000
HOST_CALLBACKS = re.compile(r"callback|xla_python|xla_ffi_python|host_compute|SendToHost|RecvFromHost|outfeed|infeed", re.I)


def test_a_mixers_lowering_for_the_tpu_stays_small_and_cacheable():
    """One ``Mamba2Mixer`` at the cell's widths, forward and gradient in
    one module, lowered for the TPU from shapes alone (nothing runs)."""
    from fedml_tpu.models.decoder import Mamba2Mixer

    mixer = Mamba2Mixer(**CELL)
    u = jax.ShapeDtypeStruct((1, SEQ, HIDDEN), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(u.shape, u.dtype)))["params"]

    def both(params, u):
        forward = lambda p, v: mixer.apply({"params": p}, v, mutable=["counters"])[0]
        loss = lambda p, v: jnp.sum(forward(p, v).astype(jnp.float32))
        return forward(params, u), jax.grad(loss, argnums=(0, 1))(params, u)

    lowered = jax.jit(both).trace(params, u).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    calls = text.count("tpu_custom_call")
    print("mixer lowering:", calls, "tpu_custom_call sites,", len(text), "bytes")
    assert 0 < calls <= MAX_CUSTOM_CALLS, calls
    assert len(text) <= MAX_TEXT_BYTES, len(text)
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert "blk.ssm.scan" in lowered.as_text(debug_info=True)  # the scope the trace's readers find the kernels under
    found = HOST_CALLBACKS.findall(text)
    assert not found, found
