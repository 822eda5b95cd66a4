#!/usr/bin/env python
"""One rotated attention layer at each rotated LM cell's widths, the
rotation written three ways: one JSON line a measurement.

The forms are ``concat`` (upcast, concatenate the negated halves: the
decoder's only form before the product, and still its form for a head
narrower than 128), ``product`` (``models/decoder._rope_bf16``:
rotate-half as a signed-permutation product on the bfloat16 operand,
with its own backward; ``apply_rope`` takes it where the head width is
a multiple of 128) and ``none`` (the same layer unrotated: the floor).
Each is timed and counted as a layer-step (forward, ``jax.checkpoint``'s
recompute and backward, the parameters' and the input's gradients, as a
training step runs it) and as an evaluation's forward over one
sequence. A shape is a cell's attention widths (hidden, heads, head
width, window, q/k norm, output gate), its sequence length and its
sequences a step (Mellum2's two lanes of 4 as one batch of 8); the
weights are drawn, in bfloat16.

    python scripts/rope_probe.py [mellum2_window mellum2_full trinity_window lfm2_full] [--aot | --rounding]

On a TPU it runs, times (host clock over 10 calls after a warm-up) and
reads ``cost_analysis()`` of what it ran, then says how many elements
of the two rotated forms' outputs and gradients differ; ``taken`` marks
the form ``apply_rope`` takes at the shape. With ``--aot`` it only
compiles each form for a described v5e (from the CPU, no timing) and
prints the same counts of bytes and operations. With ``--rounding`` it
runs the rotation alone in both forms and counts the elements each
misses of the formula evaluated with every operation rounded as written.
"""

import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from fedml_tpu.models import decoder  # noqa: E402

SHAPES = {  # name: (configuration, layer type)
    "mellum2_window": ("mellum2_12b_a2p5b_fedavg_ep8", decoder.SLIDING),
    "mellum2_full": ("mellum2_12b_a2p5b_fedavg_ep8", decoder.FULL),
    "trinity_window": ("trinity_mini_fedavg_ep16", decoder.SLIDING),
    "lfm2_full": ("lfm2_8b_a1b_fedavg_ep4", decoder.FULL),
}
LANES = {"mellum2_12b_a2p5b_fedavg_ep8": 2}  # the vmapped cohort's lanes; the others run one at a time
REPS = 10
AOT = "--aot" in sys.argv


def _sharding():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])


ONE_CHIP = _sharding() if AOT else None


def concat(x, cos, sin):
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None] + rotated * sin[None, :, None]).astype(x.dtype)


@contextlib.contextmanager
def rotation(form):
    """``decoder.apply_rope`` as ``form`` while a layer is traced."""
    taken = decoder.apply_rope
    decoder.apply_rope = concat if form == "concat" else decoder._rope_bf16
    try:
        yield
    finally:
        decoder.apply_rope = taken


def layer(name):
    """``(Attention, rope parameters, seq_len, sequences a step)``."""
    config, kind = SHAPES[name]
    with open(os.path.join(REPO, "benchmark", "configs", config + ".json")) as f:
        a = json.load(f)["program_args"]
    attn = decoder.Attention(
        num_heads=a["num_heads"], num_kv_heads=a["num_kv_heads"], head_dim=a["head_dim"],
        window=a["sliding_window"] if kind == decoder.SLIDING else None, impl="flash",
        eps=a.get("rms_norm_eps", 1e-6), qk_norm=a.get("qk_norm", True), output_gate=bool(a.get("output_gate")))
    return attn, a["hidden_size"], a["rope_parameters"][kind], a["seq_len"], a["batch_size"] * LANES.get(config, 1)


def _array(key, shape, dtype=jnp.bfloat16):
    if AOT:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=ONE_CHIP)
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


def _fns(attn, rope, form):
    def tables(x):
        if form == "none":
            return None, None
        return decoder.rope_tables(x.shape[1], attn.head_dim, dict(rope))

    def fwd(params, x):
        return attn.apply(params, x, *tables(x))

    def step(params, x, g):
        out, pullback = jax.vjp(jax.checkpoint(fwd), params, x)
        return pullback(g)

    return step, fwd


def _compile(fn, args, form):
    with rotation(form):
        lowered = jax.jit(fn).trace(*args).lower(**({"lowering_platforms": ("tpu",)} if AOT else {}))
        t0 = time.perf_counter()
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    line = {"compile_s": round(time.perf_counter() - t0, 2), "bytes": int(cost.get("bytes accessed", -1)),
            "gib": round(cost.get("bytes accessed", -1) / 2**30, 3), "flops": int(cost.get("flops", -1))}
    return compiled, line


def _time(compiled, args):
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = compiled(*args)
    jax.block_until_ready(out)
    return {"ms": round((time.perf_counter() - t0) / REPS * 1e3, 4)}, out


def _differ(a, b):
    """Elements that differ between two trees, and the largest gap."""
    n, gap = 0, 0.0
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        n += int(np.sum(u != v))
        gap = max(gap, float(np.max(np.abs(u - v))))
    return {"differ": n, "max_abs": gap}


def probe(name):
    attn, hidden, rope, T, batch = layer(name)
    x = _array(1, (batch, T, hidden))
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, T, hidden), jnp.bfloat16),
                            *decoder.rope_tables(T, attn.head_dim, dict(rope)))
    if AOT:
        params = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=ONE_CHIP), params)
    else:
        params = jax.tree_util.tree_map(lambda s: (0.02 * jax.random.normal(jax.random.PRNGKey(2), s.shape)).astype(jnp.bfloat16),
                                        params)
    g, one = _array(3, (batch, T, hidden)), _array(4, (1, T, hidden))
    outputs = {}
    for form in ("concat", "product", "none"):
        step, fwd = _fns(attn, rope, form)
        for pass_, fn, args in (("step", step, (params, x, g)), ("eval_fwd", fwd, (params, one))):
            compiled, line = _compile(fn, args, form)
            if not AOT:
                timed, outputs[form, pass_] = _time(compiled, args)
                line.update(timed)
            taken = form == ("product" if attn.head_dim % decoder._LANES == 0 else "concat")
            print(json.dumps(dict(shape=name, form=form, pass_=pass_, batch=batch if pass_ == "step" else 1,
                                  seq_len=T, taken=taken, **line)), flush=True)
    if not AOT:
        for pass_ in ("step", "eval_fwd"):
            print(json.dumps(dict(shape=name, check=pass_, **_differ(outputs["product", pass_],
                                                                       outputs["concat", pass_]))), flush=True)


def rounding(name):
    """The rotation alone (the q heads of the shape's first sequence),
    jitted in both forms, against numpy's float32 evaluation of the
    same formula with each operation rounded as written: how many
    elements of the output and of the cotangent each form misses."""
    attn, _, rope, T, _ = layer(name)
    cos, sin = decoder.rope_tables(T, attn.head_dim, dict(rope))
    shape = (1, T, attn.num_heads, attn.head_dim)
    x, g = _array(5, shape), _array(6, shape)
    c, s = np.asarray(cos)[None, :, None], np.asarray(sin)[None, :, None]
    h = attn.head_dim // 2
    half = lambda a, sign: np.concatenate([sign * a[..., h:], -sign * a[..., :h]], axis=-1)
    x32, g32 = np.asarray(x, np.float32), np.asarray(g, np.float32)
    want = {"out": x32 * c + half(x32, -1) * s, "dx": g32 * c + half(g32, 1) * np.concatenate([s[..., h:], s[..., :h]], -1)}
    for form, fn in (("concat", concat), ("product", decoder._rope_bf16)):
        got = jax.jit(lambda x, g: dict(zip(want, _vjp_of(fn, cos, sin, x, g))))(x, g)
        print(json.dumps(dict(shape=name, check="rounding", form=form, elements=int(np.prod(shape)), **{
            k: int(np.sum(np.asarray(got[k], np.float32) != np.asarray(jnp.asarray(want[k]).astype(jnp.bfloat16), np.float32)))
            for k in want})), flush=True)


def _vjp_of(fn, cos, sin, x, g):
    out, pullback = jax.vjp(lambda x: fn(x, cos, sin), x)
    return out, pullback(g)[0]


if __name__ == "__main__":
    for name in [a for a in sys.argv[1:] if a in SHAPES] or list(SHAPES):
        if "--rounding" in sys.argv:
            rounding(name)
        else:
            probe(name)
    print(json.dumps({"device": jax.devices()[0].device_kind, "aot": AOT}))
