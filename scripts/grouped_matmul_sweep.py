#!/usr/bin/env python
"""The experts' grouped product at the LM cells' chunk shapes, kernel
against ``jax.lax.ragged_dot``: one JSON line a measurement.

For each cell's chunk (rows, hidden, expert width; 8 held experts, rows
filled to an even load from a Dirichlet draw) it times the six product
forms a layer-step runs -- the first products (``up``), ``down``, the
backward's ``dh`` and ``dx`` (transposed stacks) and the two stack
gradients (``dw_in``, ``dw_out``) -- as ``ragged_dot`` and as the shipped
Pallas ``gmm`` / ``tgmm`` over a few tilings, ``ops/grouped_matmul.tiling``'s
among them (``"rule": true``); then the whole chunk MLP forward and
backward both ways (``grouped_matmul._kernel_*`` / ``_reference_*``) at a
full and at a drifted (0.45) load, and how far the two backwards differ.

    python scripts/grouped_matmul_sweep.py [mellum2 lfm2 twotower] [--aot]

On a TPU it runs and times (host clock round 20 calls after a warm-up);
with ``--aot`` it only compiles every form for a described v5e (from
the CPU, no timing), which finds tilings the chip's compiler refuses.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from fedml_tpu.models.decoder import expert_chunk_rows  # noqa: E402
from fedml_tpu.ops import grouped_matmul as gm  # noqa: E402

CONFIGS = {"mellum2": "mellum2_12b_a2p5b_fedavg_ep8", "lfm2": "lfm2_8b_a1b_fedavg_ep4",
           "twotower": "nemotron_twotower_30b_a3b_fedavg_ep16"}
GROUPS, REPS = 8, 20


def cell_chunk(cell):
    """``(rows, hidden, expert width, stacks)`` of the chunk the cell's
    expert layer hands the grouped product, from its configuration."""
    with open(os.path.join(REPO, "benchmark", "configs", CONFIGS[cell] + ".json")) as f:
        a = json.load(f)["program_args"]
    held = a["num_experts"] // a["expert_parallel"]
    rows = expert_chunk_rows(a["batch_size"] * a["seq_len"], a["experts_per_token"], held, a["num_experts"])
    return rows, a["hidden_size"], a["expert_dim"], 2 if a.get("expert_activation") == "relu2" else 3


CHUNKS = {cell: cell_chunk(cell) for cell in CONFIGS}
AOT = "--aot" in sys.argv


def _sharding():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])


ONE_CHIP = _sharding() if AOT else None


def _sizes(m, fill, seed=0):
    p = np.random.default_rng(seed).dirichlet(np.full(GROUPS, 8.0))
    total = int(m / 1.25 * fill)
    s = np.floor(p * total).astype(np.int32)
    s[0] += total - s.sum()
    return jnp.asarray(s, jnp.int32)


def _array(key, shape, scale=1.0, dtype=jnp.bfloat16):
    if AOT:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=ONE_CHIP)
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale).astype(dtype)


def _time(fn, args):
    f = jax.jit(fn)
    if AOT:
        t0 = time.perf_counter()
        f.trace(*args).lower(lowering_platforms=("tpu",)).compile()
        return {"compile_s": round(time.perf_counter() - t0, 2)}
    t0 = time.perf_counter()
    jax.block_until_ready(f(*args))
    first = time.perf_counter() - t0
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = f(*args)
    jax.block_until_ready(out)
    return {"ms": round((time.perf_counter() - t0) / REPS * 1e3, 4), "first_s": round(first, 2)}


def emit(fn, args, **line):
    try:
        line.update(_time(fn, args))
    except Exception as e:  # a tiling the compiler refuses
        line["error"] = f"{type(e).__name__}: {str(e).splitlines()[0][:200]}"
    print(json.dumps(line), flush=True)


def _rd(a, w, s):
    return jax.lax.ragged_dot(a, w, s, preferred_element_type=jnp.float32)


def _rd_grad(a, d, s):
    dims = jax.lax.RaggedDotDimensionNumbers((((0,), (0,)), ((), ())), (0,), ())
    return jax.lax.ragged_dot_general(a, d, s, dims, preferred_element_type=jnp.float32)


def sweep(cell):
    m, c, i, n_stacks = CHUNKS[cell]
    sizes = jax.ShapeDtypeStruct((GROUPS,), jnp.int32, sharding=ONE_CHIP) if AOT else _sizes(m, 1.0)
    xs, dout, h, dh = _array(1, (m, c)), _array(2, (m, c)), _array(3, (m, i)), _array(4, (m, i))
    w_in, w_out = _array(5, (GROUPS, c, i), 0.02), _array(6, (GROUPS, i, c), 0.02)
    t_out = lambda w: jnp.swapaxes(w, 1, 2)
    forms = {  # name: (ragged form, kernel form for a tiling, operands, (k, n), kernel)
        "up": (_rd, lambda t: lambda a, w, s: gmm(a, w, s, jnp.float32, t), (xs, w_in), (c, i), "gmm"),
        "down": (_rd, lambda t: lambda a, w, s: gmm(a, w, s, jnp.float32, t), (h, w_out), (i, c), "gmm"),
        "dh": (lambda a, w, s: _rd(a, t_out(w), s),
               lambda t: lambda a, w, s: gmm(a, w, s, jnp.float32, t, transpose_rhs=True), (dout, w_out), (c, i), "gmm"),
        "dx": (lambda a, w, s: _rd(a, t_out(w), s),
               lambda t: lambda a, w, s: gmm(a, w, s, jnp.float32, t, transpose_rhs=True), (dh, w_in), (i, c), "gmm"),
        "dw_in": (_rd_grad, lambda t: lambda a, d, s: tgmm(a.swapaxes(0, 1), d, s, jnp.float32, t), (xs, dh), (c, i), "tgmm"),
        "dw_out": (_rd_grad, lambda t: lambda a, d, s: tgmm(a.swapaxes(0, 1), d, s, jnp.float32, t), (h, dout), (i, c), "tgmm"),
    }
    for name, (ragged, kernel, operands, (k, n), form) in forms.items():
        line = dict(cell=cell, form=name, flops=2 * int(m / 1.25) * k * n)
        emit(ragged, (*operands, sizes), impl="ragged_dot", **line)
        rule = gm.tiling(form, m, k, n)
        tilings = {rule} | {(tm, min(tk, k), min(tn, n))
                            for tm in ((128, 256) if m % 512 else (256, 512, 1024))
                            for tk, tn in ((512, 512), (1024, 512), (512, 1024), (1024, 1024))}
        for t in sorted(tilings):
            emit(kernel(t), (*operands, sizes), impl="kernel", tiling=list(t), rule=t == rule, **line)
    stacks = [w_in] * (n_stacks - 1) + [w_out]
    for fill in (1.0, 0.45):
        s = sizes if AOT else _sizes(m, fill)
        for impl, fwd, bwd in (("ragged_dot", gm._reference_forward, gm._reference_backward),
                               ("kernel", gm._kernel_forward, gm._kernel_backward)):
            emit(lambda x, s, *w: fwd(x, s, *w), (xs, s, *stacks), cell=cell, form="mlp_fwd", impl=impl, fill=fill)
            emit(lambda x, s, d, *w: bwd(x, s, d, *w), (xs, s, dout, *stacks), cell=cell, form="mlp_bwd", impl=impl, fill=fill)
    if not AOT:
        s = _sizes(m, 1.0)
        want = jax.jit(gm._reference_backward)(xs, s, dout, *stacks)
        got = jax.jit(gm._kernel_backward)(xs, s, dout, *stacks)
        rows = np.arange(m) < int(s.sum())
        gap = lambda a, b: float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
                                 / max(np.max(np.abs(np.asarray(b, np.float64))), 1e-30))
        print(json.dumps(dict(cell=cell, check="mlp_bwd", out=gap(got[0][rows], want[0][rows]),
                              dxs=gap(got[1][rows], want[1][rows]),
                              dstacks=[gap(a, b) for a, b in zip(got[2], want[2])])), flush=True)


if __name__ == "__main__":
    for cell in [a for a in sys.argv[1:] if a in CHUNKS] or list(CHUNKS):
        sweep(cell)
    print(json.dumps({"device": jax.devices()[0].device_kind, "aot": AOT}))
