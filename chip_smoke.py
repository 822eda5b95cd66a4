#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

    python chip_smoke.py            # from the repo root, on a TPU host

One process, no children, no arguments, no network. It drives the main
path once through the entry points a user calls, with random weights
made from a seed, and checks what comes out by the repo's own means:

- stage A  ``fedml_tpu.run_simulation()`` (SP backend, ``--cf``
  examples/simulation_sp/resnet18_cifar10/fedml_config.yaml): 100-client
  FedAvg, ResNet-18(GN) at full width, CIFAR-10-shaped stand-in
  synthesized on the device, 10 clients a round, batch 64, bf16, 3
  rounds with an evaluation each. Passes when every round's loss and the
  final params are finite, the params live on a TPU device, and the
  round function was traced once (rounds 2-3 traced nothing new).
- stage B  ``fedml_tpu.run_distributed()`` (``--cf``
  examples/longcontext/flash_one_chip/fedml_config.yaml): one epoch of
  a 12-layer x 12-head x 768 decoder at 1,024 tokens with
  ``attention_impl: flash``, then the Pallas kernel alone — forward and
  backward at B4 H8 T4096 D64 bf16, compared with dense attention at a
  smaller shape inside a stated bf16 tolerance, and run at the largest
  sequence length it accepts.
- stage C  only with >= 4 devices: stage A's cohort through
  ``run_simulation(backend="MESH")`` on ``mesh_shape {data: 2, fsdp: 2}``
  and on ``{data: 1, fsdp: 1}``; params sharded per the SpecLayout
  table, memory in use on every chip, and ``max_abs_diff`` of the final
  params between the worlds (reported, never a failure).

Timings printed here are smoke observations (compile apart from run),
not benchmark numbers. Exit code 0 and a last stdout line
``{"ok": true, "device": {...}}`` mean every stage passed; without a TPU
(or outside the repo) it exits non-zero and prints no result.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
STAGE_A_CF = os.path.join(
    REPO, "examples", "simulation_sp", "resnet18_cifar10", "fedml_config.yaml"
)
STAGE_B_CF = os.path.join(
    REPO, "examples", "longcontext", "flash_one_chip", "fedml_config.yaml"
)
# B, T, H, D of the kernel run alone: the LM cell's T, 8 heads of 64
KERNEL_BENCH_SHAPE = (4, 4096, 8, 64)
# where flash (bf16) is compared with parallel.sequence.full_attention
KERNEL_CHECK_SHAPE = (2, 1024, 4, 64)
# bf16 keeps 8 significand bits (eps 2^-8 = 3.9e-3); inputs are N(0, 1),
# outputs O(1), the probabilities are rounded to bf16 once before the PV
# product and the output once more — a few eps, with headroom
FLASH_OUT_ATOL = 3e-2
# gradients pass through two more bf16 roundings; judged relative to the
# largest reference gradient entry
FLASH_GRAD_RTOL = 5e-2
# the backward kernels' second comparison on the chip: B, T, H, KV, D, window
GROUPED_WINDOW_SHAPE = (2, 1024, 8, 2, 128, 256)

_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
_BACKEND_COMPILE_EVENT = _COMPILE_EVENT_PREFIX + "backend_compile_duration"


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class StageClock:
    """Wall seconds of a stage, with JAX's own compile accounting taken
    out: ``compile_s`` is backend compilation (XLA + Mosaic, or the load
    from the persistent cache — what a warm cache shrinks),
    ``trace_lower_s`` is jaxpr tracing + MLIR lowering (nested jits can
    be counted twice, so the remainder is clamped at zero)."""

    def __init__(self) -> None:
        self.compile_s = self.trace_lower_s = self.wall_s = 0.0
        self._t0 = 0.0

    def on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.compile_s += duration_secs
        elif event.startswith(_COMPILE_EVENT_PREFIX):
            self.trace_lower_s += duration_secs

    def __enter__(self) -> "StageClock":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self.on_duration)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        self.wall_s = time.perf_counter() - self._t0
        monitoring.unregister_event_duration_listener(self.on_duration)

    def report(self) -> dict:
        rest = self.wall_s - self.compile_s - self.trace_lower_s
        return {
            "wall_s": round(self.wall_s, 2),
            "compile_s": round(self.compile_s, 2),
            "trace_lower_s": round(self.trace_lower_s, 2),
            "run_s": round(max(rest, 0.0), 2),
        }


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _run_with_cf(fn, cf: str, **kw):
    """Call a one-line entry the way a user does: config through --cf."""
    argv = sys.argv
    sys.argv = [argv[0], "--cf", cf]
    try:
        return fn(**kw)
    finally:
        sys.argv = argv


def _tree_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    return all(
        bool(jnp.isfinite(leaf).all())
        for leaf in jax.tree.leaves(tree)
        if jnp.issubdtype(leaf.dtype, jnp.floating)
    )


def _max_abs_diff(a, b) -> float:
    import jax
    import numpy as np

    return max(
        float(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64)).max())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def run_fedavg_world(cf: str, backend: str, platform: str, inspect=None) -> dict:
    """One FedAvg world through ``run_simulation`` (stage A and each
    stage C world). Returns the checks' evidence plus the final params
    as host arrays; ``inspect(simulator) -> dict`` adds evidence while
    the world's device arrays are still alive (nothing of the world
    outlives this call, so the next stage gets the chip's memory back)."""
    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import constants
    from fedml_tpu.simulation import simulator

    # run_simulation() returns the final stats only; the simulator it
    # builds is what holds params and the trace count
    cls = (
        simulator.SimulatorSingleProcess
        if backend == constants.FEDML_SIMULATION_TYPE_SP
        else simulator.SimulatorMesh
    )
    built = []
    orig_run = cls.run

    def recording_run(self):
        built.append(self)
        return orig_run(self)

    cls.run = recording_run
    try:
        with StageClock() as clock:
            final = _run_with_cf(fedml_tpu.run_simulation, cf, backend=backend)
            api = built[0].fl_trainer
            jax.block_until_ready(api.global_params)
    finally:
        cls.run = orig_run

    rounds = int(api.args.comm_round)
    losses = [h["train_loss_cohort"] for h in api.history]
    _check(len(api.history) == rounds, f"{len(api.history)} round records, want {rounds}")
    _check(
        all(math.isfinite(h[k]) for h in api.history
            for k in ("train_loss_cohort", "train_loss", "test_loss")),
        f"non-finite loss in {api.history}",
    )
    _check(math.isfinite(final["test_loss"]), f"final stats {final}")
    _check(_tree_finite(api.global_params), "non-finite final params")
    leaves = jax.tree.leaves(api.global_params)
    on = {d.platform for leaf in leaves for d in leaf.devices()}
    _check(on == {platform}, f"params live on {on}, want {platform}")
    _check(
        api._round_trace_count == 1,
        f"round fn traced {api._round_trace_count}x over {rounds} rounds "
        "(rounds after the first must trace nothing new)",
    )
    return {
        "params": jax.tree.map(np.asarray, api.global_params),
        "evidence": {
            "model": api.model.name,
            "param_count": int(sum(leaf.size for leaf in leaves)),
            "clients_per_round": int(api.args.client_num_per_round),
            "cohort_bucket": api.pipeline_stats.get("bucket"),
            "rounds": rounds,
            "cohort_loss_by_round": [round(x, 4) for x in losses],
            "final_test_loss": round(final["test_loss"], 4),
            "round_fn_traces": api._round_trace_count,
            **clock.report(),
            **(inspect(built[0]) if inspect else {}),
        },
    }


def _kernel_alone(bench_shape, check_shape) -> dict:
    """The flash kernel outside any model: compiled forward + backward
    at the bench shape, agreement with dense attention at the check
    shape, and the largest sequence it accepts."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.flash_attention import flash_attention, max_seq_len
    from fedml_tpu.parallel.sequence import full_attention

    def qkv(shape, dtype, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return tuple(jax.random.normal(k, shape, dtype) for k in ks)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) ** 2).sum()

    def grad_rel_err(got, want):  # the worst entry, relative to the reference's largest
        return max(
            float(jnp.abs(g.astype(jnp.float32) - w).max() / jnp.abs(w).max())
            for g, w in zip(got, want)
        )

    out = {}
    # -- bench shape: forward and backward, compiled ------------------
    q, k, v = qkv(bench_shape, jnp.bfloat16, 0)
    flash = lambda *a: flash_attention(*a, True)
    fwd = jax.jit(flash)
    _check(
        "tpu_custom_call" in fwd.lower(q, k, v).as_text(),
        "flash forward did not lower to a Mosaic call (interpreted?)",
    )
    o = fwd(q, k, v)
    grads = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(q, k, v)
    jax.block_until_ready((o, grads))
    _check(o.shape == q.shape and o.dtype == q.dtype, f"out {o.shape} {o.dtype}")
    _check(_tree_finite((o, grads)), "non-finite flash output/grads at bench shape")
    out["bench_shape"] = "B%d T%d H%d D%d bf16 fwd+bwd compiled" % bench_shape

    # -- check shape: against the dense oracle ------------------------
    q, k, v = qkv(check_shape, jnp.bfloat16, 1)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    dense = lambda *a: full_attention(*a, causal=True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(dense)(q32, k32, v32)
        want_g = jax.jit(jax.grad(loss(dense), (0, 1, 2)))(q32, k32, v32)
    got = jax.jit(flash)(q, k, v)
    got_g = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(q, k, v)
    out_err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    grad_err = grad_rel_err(got_g, want_g)
    out["check_shape"] = "B%d T%d H%d D%d" % check_shape
    out["out_max_abs_err"] = round(out_err, 5)
    out["out_atol"] = FLASH_OUT_ATOL
    out["grad_max_rel_err"] = round(grad_err, 5)
    out["grad_rtol"] = FLASH_GRAD_RTOL
    _check(out_err <= FLASH_OUT_ATOL, f"flash vs dense: out err {out_err}")
    _check(grad_err <= FLASH_GRAD_RTOL, f"flash vs dense: grad err {grad_err}")

    # -- grouped KV under a window: the backward kernels' other shape --
    # (D = one lane tile, 4 query heads a KV head, a band of 256)
    from fedml_tpu.models.decoder import dense_attention

    B, T, H, KV, D, window = GROUPED_WINDOW_SHAPE
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, T, KV, D), jnp.bfloat16) for key in ks[1:])
    banded = lambda *a: flash_attention(*a, True, None, 256, 256, window)
    with jax.default_matmul_precision("highest"):
        want_g = jax.jit(jax.grad(loss(lambda *a: dense_attention(*a, window)), (0, 1, 2)))(
            *(x.astype(jnp.float32) for x in (q, k, v))
        )
    got_g = jax.jit(jax.grad(loss(banded), (0, 1, 2)))(q, k, v)
    grouped_err = grad_rel_err(got_g, want_g)
    out["grouped_window_shape"] = "B%d T%d H%d KV%d D%d window %d" % GROUPED_WINDOW_SHAPE
    out["grouped_window_grad_max_rel_err"] = round(grouped_err, 5)
    _check(got_g[1].shape == k.shape, f"dk {got_g[1].shape} for k {k.shape}")
    _check(
        grouped_err <= FLASH_GRAD_RTOL,
        f"flash vs dense, grouped KV under a window: grad err {grouped_err}",
    )

    # -- the largest T the kernel takes -------------------------------
    d = bench_shape[3]
    t_max = max_seq_len(d, jnp.bfloat16)
    q, k, v = qkv((1, t_max, 1, d), jnp.bfloat16, 2)
    o = fwd(q, k, v)
    # the last 128 queries attend to every key: a dense [128, T] oracle
    tail = slice(t_max - 128, t_max)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum(
            "qd,kd->qk", q[0, tail, 0].astype(jnp.float32), k[0, :, 0].astype(jnp.float32)
        ) * d ** -0.5
        mask = jnp.arange(t_max)[None, :] <= jnp.arange(t_max)[tail, None]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        want_tail = p @ v[0, :, 0].astype(jnp.float32)
    tail_err = float(jnp.abs(o[0, tail, 0].astype(jnp.float32) - want_tail).max())
    _check(_tree_finite(o), f"non-finite flash output at T={t_max}")
    _check(tail_err <= FLASH_OUT_ATOL, f"flash at T={t_max}: tail err {tail_err}")
    try:
        big = jnp.zeros((1, t_max + 128, 1, d), jnp.bfloat16)
        flash_attention(big, big, big, True)
    except ValueError as e:
        _check("exceeds" in str(e), f"unexpected error above max T: {e}")
    else:
        raise AssertionError(f"T={t_max + 128} above the stated maximum did not raise")
    out["max_seq_len_d%d_bf16" % d] = t_max
    out["max_seq_len_tail_err"] = round(tail_err, 5)
    return out


def stage_b(cf: str, bench_shape, check_shape) -> dict:
    import fedml_tpu

    with StageClock() as clock:
        stats = _run_with_cf(fedml_tpu.run_distributed, cf)
    _check(
        all(math.isfinite(stats[k]) for k in ("train_loss", "test_loss")),
        f"non-finite distributed stats {stats}",
    )
    evidence = {
        "run_distributed": {
            "train_loss": round(stats["train_loss"], 4),
            "test_loss": round(stats["test_loss"], 4),
            **clock.report(),
        }
    }
    with StageClock() as clock:
        evidence["kernel"] = _kernel_alone(bench_shape, check_shape)
    evidence["kernel"].update(clock.report())
    return evidence


def _mesh_cf(base_cf: str, mesh_shape: dict, out_dir: str) -> str:
    """Stage A's config plus a ``mesh_shape`` — one committed cohort,
    so the mesh worlds can never drift from the single-chip one."""
    import yaml

    with open(base_cf) as f:
        cfg = yaml.safe_load(f)
    cfg["train_args"]["mesh_shape"] = mesh_shape
    path = os.path.join(out_dir, "mesh_%s.yaml" % "x".join(str(v) for v in mesh_shape.values()))
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def _inspect_sharded(sim) -> dict:
    """Every param leaf sits where the SpecLayout table says, on all of
    the mesh, and every chip of the mesh holds memory."""
    import jax

    from fedml_tpu.parallel.layout import tree_shardings

    params = sim.fl_trainer.global_params
    want = tree_shardings(params, sim.mesh)
    bad = [
        jax.tree_util.keystr(path)
        for (path, leaf), w in zip(
            jax.tree_util.tree_flatten_with_path(params)[0], jax.tree.leaves(want)
        )
        if not leaf.sharding.is_equivalent_to(w, leaf.ndim)
        or len(leaf.sharding.device_set) != sim.mesh.size
    ]
    _check(not bad, f"leaves off the SpecLayout table or off-mesh: {bad[:5]}")
    in_use = [
        int((d.memory_stats() or {}).get("bytes_in_use", 0))
        for d in sim.mesh.devices.flat
    ]
    _check(all(b > 0 for b in in_use), f"idle chip: bytes_in_use {in_use}")
    n_sharded = sum(
        1 for leaf in jax.tree.leaves(params)
        if not leaf.sharding.is_fully_replicated
    )
    return {
        "mesh": dict(sim.mesh.shape),
        "leaves_sharded_over_fsdp": n_sharded,
        "leaves_total": len(jax.tree.leaves(params)),
        "bytes_in_use_per_device": in_use,
    }


def stage_c(base_cf: str, platform: str, params_a) -> dict:
    from fedml_tpu import constants

    evidence = {}
    params = {}
    with tempfile.TemporaryDirectory() as tmp:
        for key, shape in (("2x2", {"data": 2, "fsdp": 2}), ("1x1", {"data": 1, "fsdp": 1})):
            w = run_fedavg_world(
                _mesh_cf(base_cf, shape, tmp), constants.FEDML_SIMULATION_TYPE_MESH,
                platform, inspect=_inspect_sharded,
            )
            evidence[key], params[key] = w["evidence"], w["params"]
    # reported, never failed: the bitwise-identity claim was proven on
    # XLA:CPU; this is what the chip says
    evidence["max_abs_diff_2x2_vs_1x1_mesh"] = _max_abs_diff(params["2x2"], params["1x1"])
    evidence["max_abs_diff_2x2_vs_stage_a"] = _max_abs_diff(params["2x2"], params_a)
    return evidence


def main() -> int:
    # stages A-C use no native code; keep the run independent of whatever
    # native/build/ holds (core/native.py reuses binaries by mtime)
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    import jax

    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    say(f"jax {jax.__version__}  platform={platform}  device_kind={kind!r}  devices={count}")
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
            "— no result",
            file=sys.stderr,
        )
        return 2
    say("native: off (FEDML_TPU_NO_NATIVE=1) — Python scheduler/broker twins, "
        "none of which these stages call")

    import fedml_tpu  # noqa: F401  (outside the repo this is the failure)
    from fedml_tpu import constants
    from fedml_tpu.core import compile_cache

    # the peak table must know this chip (an unknown kind raises)
    say(f"peak table: {constants.peak_bf16_flops(kind) / 1e12:.0f} TFLOP/s bf16, "
        f"{constants.hbm_bandwidth_bytes(kind) / 1e9:.0f} GB/s HBM "
        f"for {constants.normalize_device_kind(kind)!r}")

    a = run_fedavg_world(STAGE_A_CF, constants.FEDML_SIMULATION_TYPE_SP, platform)
    say("stage A passed: " + json.dumps(a["evidence"]))
    b = stage_b(STAGE_B_CF, KERNEL_BENCH_SHAPE, KERNEL_CHECK_SHAPE)
    say("stage B passed: " + json.dumps(b))
    if count >= 4:
        c = stage_c(STAGE_A_CF, platform, a["params"])
        say("stage C passed: " + json.dumps(c))
    else:
        say(f"stage C skipped: {count} device(s), needs 4")
    say("compile cache: " + json.dumps(compile_cache.stats()))
    say("timings above are smoke observations, not benchmark numbers")
    print(json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
