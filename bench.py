#!/usr/bin/env python
"""Benchmark: FedAvg round throughput + scaling + MFU on the accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Headline metric (stable across rounds): fully-
jitted vectorized FedAvg rounds/sec (CNN, FEMNIST-shaped data, 32
clients/round, 5 local epochs) vs the reference's architecture on the
same hardware (sequential per-client python loop + host-side
aggregation, fedavg_api.py:102-115 / _aggregate — implemented with the
same jitted per-client step so the comparison isolates architecture).

``detail`` carries the BASELINE.md "new metrics to establish":
- ``dense``: the compute-dense north-star cohort (100-client FedAvg,
  ResNet-18(GN)/CIFAR-10-shape, 10/round, bf16) with samples/s/chip
  and ``mfu_vs_bf16_peak`` — the MFU figure that means something (the
  tiny-CNN headline is latency-bound by design);
- ``scaling``: 8->512 simulated-client sweep — cohort size vs rounds/s
  and client samples/s. ``throughput_retention_vs_base`` = sps(C)/sps(base):
  on a single chip, ~1.0 means the vectorized engine keeps the chip
  saturated as the cohort grows 64x (cohorts are compute-bound, not
  dispatch-bound); ``per_client_efficiency`` is the strong-scaling view
  (per-client throughput vs the 8-client cohort — bounded by 8/C once
  one chip saturates; >8/C headroom requires more chips, which is what
  the mesh simulator's ``clients`` axis provides). If the 8-client
  cohort itself failed, the smallest completed cohort becomes the
  base and ``retention_base_clients`` records it;
- ``samples_per_sec_per_chip`` and an MFU figure: XLA's own cost
  analysis of the round computation (compiled.cost_analysis()['flops'])
  over wall time, against the chip's peak (device-kind table);
- ``aggregation_exchange``: device-resident (zero-copy in-process
  reference passing, the TRPC-analog fast path) vs host-hop
  (msgpack serialize + deserialize + device_put, what every reference
  exchange does) round-trip time for the model tree;
- ``bf16``: the same cohort under dtype=bfloat16 (core/local_trainer.py
  mixed precision) and its speedup over the f32 headline;
- ``longctx``: the pallas flash-attention kernel vs naive XLA attention
  at T=4096 bf16, fwd+bwd tokens/s (ops/flash_attention.py — the
  long-context per-chip hot op under ring/Ulysses sequence parallelism).

Stand-in data is synthesized ON DEVICE (data/loader.py
_device_synth_classification): the machine with the chip has no dataset
and no network, and features made where they are used never cross the
host link — only labels/masks do.

Process model: one process owns the chip. The parent (no ``--phase``)
never imports JAX; it runs each phase in a child of its own, one after
another, and exits non-zero if any child failed or timed out. There is
no fallback: a child started without ``--cpu`` that finds a CPU platform
raises, so a machine without a chip produces no rate. ``--cpu`` is the
explicit flag of the CI smoke children (tiny shapes, forced host
devices); the ``meta`` block of every record names the backend and
``device_kind`` it ran on.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

# The child-phase vocabulary (argparse choices of --phase).
PHASE_CHOICES = (
    "headline", "bf16", "dense", "sweep", "longctx", "mesh", "pipeline",
    "telemetry", "serving", "chaos", "tracing", "straggler", "defense",
    "chaosplan", "planet", "hier", "multichip", "crossdevice", "elastic",
)

# round-pipeline depths the pipeline phase measures; the contract key
# set (k1/k2/k4) tests and docs pin against
_PIPELINE_KS = (1, 2, 4)

# bf16 peak matmul TFLOP/s lives in fedml_tpu.constants
# (PEAK_BF16_TFLOPS) so every MFU denominator — bench, `fedml-tpu
# perf` — is the same number. Imported lazily: the parent must not pull
# in fedml_tpu (and with it jax).


def _emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _progress(msg: str) -> None:
    """Phase breadcrumbs on STDERR (stdout carries only the JSON line)."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _force_cpu(n_devices: int = 1) -> None:
    from __graft_entry__ import _force_virtual_cpu

    _force_virtual_cpu(n_devices)


def _build_api(
    n_clients: int, epochs: int, per_client: int = 600, mesh: bool = False,
    **extra,
):
    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.simulation import FedAvgAPI

    args = Arguments()
    cfg = dict(
        dataset="femnist",
        synthetic_train_size=n_clients * per_client,
        synthetic_test_size=2000,
        model="cnn",
        partition_method="hetero",
        partition_alpha=0.5,
        client_num_in_total=n_clients,
        client_num_per_round=n_clients,
        comm_round=1,
        epochs=epochs,
        batch_size=32,
        learning_rate=0.03,
        frequency_of_the_test=10**9,
        matmul_precision="default",
    )
    cfg.update(extra)  # extras override the base config (dense phase)
    for k, v in cfg.items():
        setattr(args, k, v)
    args._validate()
    args = fedml_tpu.init(args)
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    if mesh:
        # client axis over every visible device (parallel/mesh.py
        # default); SimulatorMesh shards the packed federation and
        # replicates params — its fl_trainer is the same FedAvgAPI,
        # so _time_rounds works unchanged on the sharded arrays
        from fedml_tpu.simulation.simulator import SimulatorMesh

        sim = SimulatorMesh(args, None, dataset, model)
        return args, dataset, model, sim.fl_trainer
    api = FedAvgAPI(args, None, dataset, model)
    return args, dataset, model, api


def _time_rounds(api, dataset, args, n_rounds: int):
    """(rounds/s, samples/round, flops/round-or-None, xla-mem-or-None)
    for one cohort."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    packed = dataset.packed_train
    nsamples = jnp.asarray(dataset.packed_num_samples)
    idx = jnp.arange(args.client_num_per_round, dtype=jnp.int32)
    rng = jax.random.PRNGKey(0)

    params, state = api.global_params, api.server_state
    lowered = api._round_fn.lower(
        params, state, packed, nsamples, idx, jax.random.fold_in(rng, 0)
    )
    _progress("round fn lowered")
    compiled = lowered.compile()
    _progress("round fn compiled")
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0)) or None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        flops = None
    mem = None
    try:
        ma = compiled.memory_analysis()
        # XLA's own buffer plan: where a low MFU should send the
        # optimizer next (temp-dominated -> remat/layout; argument-
        # dominated -> batch geometry has headroom)
        mem = {
            "xla_temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
            "xla_argument_mb": round(ma.argument_size_in_bytes / 1e6, 1),
            "xla_output_mb": round(ma.output_size_in_bytes / 1e6, 1),
        }
    except Exception:  # noqa: BLE001 — best-effort, backend-dependent
        mem = None

    params, state, _ = compiled(
        params, state, packed, nsamples, idx, jax.random.fold_in(rng, 0)
    )
    jax.block_until_ready(jax.tree.leaves(params)[0])
    t0 = time.perf_counter()
    for r in range(1, n_rounds + 1):
        params, state, _ = compiled(
            params, state, packed, nsamples, idx, jax.random.fold_in(rng, r)
        )
    jax.block_until_ready(jax.tree.leaves(params)[0])
    rps = n_rounds / (time.perf_counter() - t0)
    samples_per_round = float(np.sum(dataset.packed_num_samples)) * int(args.epochs)
    return rps, samples_per_round, flops, mem


def _sequential_baseline(api, dataset, args, n_seq: int):
    """Reference architecture: python loop + host-hop aggregation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.types import Batches

    packed = dataset.packed_train
    nsamples = jnp.asarray(dataset.packed_num_samples)
    rng = jax.random.PRNGKey(0)
    local_j = jax.jit(api._local_train)

    def seq_round(params, r):
        host_acc = None
        ns = []
        for j in range(args.client_num_per_round):
            client = Batches(x=packed.x[j], y=packed.y[j], mask=packed.mask[j])
            p, _ = local_j(params, client, jax.random.fold_in(rng, r * 1000 + j))
            # reference hops every client model through host memory
            # (.cpu().state_dict(), my_model_trainer_classification.py:13)
            host_p = jax.tree.map(np.asarray, p)
            w = float(nsamples[j])
            ns.append(w)
            if host_acc is None:
                host_acc = jax.tree.map(lambda a: a * w, host_p)
            else:
                host_acc = jax.tree.map(lambda a, b: a + b * w, host_acc, host_p)
        total = sum(ns)
        return jax.tree.map(lambda a: jnp.asarray(a / total), host_acc)

    params2 = api.model.init(jax.random.PRNGKey(1))
    params2 = seq_round(params2, 0)  # compile
    t0 = time.perf_counter()
    for r in range(1, n_seq + 1):
        params2 = seq_round(params2, r)
    jax.block_until_ready(jax.tree.leaves(params2)[0])
    return n_seq / (time.perf_counter() - t0)


def _aggregation_exchange(model, n_iter: int = 20) -> dict:
    """Device-resident vs host-hop model exchange (TRPC-analog metric)."""
    import jax

    from fedml_tpu import constants
    from fedml_tpu.core.message import Message

    params = model.init(jax.random.PRNGKey(0))
    jax.block_until_ready(jax.tree.leaves(params)[0])
    dev = jax.devices()[0]

    # device-resident: the LOCAL-fabric path — the Message carries the
    # jax arrays by reference; receiver uses them directly
    t0 = time.perf_counter()
    for _ in range(n_iter):
        m = Message(constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 1, 0)
        m.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, params)
        got = m.get(constants.MSG_ARG_KEY_MODEL_PARAMS)
        jax.block_until_ready(jax.tree.leaves(got)[0])
    device_resident_s = (time.perf_counter() - t0) / n_iter

    # host-hop: serialize -> deserialize -> device_put (every reference
    # exchange, and any cross-runtime boundary)
    t0 = time.perf_counter()
    for _ in range(n_iter):
        m = Message(constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 1, 0)
        m.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, params)
        m2 = Message.from_bytes(m.to_bytes())
        back = jax.device_put(m2.get(constants.MSG_ARG_KEY_MODEL_PARAMS), dev)
        jax.block_until_ready(jax.tree.leaves(back)[0])
    host_hop_s = (time.perf_counter() - t0) / n_iter

    return {
        "device_resident_ms": round(device_resident_s * 1e3, 4),
        "host_hop_ms": round(host_hop_s * 1e3, 4),
        "speedup": round(host_hop_s / max(device_resident_s, 1e-9), 1),
    }


# headline-metric priority for the ratchet's value extraction: phases
# without a top-level {value, unit} headline expose one of these
_META_METRIC_KEYS = (
    "rounds_per_sec",
    "samples_per_sec",
    "requests_per_sec",
    "tokens_per_sec",
)


def _meta_headline(out: dict):
    """(value, metric, unit) the ratchet compares for this phase record.
    Deterministic per phase shape: explicit {value, unit} headline
    first, then the known throughput keys, then the first top-level
    numeric by sorted key."""
    v = out.get("value")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return float(v), str(out.get("metric", "value")), str(out.get("unit", ""))
    for k in _META_METRIC_KEYS:
        v = out.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return float(v), k, k
    for k in sorted(out):
        v = out[k]
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return float(v), k, k
    return None, None, None


def _find_mfu(node):
    """First ``mfu_vs_bf16_peak`` anywhere in the record (the dense /
    headline detail blocks carry it when the device kind is known)."""
    if isinstance(node, dict):
        v = node.get("mfu_vs_bf16_peak")
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return float(v)
        for val in node.values():
            found = _find_mfu(val)
            if found is not None:
                return found
    elif isinstance(node, list):
        for val in node:
            found = _find_mfu(val)
            if found is not None:
                return found
    return None


def _bench_meta(phase: str, smoke: bool, out: dict) -> dict:
    """The mandatory meta block every bench record carries (perf-plane
    ratchet contract, tests/test_bench_contract.py): device_kind /
    backend / smoke label the record so `fedml-tpu perf --ratchet`
    groups CPU smoke records separately from TPU captures; value /
    metric / unit carry the phase headline it compares; mfu rides along
    where the phase computed one."""
    import jax

    from fedml_tpu.constants import normalize_device_kind

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    meta = {
        "schema": 1,
        "phase": str(phase),
        "device_kind": normalize_device_kind(kind),
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
    }
    value, metric, unit = _meta_headline(out)
    if value is not None:
        meta.update(value=value, metric=metric, unit=unit)
    mfu = _find_mfu(out)
    if mfu is not None:
        meta["mfu"] = mfu
    return meta


def _mfu_detail(flops: float, rps: float, n_chips: int = 1) -> dict:
    """Achieved FLOP/s, plus MFU against the device kind's bf16 peak on
    an accelerator (a ``--cpu`` child has no device peak to divide by;
    an accelerator the peak table does not know raises).

    cost_analysis is XLA's static estimate (it undercounts fused convs)
    — the figure exists so utilization is judgeable, not to flatter it.
    """
    import jax

    from fedml_tpu.constants import peak_bf16_flops

    out = {
        "model_flops_per_sec": round(flops * rps, 1),
        "flops_source": "xla_cost_analysis (static estimate)",
    }
    if jax.default_backend() != "cpu":
        peak = peak_bf16_flops(jax.devices()[0].device_kind)
        out["mfu_vs_bf16_peak"] = round(flops * rps / (peak * n_chips), 4)
        out["peak_assumed_tflops"] = peak / 1e12
    return out


def _headline_cohort(on_cpu: bool) -> dict:
    """Shared by the f32 headline and the bf16 phase — their cohorts
    MUST match or detail.bf16.speedup_vs_f32 compares different work."""
    return dict(
        n_clients=8 if on_cpu else 32,
        epochs=1 if on_cpu else 5,
        n_rounds=3 if on_cpu else 10,
        per_client=100 if on_cpu else 600,
    )


def run_headline(on_cpu: bool) -> dict:
    """Headline rounds/s + sequential baseline + MFU + exchange metric
    (everything except the scaling sweep, which runs in isolated
    per-cohort subprocesses — see main())."""
    import jax

    _progress(f"backend up: {jax.devices()[0]}")

    cohort = _headline_cohort(on_cpu)
    n_clients, epochs = cohort["n_clients"], cohort["epochs"]
    n_rounds, headline_per_client = cohort["n_rounds"], cohort["per_client"]
    n_seq = 1 if on_cpu else 2

    args, dataset, model, api = _build_api(
        n_clients, epochs, per_client=headline_per_client
    )
    _progress("headline built")
    vec_rps, samples_per_round, flops, _ = _time_rounds(api, dataset, args, n_rounds)
    _progress(f"headline timed: {vec_rps:.3f} rounds/s")
    seq_rps = _sequential_baseline(api, dataset, args, n_seq)
    _progress(f"sequential baseline: {seq_rps:.4f} rounds/s")

    # the headline round is a plain jit on ONE device — per-chip and
    # MFU figures are for that chip; mesh-sharded multi-chip runs are
    # the mesh simulator's department
    n_chips = 1
    sps = vec_rps * samples_per_round
    detail = {
        "sequential_baseline_rounds_per_sec": round(seq_rps, 4),
        "client_samples_per_sec": round(sps, 1),
        "samples_per_sec_per_chip": round(sps / n_chips, 1),
        "device": str(jax.devices()[0]),
        "n_chips_used": n_chips,
        "n_devices_visible": len(jax.devices()),
    }

    # MFU of the small-CNN headline: small-model FL at batch 32 is
    # latency/HBM-bound by nature — the compute-dense phase (run_dense)
    # is where a meaningful MFU comes from; this one is context only.
    if flops:
        detail.update(_mfu_detail(flops, vec_rps, n_chips))

    detail["aggregation_exchange"] = _aggregation_exchange(model)
    return {
        "metric": "fedavg_rounds_per_sec",
        "value": round(vec_rps, 4),
        "unit": f"rounds/s ({n_clients} clients x {epochs} epochs, CNN/FEMNIST-shape)",
        "vs_baseline": round(vec_rps / seq_rps, 2),
        "detail": detail,
    }


def run_bf16(on_cpu: bool) -> dict:
    """Mixed-precision phase: same cohort as the headline but with
    dtype=bfloat16 (bf16 matmuls, f32 master weights). The speedup over
    the f32 headline is the MXU's bf16 advantage net of the cast
    overhead; the parent stitches it into detail.bf16."""
    cohort = _headline_cohort(on_cpu)
    args, dataset, _model, api = _build_api(
        cohort["n_clients"], cohort["epochs"],
        per_client=cohort["per_client"], dtype="bfloat16",
    )
    _progress("bf16 built")
    rps, spr, _, _ = _time_rounds(api, dataset, args, cohort["n_rounds"])
    _progress(f"bf16 timed: {rps:.3f} rounds/s")
    return {
        "rounds_per_sec": round(rps, 4),
        "samples_per_sec": round(rps * spr, 1),
    }


def run_dense(on_cpu: bool) -> dict:
    """Compute-dense phase: the BASELINE.json north-star cohort —
    100-client FedAvg, ResNet-18(GN)/CIFAR-10-shape, 10 clients/round,
    bf16 — big enough that samples/s/chip and MFU are meaningful
    (the tiny-CNN headline cannot demonstrate MFU).
    """
    if on_cpu:
        # vmapped conv gradients take XLA:CPU's slow path (a ResNet
        # cohort round takes minutes) — the --cpu smoke child exercises
        # the phase plumbing with the small CNN instead
        cohort = dict(total=4, per_round=2, per_client=64, batch=16, n_rounds=1)
        model_name = "cnn"
    else:
        cohort = dict(
            total=100, per_round=10, per_client=500, batch=64, n_rounds=3
        )
        model_name = "resnet18"
    args, dataset, _model, api = _build_api(
        cohort["total"],
        epochs=1,
        per_client=cohort["per_client"],
        dataset="cifar10",
        model=model_name,
        batch_size=cohort["batch"],
        client_num_per_round=cohort["per_round"],
        dtype="bfloat16",
    )
    _progress(f"dense ({model_name}/cifar10) built")
    rps, spr, flops, mem = _time_rounds(api, dataset, args, cohort["n_rounds"])
    _progress(f"dense timed: {rps:.3f} rounds/s")
    out = {
        "model": "resnet18_gn" if not on_cpu else "cnn (--cpu smoke stand-in)",
        "dataset_shape": "cifar10 (32x32x3, 10 classes)",
        "clients_total": cohort["total"],
        "clients_per_round": cohort["per_round"],
        "batch_size": cohort["batch"],
        "dtype": "bfloat16",
        "rounds_per_sec": round(rps, 4),
        "samples_per_sec_per_chip": round(rps * spr, 1),
    }
    if flops:
        out.update(_mfu_detail(flops, rps))
    if mem:
        out["xla_memory_analysis"] = mem
    try:
        # HBM headroom tells the optimization story where to go next:
        # plenty free -> grow batch/cohort toward MXU saturation;
        # near the ceiling -> remat / smaller per-round state
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        if "bytes_in_use" in stats:
            out["hbm_used_gb"] = round(stats["bytes_in_use"] / 1e9, 2)
        if "bytes_limit" in stats:
            out["hbm_limit_gb"] = round(stats["bytes_limit"] / 1e9, 2)
    except Exception:  # noqa: BLE001 — telemetry only, never fail the phase
        pass
    return out


def run_longctx(on_cpu: bool) -> dict:
    """Long-context kernel phase: the pallas flash-attention kernel
    (ops/flash_attention.py — blockwise online-softmax, custom_vjp
    blockwise backward) vs naive XLA attention (materializes the [T, T]
    score matrix), fwd+bwd, bf16 on TPU. Reports tokens/s each way and
    the score-matrix HBM traffic the kernel never pays. Under ``--cpu``
    the kernel runs in the Pallas interpreter, so shapes are tiny — the
    phase exists to be measured on the TPU.

    A flash failure fails the phase. Only the naive side may fail and
    be survived, and only by running out of memory: its ~2.1 GB f32
    score tensors (B4/H8/T4096, plus backward) run near the 16 GB v5e
    HBM ceiling, so a naive OOM is recorded as ``naive_oom`` beside
    the flash numbers."""
    import functools

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.flash_attention import flash_attention

    if on_cpu:
        B, H, T, D, iters = 1, 2, 256, 32, 2
    else:
        B, H, T, D, iters = 4, 8, 4096, 64, 10
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, H, D), dtype)
    v = jax.random.normal(ks[2], (B, T, H, D), dtype)

    def naive(q, k, v):
        # [B, T, H, D] -> [B, H, T, T] scores, causal-masked softmax
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = s / (D ** 0.5)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def timed(attn) -> float:
        def loss(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()

        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(q, k, v)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    def record(name: str, dt: float) -> None:
        out[f"{name}_ms"] = round(dt * 1e3, 2)
        out[f"{name}_tokens_per_sec"] = round(B * T / dt, 1)
        _progress(f"longctx {name}: {dt*1e3:.1f} ms/step")

    out = {"shape": f"B{B} H{H} T{T} D{D}", "dtype": str(dtype.__name__)}
    record("flash", timed(functools.partial(flash_attention, causal=True)))
    try:
        record("naive", timed(naive))
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        out["naive_oom"] = str(e)[:300]
        _progress("longctx naive: out of memory (recorded)")
    else:
        out["flash_speedup_vs_naive"] = round(
            out["naive_ms"] / max(out["flash_ms"], 1e-9), 2
        )
    # the [B, H, T, T] f32 score matrix naive writes+reads to HBM and
    # flash never materializes (forward; backward recomputes blockwise)
    out["score_matrix_mb_avoided"] = round(B * H * T * T * 4 / 1e6, 1)
    return out


def run_mesh(on_cpu: bool) -> dict:
    """Mesh-simulator phase: the headline cohort run through
    SimulatorMesh with the client axis over every visible device. On
    one chip this measures the mesh path's overhead vs the plain-vmap
    engine — the single-chip baseline the multi-chip scaling story
    extrapolates from (the parent stitches ``vs_vmap_engine`` against
    the headline). Under ``--cpu`` a 2-device virtual mesh exercises
    real sharding (more devices drown a small box in collective
    emulation)."""
    import jax

    if on_cpu:
        # emulating a device mesh on ONE physical core is ~90s/round at
        # headline size (8 virtual devices of collective emulation +
        # thread oversubscription) — exercise the phase with a 2-device
        # mesh and a mini cohort
        cohort = dict(n_clients=4, epochs=1, n_rounds=1, per_client=50)
    else:
        cohort = _headline_cohort(on_cpu)
    args, dataset, _model, api = _build_api(
        cohort["n_clients"], cohort["epochs"],
        per_client=cohort["per_client"], mesh=True,
    )
    _progress("mesh built")
    rps, spr, _, _ = _time_rounds(api, dataset, args, cohort["n_rounds"])
    _progress(f"mesh timed: {rps:.3f} rounds/s")
    out = {
        "mesh_shape": {"clients": len(jax.devices())},
        "rounds_per_sec": round(rps, 4),
        "samples_per_sec": round(rps * spr, 1),
    }
    return out


def _pipeline_cohort(on_cpu: bool, smoke: bool):
    """(n_rounds, cohort) shared by run_pipeline and run_telemetry —
    both phases MUST measure the same cohorts or the telemetry-overhead
    figure compares different work.

    smoke: LR/MNIST-shape, the CI gate needs seconds, not a CNN
    compile. on_cpu: small LR cohort — a CNN cohort x many rounds blows
    past the phase window on a 1-core box."""
    if smoke:
        return 6, dict(
            n_clients=4, epochs=1, per_client=50,
            dataset="mnist", model="lr",
        )
    if on_cpu:
        return 12, dict(
            n_clients=8, epochs=1, per_client=100,
            dataset="mnist", model="lr",
        )
    return 30, dict(n_clients=32, epochs=1, per_client=200)


def _build_pipeline_api(n_rounds: int, cohort: dict, **overrides):
    """Build + warm up the pipelined-cohort api (compiles round/eval
    fns outside the clock) and set ``comm_round`` for the timed runs;
    ONE api per phase so every timed ``train()`` reuses the jits — on a
    TPU window that is one compile cycle, not one per run."""
    extra = {k: v for k, v in cohort.items()
             if k not in ("n_clients", "epochs", "per_client")}
    extra.update(overrides)
    args, _dataset, _model, api = _build_api(
        cohort["n_clients"],
        cohort["epochs"],
        per_client=cohort["per_client"],
        comm_round=1,
        frequency_of_the_test=max(2, n_rounds // 3),
        **extra,
    )
    api.train()  # warmup
    args.comm_round = n_rounds
    return args, api


def run_pipeline(on_cpu: bool, smoke: bool = False) -> dict:
    """Round-pipeline phase: the async K-rounds-in-flight executor
    (core/round_pipeline.py) driven end-to-end through ``train()`` at
    K ∈ {1,2,4} on one cohort. Reports rounds/s per depth plus the
    executor's own host-syncs-per-round figure — the zero-sync hot-loop
    claim as a measured number, and the K=4 ≥ K=1 check as a ratio.

    ``smoke`` (CI gate): K=2 only, 6 rounds — exercises the pipeline
    plumbing in seconds; no cross-K comparison."""
    import jax

    n_rounds, cohort = _pipeline_cohort(on_cpu, smoke)
    ks = (2,) if smoke else _PIPELINE_KS
    out = {
        "cohort_clients": cohort["n_clients"],
        "rounds_timed": n_rounds,
        "device": str(jax.devices()[0]),
    }
    args, api = _build_pipeline_api(n_rounds, cohort)
    for k in ks:
        args.pipeline_depth = k
        t0 = time.perf_counter()
        api.train()
        dt = time.perf_counter() - t0
        out[f"k{k}"] = {
            "rounds_per_sec": round(n_rounds / dt, 4),
            "host_syncs_per_round": api.pipeline_stats.get(
                "host_syncs_per_round"
            ),
            "compile_bucket": api.pipeline_stats.get("bucket"),
        }
        _progress(f"pipeline k={k}: {n_rounds / dt:.3f} rounds/s")
    if "k4" in out and "k1" in out:
        out["speedup_k4_vs_k1"] = round(
            out["k4"]["rounds_per_sec"]
            / max(out["k1"]["rounds_per_sec"], 1e-9),
            3,
        )
    return out


def run_telemetry(on_cpu: bool, smoke: bool = False) -> dict:
    """Telemetry-overhead phase: the pipelined cohort at depth 4 run
    twice through ``train()`` — flight-recorder telemetry OFF then ON
    (with trace.json export) — on the SAME jitted fns. Reports rounds/s
    each way, the overhead percentage, and whether
    ``host_syncs_per_round`` is bit-identical (the telemetry contract:
    instruments are host-side only and never add a device fetch).

    ``smoke`` (CI gate): 6 rounds on the LR/MNIST mini cohort."""
    import tempfile

    import jax

    from fedml_tpu.core.telemetry import Telemetry

    n_rounds, cohort = _pipeline_cohort(on_cpu, smoke)
    args, api = _build_pipeline_api(n_rounds, cohort, pipeline_depth=4)
    tdir = tempfile.mkdtemp(prefix="bench_telemetry_")
    out = {
        "cohort_clients": cohort["n_clients"],
        "rounds_timed": n_rounds,
        "pipeline_depth": 4,
        "device": str(jax.devices()[0]),
    }
    try:
        for mode in ("off", "on"):
            Telemetry.reset()
            api.telemetry = Telemetry.get_instance(args)
            api.telemetry.enabled = mode == "on"
            api.telemetry.attach_profiler(api.profiler)
            # telemetry_dir stays unset during the clock: the timed
            # window measures the INSTRUMENT overhead (the <2% claim),
            # not the one-time trace/prom export I/O at run end
            t0 = time.perf_counter()
            api.train()
            dt = time.perf_counter() - t0
            out[mode] = {
                "rounds_per_sec": round(n_rounds / dt, 4),
                "host_syncs_per_round": api.pipeline_stats.get(
                    "host_syncs_per_round"
                ),
            }
            _progress(f"telemetry {mode}: {n_rounds / dt:.3f} rounds/s")
        api.telemetry.export_run_artifacts(tdir)  # outside the clock
        trace = os.path.join(tdir, "trace.json")
        if os.path.exists(trace):
            with open(trace) as fh:
                out["trace_events"] = len(json.load(fh).get("traceEvents", []))
    finally:
        import shutil

        shutil.rmtree(tdir, ignore_errors=True)
    out["overhead_pct"] = round(
        (out["off"]["rounds_per_sec"] - out["on"]["rounds_per_sec"])
        / max(out["off"]["rounds_per_sec"], 1e-9) * 100,
        2,
    )
    out["host_syncs_match"] = (
        out["on"]["host_syncs_per_round"] == out["off"]["host_syncs_per_round"]
    )
    return out


def run_serving(on_cpu: bool, smoke: bool = False) -> dict:
    """Serving-plane phase (fedml_tpu/serving): the continuous
    micro-batching engine driven at two deterministic burst sizes
    (pause/submit/resume turns each burst into exactly one micro-batch)
    so TWO pow2 buckets are exercised. Reports p50/p99 request latency
    and req/s per bucket, plus the zero-recompile evidence: per-bucket
    jit trace counts (must be exactly 1 each) held across >= 2 weight
    hot-swaps mid-run, and a forced queue-full shed counted by
    ``serving_shed_total`` instead of queue growth.

    ``smoke`` (CI gate): fewer iterations on the same tiny LR model —
    the contract keys in seconds."""
    import numpy as np
    import jax

    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.serving import ModelEndpoint, ServingEngine

    Telemetry.reset()
    args = Arguments()
    args.dataset = "synthetic"
    args.input_dim = 64
    args.model = "lr" if (on_cpu or smoke) else "mlp"
    args.serve_deadline_ms = 0.0  # measuring latency, not shedding
    args.serve_max_batch = 64
    args._validate()
    model = models.create(args, 10)
    params = model.init(jax.random.PRNGKey(0))
    endpoint = ModelEndpoint(model, params)
    engine = ServingEngine(endpoint, args).start()
    tel = Telemetry.get_instance(args)

    iters = 4 if smoke else 30
    bursts = (3, 12)  # -> buckets 4 and 16
    rs = np.random.RandomState(0)
    out = {
        "model": model.name,
        "device": str(jax.devices()[0]),
        "iters_per_bucket": iters,
        "buckets": {},
    }
    swaps_done = 0
    burst_inputs = []  # one request set per measured bucket
    try:
        for phase_i, burst in enumerate(bursts):
            lats, t_first = [], None
            xs = [
                rs.randn(*model.example_shape).astype(np.float32)
                for _ in range(burst)
            ]
            burst_inputs.append(xs)
            for it in range(iters):
                engine.pause()
                futs = [engine.submit(x) for x in xs]
                engine.resume()
                t0 = time.perf_counter()
                if t_first is None:
                    t_first = t0
                for f in futs:
                    f.result(timeout=120)
                done = time.perf_counter()
                if it == 0:
                    # warmup iteration compiles the bucket; keep it out
                    # of the latency stats but in the trace counts
                    t_first = done
                    continue
                lats.extend([done - t0] * burst)
            wall = max(time.perf_counter() - t_first, 1e-9)
            from fedml_tpu.core.bucketing import bucket_cohort

            b = bucket_cohort(burst, max_size=args.serve_max_batch)
            out["buckets"][str(b)] = {
                "burst": burst,
                "requests": (iters - 1) * burst,
                "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
                "req_per_sec": round((iters - 1) * burst / wall, 1),
                "jit_traces": endpoint.trace_counts.get(b, 0),
            }
            _progress(
                f"serving bucket {b}: p50 "
                f"{out['buckets'][str(b)]['p50_ms']} ms"
            )
            # >= 2 hot swaps (one after each bucket phase), then every
            # measured bucket is re-served below: trace counts must
            # not move for ANY of them
            endpoint.swap(model.init(jax.random.PRNGKey(phase_i + 1)))
            swaps_done += 1
        for xs in burst_inputs:
            engine.pause()
            futs = [engine.submit(x) for x in xs]
            engine.resume()
            for f in futs:
                f.result(timeout=120)

        # forced overload: a paused engine with a tiny queue must shed,
        # not grow — the bounded-queue contract as a measured number
        args_shed = Arguments()
        args_shed.dataset = "synthetic"
        args_shed.input_dim = 64
        args_shed.model = args.model
        args_shed.serve_queue_size = 4
        args_shed._validate()
        shed_engine = ServingEngine(
            ModelEndpoint(model, params), args_shed
        ).start()
        shed_engine.pause()
        shed_futs = [
            shed_engine.submit(np.zeros(model.example_shape, np.float32))
            for _ in range(8)
        ]
        shed_engine.resume()
        for f in shed_futs:
            try:
                f.result(timeout=60)
            except Exception:  # noqa: BLE001 — the shed half fails by design
                pass
        shed_engine.stop()
    finally:
        engine.stop()

    out["swaps"] = swaps_done
    out["trace_counts"] = {str(k): v for k, v in endpoint.trace_counts.items()}
    out["one_trace_per_bucket"] = all(
        v == 1 for v in endpoint.trace_counts.values()
    ) and len(endpoint.trace_counts) >= 2
    out["shed_queue_full"] = tel.get_counter(
        "serving_shed_total", reason="queue_full"
    )
    out["mesh"] = _serving_mesh_variant(model, params, args, smoke)
    out["fleet"] = _serving_fleet_variant(model, params, args, smoke, tel)
    return out


def _serving_mesh_variant(model, params, args, smoke: bool) -> dict:
    """Mesh-endpoint half of detail.serving: the SAME deterministic
    request set served through ``MeshModelEndpoint`` at two (data,
    fsdp) mesh shapes — (1,1) and (2,2) device-prefix submeshes —
    across 2 mid-run hot swaps each. The gate: responses **bitwise
    identical** across shapes for every published version (the serving
    half of the multichip identity), exactly one jit trace per bucket
    (swaps never retrace, swap counter == 2), req/s + p99 per shape.
    With < 4 visible devices the (2,2) shape records a skip reason
    instead of silently shrinking coverage."""
    import numpy as np
    import jax

    from fedml_tpu.parallel.layout import build_fed_mesh
    from fedml_tpu.serving import MeshModelEndpoint, ServingEngine

    n_dev = len(jax.devices())
    shapes = [(1, 1), (2, 2)]
    rs = np.random.RandomState(7)
    bursts = (3, 12)  # -> buckets 4 and 16, both tile 1 and 2 lanes
    iters = 2 if smoke else 6
    fixed = [
        [rs.randn(*model.example_shape).astype(np.float32) for _ in range(b)]
        for b in bursts
    ]
    # 2 deterministic publishes, identical for every shape
    published = [
        jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(101 + i)))
        for i in range(2)
    ]
    mesh_out: dict = {"shapes": {}, "skipped": {}}
    responses: dict = {}
    for d, f in shapes:
        key = f"{d}x{f}"
        if d * f > n_dev:
            mesh_out["skipped"][key] = (
                f"needs {d * f} devices, have {n_dev}"
            )
            continue
        mesh = build_fed_mesh(
            mesh_shape={"data": d, "fsdp": f}, warn_nonpartitionable=False
        )
        ep = MeshModelEndpoint(model, params, mesh)
        eng = ServingEngine(ep, args).start()
        lats: list = []
        resp: list = []
        served = 0
        t_start = None
        try:
            def serve_fixed(measure: bool) -> None:
                nonlocal served, t_start
                for xs in fixed:
                    for _ in range(iters):
                        eng.pause()
                        futs = [eng.submit(x) for x in xs]
                        eng.resume()
                        t0 = time.perf_counter()
                        rows = [
                            np.asarray(fu.result(timeout=120)) for fu in futs
                        ]
                        dt = time.perf_counter() - t0
                        if measure:
                            if t_start is None:
                                t_start = t0
                            lats.extend([dt] * len(xs))
                            served += len(xs)
                    resp.append(np.stack(rows))

            # warmup pass compiles both buckets, then the measured run
            serve_fixed(measure=False)
            serve_fixed(measure=True)
            for step, pub in enumerate(published):
                ep.swap(pub, version=step + 1)
                serve_fixed(measure=True)
        finally:
            eng.stop()
        wall = max(time.perf_counter() - (t_start or 0.0), 1e-9)
        responses[key] = np.concatenate([r.ravel() for r in resp])
        mesh_out["shapes"][key] = {
            "devices": d * f,
            "requests": served,
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
            "req_per_sec": round(served / wall, 1),
            "swaps": ep.swaps,
            "jit_traces": {str(k): v for k, v in ep.trace_counts.items()},
            "one_trace_per_bucket": all(
                v == 1 for v in ep.trace_counts.values()
            ) and len(ep.trace_counts) >= 2,
        }
        _progress(
            f"serving mesh {key}: p99 "
            f"{mesh_out['shapes'][key]['p99_ms']} ms, "
            f"swaps {ep.swaps}"
        )
    if len(responses) >= 2:
        keys = sorted(responses)
        base = responses[keys[0]]
        diff = max(
            float(np.max(np.abs(responses[k] - base))) for k in keys[1:]
        )
        mesh_out["max_abs_diff_across_shapes"] = diff
        mesh_out["bitwise_identical_across_shapes"] = all(
            np.array_equal(responses[k], base) for k in keys[1:]
        )
    else:
        # one shape is no identity check — loud, never silent
        mesh_out["bitwise_identical_across_shapes"] = None
    return mesh_out


def _serving_fleet_variant(model, params, args, smoke: bool, tel) -> dict:
    """Fleet half of detail.serving: 2 endpoints behind the load-aware
    frontend seam. A paused-fleet burst measures queue depth, routed
    request counts prove <= 2x load skew, a mid-run fleet-wide hot swap
    rides along, and the occupancy histogram summarizes batching."""
    import numpy as np
    import jax

    from fedml_tpu.arguments import Arguments
    from fedml_tpu.serving import ServingFleet

    fa = Arguments()
    fa.dataset = "synthetic"
    fa.input_dim = args.input_dim
    fa.model = args.model
    fa.serve_deadline_ms = 0.0
    fa.serve_fleet_size = 2
    fa._validate()
    rs = np.random.RandomState(11)
    n_req = 24 if smoke else 96
    xs = [
        rs.randn(*model.example_shape).astype(np.float32)
        for _ in range(n_req)
    ]
    fleet = ServingFleet.build(model, params, fa).start()
    try:
        # warmup both endpoints' buckets
        for fu in fleet.submit_burst(xs[: 2 * len(fleet.engines)]):
            fu.result(timeout=120)
        for e in fleet.engines:
            e.pause()
        t0 = time.perf_counter()
        futs = [fleet.submit(x) for x in xs]
        depth_max = max(fleet.depths())
        for e in fleet.engines:
            e.resume()
        lats = []
        for fu in futs:
            fu.result(timeout=120)
            lats.append(time.perf_counter() - t0)
        wall = max(time.perf_counter() - t0, 1e-9)
        # fleet-wide hot swap mid-run, then one more routed burst
        fleet.hot_swap(
            jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(103)))
        )
        for fu in [fleet.submit(x) for x in xs[: len(xs) // 2]]:
            fu.result(timeout=120)
    finally:
        fleet.stop()
    snap = tel.snapshot()
    occ = None
    for k, h in snap.get("histograms", {}).items():
        if k.startswith("serving_batch_occupancy_frac") and h.get("count"):
            occ = round(float(h["sum"]) / float(h["count"]), 3)
    return {
        "endpoints": len(fleet.engines),
        "routed": list(fleet.routed),
        "load_skew": (
            None if fleet.load_skew() == float("inf") else
            round(fleet.load_skew(), 3)
        ),
        "depth_max": depth_max,
        "occupancy_frac": occ,
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
        "req_per_sec": round(n_req / wall, 1),
        "failovers": tel.get_counter("serving_fleet_failover_total"),
        "sheds": sum(
            v for k, v in tel.counters_matching(
                "serving_fleet_shed_total"
            ).items()
        ),
        "swaps": fleet.engines[0].endpoint.swaps,
    }


def run_chaos(on_cpu: bool, smoke: bool = False) -> dict:
    """Chaos phase (docs/robustness.md): a LOCAL cross-silo world under
    combined drop/dup/delay faults with the full fault-tolerance layer
    on (``reliable_comm`` + heartbeats + round WAL), plus one mid-run
    client kill (replaced — the server RESYNCs the replacement into the
    pending round) and one server crash + restart (resumes from its
    checkpoint/WAL). Asserts the run completes, every client upload is
    aggregated EXACTLY once per round (telemetry counters), and the
    final params are bit-identical to a fault-free run of the same
    seed — the cohort is preserved through both failures, so identity
    must hold.

    ``smoke`` (CI gate): 3 clients x 4 rounds on the LR mini cohort —
    the same kill + restart choreography in seconds."""
    import tempfile as _tempfile
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import constants as C
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.data import load

    n_clients = 3 if (smoke or on_cpu) else 4
    rounds = 4 if (smoke or on_cpu) else 6
    train_size = 240 if smoke else 400
    chaos_kw = dict(
        reliable_comm=True,
        comm_retry_max=8,
        comm_retry_base_s=0.05,
        heartbeat_interval_s=0.1,
        # generous: deaths in this phase are healed by restarts, not
        # declared (declaration is covered by tests/test_robustness.py)
        heartbeat_timeout_s=60.0,
        checkpoint_freq=1,
        fault_injection={
            "drop_prob": 0.3,
            "duplicate_prob": 0.2,
            "delay_s": 0.05,
            "delay_prob": 0.1,
        },
    )

    def mk(rank, run_id, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = train_size
        a.synthetic_test_size = 60
        a.model = "lr"
        a.partition_method = "hetero"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 1
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def build_world(run_id, **kw):
        a0, ds0, m0 = mk(0, run_id, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, n_clients + 1):
            a, ds, m = mk(r, run_id, **kw)
            clients.append(Client(a, None, ds, m))
        return server, clients

    def join_all(threads, note):
        for t in threads:
            t.join(timeout=120)
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"{note}: threads hung: {hung}")

    # -- fault-free reference run -------------------------------------
    Telemetry.reset()
    server, clients = build_world("bench_chaos_clean")
    threads = [
        threading.Thread(target=c.run, daemon=True, name=f"clean-c{i}")
        for i, c in enumerate(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    server.run()
    join_all(threads, "clean world")
    clean_dt = time.perf_counter() - t0
    clean_params = jax.tree.map(
        np.asarray, server.aggregator.get_global_model_params()
    )
    _progress(f"chaos: clean world done in {clean_dt:.1f}s")

    # -- chaos run ----------------------------------------------------
    class _ChaosKill(Exception):
        pass

    class _ChaosCrash(Exception):
        pass

    Telemetry.reset()
    ckpt_dir = _tempfile.mkdtemp(prefix="bench_chaos_ck_")
    tel_dir = _tempfile.mkdtemp(prefix="bench_chaos_td_")
    chaos_kw["checkpoint_dir"] = ckpt_dir
    chaos_kw["telemetry_dir"] = tel_dir
    server1, cclients = build_world("bench_chaos", **chaos_kw)

    # client kill: rank 2's handler dies (kill -9 analog: the exception
    # tears down its receive loop AND we stop its beat thread) instead
    # of training round 1; a replacement with the same rank reconnects
    killed = threading.Event()
    victim = cclients[1]
    orig_tas = victim.manager._train_and_send

    def kill_or_train(msg):
        if (
            int(msg.get(C.MSG_ARG_KEY_ROUND_INDEX, 0)) == 1
            and not killed.is_set()
        ):
            if victim.manager._heartbeat is not None:
                victim.manager._heartbeat.stop()
            killed.set()
            raise _ChaosKill()
        orig_tas(msg)

    victim.manager._train_and_send = kill_or_train

    # server crash: after round rounds-2 fully closes (next broadcast
    # out, checkpoint + WAL written, metrics reported) the dispatch
    # thread dies; a fresh server restores from the checkpoint dir and
    # the clients' heartbeats re-announce them to it
    crashed = threading.Event()
    mgr1 = server1.manager
    orig_report = mgr1._report_round

    def report_then_crash(eval_round, cohort, n_aggregated):
        orig_report(eval_round, cohort, n_aggregated)
        if eval_round == rounds - 2 and not crashed.is_set():
            if mgr1._failure_detector is not None:
                mgr1._failure_detector.stop()
            crashed.set()
            raise _ChaosCrash()

    mgr1._report_round = report_then_crash

    def client_thread(c):
        try:
            c.run()
        except _ChaosKill:
            pass

    cthreads = [
        threading.Thread(
            target=client_thread, args=(c,), daemon=True, name=f"chaos-c{i}"
        )
        for i, c in enumerate(cclients)
    ]
    t0 = time.perf_counter()
    for t in cthreads:
        t.start()

    def server_thread():
        try:
            server1.run()
        except _ChaosCrash:
            pass

    st = threading.Thread(target=server_thread, daemon=True, name="chaos-srv1")
    st.start()

    if not killed.wait(timeout=180):
        raise RuntimeError("chaos: client kill never triggered")
    a, ds, m = mk(2, "bench_chaos", **chaos_kw)
    replacement = Client(a, None, ds, m)
    rthread = threading.Thread(
        target=replacement.run, daemon=True, name="chaos-c-replacement"
    )
    rthread.start()
    _progress("chaos: client killed and replacement started")

    if not crashed.wait(timeout=180):
        raise RuntimeError("chaos: server crash never triggered")
    st.join(timeout=120)
    _progress("chaos: server crashed; restarting from checkpoint")
    a0b, ds0b, m0b = mk(0, "bench_chaos", **chaos_kw)
    server2 = Server(a0b, None, ds0b, m0b)
    resumed_at = server2.manager.round_idx
    server2.run()
    join_all(cthreads + [rthread], "chaos world")
    chaos_dt = time.perf_counter() - t0

    tel = Telemetry.get_instance()

    def total(counter):
        return sum(tel.counters_matching(counter).values())

    aggregated = total("cross_silo_clients_aggregated_total")
    expected = rounds * n_clients
    diff = max(
        jax.tree.leaves(
            jax.tree.map(
                lambda x, y: float(np.max(np.abs(np.asarray(x) - y))),
                server2.aggregator.get_global_model_params(),
                clean_params,
            )
        )
    )
    out = {
        "device": str(jax.devices()[0]),
        "clients": n_clients,
        "rounds": rounds,
        "clean_rounds_per_sec": round(rounds / clean_dt, 4),
        "chaos_rounds_per_sec": round(rounds / chaos_dt, 4),
        "slowdown_vs_clean": round(chaos_dt / max(clean_dt, 1e-9), 3),
        "faults_injected": total("comm_faults_injected_total"),
        "retries_total": total("comm_retries_total"),
        "dup_dropped_total": total("comm_dup_dropped_total"),
        "giveups_total": total("comm_giveups_total"),
        "resyncs_total": total("cross_silo_resyncs_total"),
        "client_killed": killed.is_set(),
        "server_restarted": crashed.is_set(),
        "server_resumed_at_round": resumed_at,
        "rounds_completed": server2.manager.round_idx,
        "wal_records": len(server2.manager._wal.records()),
        "uploads_aggregated": aggregated,
        "expected_uploads": expected,
        "exactly_once": aggregated == expected,
        "max_abs_diff_vs_clean": diff,
        "params_match_clean": diff == 0.0,
        # post-hoc invariant replay over the world's artifacts (WAL +
        # telemetry + trace) — the reusable checker, not hand asserts
        **_check_invariants(tel_dir, ckpt_dir),
    }
    _progress(
        f"chaos: {out['rounds_completed']}/{rounds} rounds, "
        f"{aggregated:.0f}/{expected} uploads aggregated, "
        f"max_abs_diff {diff:g}"
    )
    return out


def run_straggler(on_cpu: bool, smoke: bool = False) -> dict:
    """Straggler phase (docs/robustness.md "round-barrier failure
    model"): four LOCAL cross-silo worlds proving the streaming
    aggregate-on-arrival tentpole —

    1. **buffered baseline** (``agg_mode=buffered``): clean run; peak
       buffered uploads == cohort (the O(cohort x model) shape).
    2. **sync streaming** (``agg_mode=stream``): same seed; final
       params must be BIT-IDENTICAL to the baseline even though folds
       happen in nondeterministic arrival order, and peak buffered
       uploads is 0 — server aggregation memory is O(model).
    3. **quorum mode**: one client 10x-delayed past the grace window
       and one killed without OFFLINE (kill -9 analog, heartbeat
       detector on): every round closes on the quorum, the corpse
       leaves the quorum denominator, late uploads discard by round
       tag, and round wall tracks quorum arrival — bounded well below
       the blocked-on-straggler wall.
    4. **async mode** (``agg_mode=async``): drop+dup+delay faults with
       the reliable channel, the same 10x straggler and client kill,
       plus one server crash right after a publish and a restart that
       reseeds the fold ledger from the WAL. Every accepted update
       folds EXACTLY once across both incarnations (telemetry counters
       == the WAL's (rank, seq) ledger, pairwise distinct) and every
       fold's staleness weight matches the unit oracle.

    ``smoke`` (CI gate): 4 clients x 3 rounds on the LR mini cohort."""
    import tempfile as _tempfile
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.aggregation import staleness_weight
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.data import load

    n_clients = 4
    rounds = 3 if (smoke or on_cpu) else 5
    train_size = 240 if smoke else 400
    delay_s = 6.0 if smoke else 10.0  # ~10x a typical mini round

    def mk(rank, run_id, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = train_size
        a.synthetic_test_size = 60
        a.model = "lr"
        a.partition_method = "hetero"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 1
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def build_world(run_id, **kw):
        a0, ds0, m0 = mk(0, run_id, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, n_clients + 1):
            a, ds, m = mk(r, run_id, **kw)
            clients.append(Client(a, None, ds, m))
        return server, clients

    def run_clean(run_id, **kw):
        Telemetry.reset()
        server, clients = build_world(run_id, **kw)
        threads = [
            threading.Thread(target=c.run, daemon=True, name=f"{run_id}-c{i}")
            for i, c in enumerate(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        server.run()
        dt = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=120)
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"{run_id}: threads hung: {hung}")
        return server, dt

    def max_diff(a, b):
        return max(
            jax.tree.leaves(
                jax.tree.map(
                    lambda x, y: float(
                        np.max(np.abs(np.asarray(x) - np.asarray(y)))
                    ),
                    a, b,
                )
            )
        )

    out = {"device": str(jax.devices()[0]), "clients": n_clients,
           "rounds": rounds, "straggler_delay_s": delay_s}

    # -- 1+2: buffered baseline vs sync streaming (bit-identity) ------
    buffered, buf_dt = run_clean("bench_strag_buf", agg_mode="buffered")
    _progress(f"straggler: buffered baseline done in {buf_dt:.1f}s")
    streamed, str_dt = run_clean("bench_strag_str", agg_mode="stream")
    _progress(f"straggler: streaming world done in {str_dt:.1f}s")
    diff = max_diff(
        buffered.aggregator.get_global_model_params(),
        streamed.aggregator.get_global_model_params(),
    )
    out["max_abs_diff_stream_vs_buffered"] = diff
    out["stream_identical_to_buffered"] = diff == 0.0
    out["buffered_peak_buffered"] = buffered.aggregator.peak_buffered
    out["stream_peak_buffered"] = streamed.aggregator.peak_buffered

    # -- 3: quorum close with a 10x straggler + a kill ----------------
    class _StragKill(Exception):
        pass

    Telemetry.reset()
    q_ck = _tempfile.mkdtemp(prefix="bench_strag_qck_")
    q_td = _tempfile.mkdtemp(prefix="bench_strag_qtd_")
    qserver, qclients = build_world(
        "bench_strag_quorum",
        agg_mode="stream",
        round_quorum_frac=0.5,
        round_grace_s=1.0,
        heartbeat_interval_s=0.1,
        heartbeat_timeout_s=1.5,
        # the WAL (created with the dir) is all the invariant checker
        # needs; this world is TIMING-gated (quorum_wall vs the
        # blocked bound), so a per-round orbax save must not inflate
        # the wall the gate measures
        checkpoint_dir=q_ck,
        checkpoint_freq=10_000,
        telemetry_dir=q_td,
    )
    drain = threading.Event()  # post-run: stop sleeping, drain fast
    slow_trainer = qclients[2].trainer
    orig_train = slow_trainer.train

    def slow_train(params, round_idx):
        drain.wait(delay_s)
        return orig_train(params, round_idx)

    slow_trainer.train = slow_train

    victim = qclients[1]

    def kill(msg):
        if victim.manager._heartbeat is not None:
            victim.manager._heartbeat.stop()
        raise _StragKill()

    victim.manager._train_and_send = kill

    def qclient_thread(c):
        try:
            c.run()
        except _StragKill:
            pass

    qthreads = [
        threading.Thread(
            target=qclient_thread, args=(c,), daemon=True, name=f"strag-q{i}"
        )
        for i, c in enumerate(qclients)
    ]
    t0 = time.perf_counter()
    for t in qthreads:
        t.start()
    qserver.run()
    quorum_wall = time.perf_counter() - t0
    drain.set()
    for t in qthreads:
        t.join(timeout=120)
    hung = [t.name for t in qthreads if t.is_alive()]
    if hung:
        raise RuntimeError(f"straggler quorum world: threads hung: {hung}")
    qtel = Telemetry.get_instance()

    def qtotal(counter):
        return sum(qtel.counters_matching(counter).values())

    blocked_bound = rounds * delay_s  # a barrier would wait this long
    out["quorum"] = {
        "rounds_completed": qserver.manager.round_idx,
        "quorum_closes": qserver.manager.quorum_closes,
        "stragglers_dropped": qserver.manager.stragglers_dropped,
        "client_killed": True,
        "deaths": qserver.manager.deaths,
        "late_uploads_discarded": qtotal("agg_late_uploads_total"),
        "wall_s": round(quorum_wall, 2),
        "blocked_wall_bound_s": blocked_bound,
        "tracks_quorum_not_straggler": quorum_wall < 0.75 * blocked_bound,
        "peak_buffered": qserver.aggregator.peak_buffered,
        # the checker must account every partial close to the quorum /
        # death counters from artifacts alone
        **_check_invariants(q_td, q_ck),
    }
    _progress(
        f"straggler: quorum world {quorum_wall:.1f}s vs blocked bound "
        f"{blocked_bound:.0f}s ({qserver.manager.quorum_closes} quorum closes)"
    )

    # -- 4: async exactly-once under faults + kill + restart ----------
    class _StragCrash(Exception):
        pass

    Telemetry.reset()
    ckpt_dir = _tempfile.mkdtemp(prefix="bench_strag_ck_")
    async_td = _tempfile.mkdtemp(prefix="bench_strag_atd_")
    async_kw = dict(
        agg_mode="async",
        telemetry_dir=async_td,
        async_publish_every=2,
        staleness_decay=0.5,
        staleness_max=64,
        reliable_comm=True,
        comm_retry_max=8,
        comm_retry_base_s=0.05,
        heartbeat_interval_s=0.1,
        heartbeat_timeout_s=60.0,
        checkpoint_dir=ckpt_dir,
        checkpoint_freq=1,
        fault_injection={
            "drop_prob": 0.2,
            "duplicate_prob": 0.2,
            "delay_s": 0.05,
            "delay_prob": 0.1,
        },
    )
    aserver1, aclients = build_world("bench_strag_async", **async_kw)

    adrain = threading.Event()
    aslow = aclients[2].trainer
    aorig = aslow.train

    def aslow_train(params, round_idx):
        adrain.wait(delay_s / 2.0)
        return aorig(params, round_idx)

    aslow.train = aslow_train

    avictim = aclients[1]
    akills = {"n": 0}
    aorig_tas = avictim.manager._train_and_send

    def akill_or_train(msg):
        akills["n"] += 1
        if akills["n"] >= 2:
            if avictim.manager._heartbeat is not None:
                avictim.manager._heartbeat.stop()
            raise _StragKill()
        aorig_tas(msg)

    avictim.manager._train_and_send = akill_or_train

    crashed = threading.Event()
    amgr1 = aserver1.manager
    orig_publish = amgr1._async_publish

    def publish_then_crash():
        orig_publish()
        if amgr1.version >= 2 and not crashed.is_set():
            if amgr1._failure_detector is not None:
                amgr1._failure_detector.stop()
            crashed.set()
            raise _StragCrash()

    amgr1._async_publish = publish_then_crash

    def aclient_thread(c):
        try:
            c.run()
        except _StragKill:
            pass

    athreads = [
        threading.Thread(
            target=aclient_thread, args=(c,), daemon=True, name=f"strag-a{i}"
        )
        for i, c in enumerate(aclients)
    ]
    t0 = time.perf_counter()
    for t in athreads:
        t.start()

    def aserver_thread():
        try:
            aserver1.run()
        except _StragCrash:
            pass

    ast = threading.Thread(target=aserver_thread, daemon=True, name="strag-asrv1")
    ast.start()
    if not crashed.wait(timeout=240):
        raise RuntimeError("straggler: async server crash never triggered")
    ast.join(timeout=120)
    _progress("straggler: async server crashed after a publish; restarting")
    a0b, ds0b, m0b = mk(0, "bench_strag_async", **async_kw)
    aserver2 = Server(a0b, None, ds0b, m0b)
    amgr2 = aserver2.manager
    resumed_version = amgr2.version
    folded_before = set((e["rank"], e["seq"]) for e in amgr1.async_weight_log)
    aserver2.run()
    async_wall = time.perf_counter() - t0
    adrain.set()
    for t in athreads:
        t.join(timeout=180)
    hung = [t.name for t in athreads if t.is_alive()]
    if hung:
        raise RuntimeError(f"straggler async world: threads hung: {hung}")

    atel = Telemetry.get_instance()

    def atotal(counter):
        return sum(atel.counters_matching(counter).values())

    # exactly-once ledger: WAL publish records across BOTH incarnations
    wal_pairs = []
    for rec in amgr2._wal.records():
        if rec.get("kind") == "publish":
            wal_pairs.extend(tuple(p) for p in rec.get("folded") or [])
    folded_after = set((e["rank"], e["seq"]) for e in amgr2.async_weight_log)
    weight_oracle_ok = all(
        abs(
            e["weight"]
            - staleness_weight(
                e["sample_num"], e["staleness"], amgr2.staleness_decay
            )
        ) <= 1e-12 * max(1.0, abs(e["weight"]))
        for e in list(amgr1.async_weight_log) + list(amgr2.async_weight_log)
    )
    stale_folds = sum(
        1
        for e in list(amgr1.async_weight_log) + list(amgr2.async_weight_log)
        if e["staleness"] > 0
    )
    out["async"] = {
        "folds_total": amgr2.async_folds,
        "target_folds": amgr2._async_target_folds(),
        "publishes": amgr2.version,
        "server_restarted": crashed.is_set(),
        "resumed_at_version": resumed_version,
        "client_killed": akills["n"] >= 2,
        "wal_folded_pairs": len(wal_pairs),
        "double_folds": len(wal_pairs) - len(set(wal_pairs)),
        "refolded_across_restart": len(folded_before & folded_after),
        "folds_counter_total": atotal("agg_folds_total"),
        "exactly_once": (
            len(wal_pairs) == len(set(wal_pairs))
            and not (folded_before & folded_after)
            and atotal("agg_folds_total") == len(wal_pairs)
            and amgr2.async_folds >= amgr2._async_target_folds()
        ),
        "stale_folds": stale_folds,
        "staleness_weights_match_oracle": weight_oracle_ok,
        "superseded_discards": atotal("agg_async_superseded_total"),
        "stale_discards": atotal("agg_stale_discarded_total"),
        "dup_dropped_total": atotal("comm_dup_dropped_total"),
        "retries_total": atotal("comm_retries_total"),
        "wall_s": round(async_wall, 2),
        # the reusable checker re-derives the exactly-once /
        # monotonicity evidence from the WAL + telemetry artifacts
        **_check_invariants(async_td, ckpt_dir),
    }
    _progress(
        f"straggler: async {amgr2.async_folds}/{amgr2._async_target_folds()} "
        f"folds, {amgr2.version} publishes, "
        f"{out['async']['double_folds']} double folds"
    )
    return out


def run_defense(on_cpu: bool, smoke: bool = False) -> dict:
    """Defense phase (docs/robustness.md threat model): poisoned LOCAL
    worlds proving Byzantine robustness is first-class on the
    streaming/async path —

    1. **clip bit-identity**: two CLEAN worlds with
       ``defense_type=norm_diff_clipping`` — ``agg_mode=buffered`` vs
       ``agg_mode=stream``. Final params must be BIT-IDENTICAL (the
       clip rides the shared per-term executables) with
       ``agg_stream_fallback_total == 0`` and stream peak buffered
       uploads 0 — the defense no longer costs O(cohort·model).
    2. **clean / undefended-poisoned baselines** (``data/poison.py``:
       one label_flip + one backdoor_pattern attacker): the undefended
       poisoned world must DIVERGE from the clean run (server eval
       loss blows up, param distance grows).
    3. **defended poisoned world** under drop+dup faults with the
       reliable channel: clipping + anomaly screening quarantine the
       attacker ranks (``defense_quarantined_total{rank}``), rounds
       keep completing (a quarantined rank drops through the
       drop-expected path), the final model lands near the clean run,
       and exactly-once accounting holds (every aggregated client ==
       exactly one fold; duplicates counted, never folded twice).
    4. **async defended world** (``agg_mode=async``): the
       construction-time defense rejection is gone — staleness-aware
       clipping + screening run per fold, the attacker is quarantined,
       the fold target is reached, and the published model lands near
       the clean run.

    ``smoke`` (CI gate): same worlds at the mini scale."""
    import tempfile as _tempfile
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.data import load

    n_clients = 6
    rounds = 6
    train_size = 360 if smoke else 600
    attacker_idxs = [1, 4]  # silo idx == rank-1 (identity mapping)
    attacks = ["label_flip", "backdoor_pattern"]
    attacker_ranks = [i + 1 for i in attacker_idxs]
    poison_kw = dict(
        poison_type=attacks,
        poisoned_client_idxs=attacker_idxs,
        poison_sample_fraction=1.0,
    )
    # split deliberately: the anomaly screen's DECISIONS are
    # arrival-order dependent (docs/robustness.md), so the bit-identity
    # world pair runs clip-only — the guarantee under test is
    # "clipping in the fold", screening rides the defended worlds
    clip_kw = dict(defense_type="norm_diff_clipping", norm_bound=1.0)
    defense_kw = dict(
        defense_anomaly_threshold=0.35,
        defense_quarantine_rounds=3,
        **clip_kw,
    )

    def mk(rank, run_id, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = train_size
        a.synthetic_test_size = 120
        a.model = "lr"
        # homo: honest clients share a data distribution, so the
        # anomaly screen's consensus-direction signal is the attack,
        # not the heterogeneity (hetero worlds are exercised in tests)
        a.partition_method = "homo"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 1
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def run_world(run_id, **kw):
        Telemetry.reset()
        a0, ds0, m0 = mk(0, run_id, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, n_clients + 1):
            a, ds, m = mk(r, run_id, **kw)
            clients.append(Client(a, None, ds, m))
        threads = [
            threading.Thread(target=c.run, daemon=True, name=f"{run_id}-c{i}")
            for i, c in enumerate(clients)
        ]
        for t in threads:
            t.start()
        server.run()
        for t in threads:
            t.join(timeout=120)
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"{run_id}: threads hung: {hung}")
        # server eval on the CLEAN test split (poisoning only touches
        # attacker train shards) — the robustness headline number
        stats = server.aggregator.test_on_server_for_all_clients(rounds)
        return server, stats

    def max_diff(a, b):
        return max(
            jax.tree.leaves(
                jax.tree.map(
                    lambda x, y: float(
                        np.max(np.abs(np.asarray(x) - np.asarray(y)))
                    ),
                    a, b,
                )
            )
        )

    def param_dist(a, b):
        return float(
            np.sqrt(
                sum(
                    float(np.sum((np.asarray(x) - np.asarray(y)) ** 2))
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
                )
            )
        )

    def quarantined_ranks_from(tel):
        out = []
        for key in tel.counters_matching("defense_quarantined_total"):
            # defense_quarantined_total{rank=N}
            out.append(int(key.rsplit("rank=", 1)[1].rstrip("}")))
        return sorted(set(out))

    out = {"device": str(jax.devices()[0]), "clients": n_clients,
           "rounds": rounds, "attacker_ranks": attacker_ranks,
           "attacks": attacks}

    # -- 1: clip bit-identity (stream == buffered, zero fallbacks) ----
    cb, _ = run_world("bench_def_clipbuf", agg_mode="buffered", **clip_kw)
    cs, _ = run_world("bench_def_clipstr", agg_mode="stream", **clip_kw)
    tel = Telemetry.get_instance()
    diff = max_diff(
        cb.aggregator.get_global_model_params(),
        cs.aggregator.get_global_model_params(),
    )
    out["max_abs_diff_clip_stream_vs_buffered"] = diff
    out["clip_stream_identical_to_buffered"] = diff == 0.0
    out["clip_stream_fallbacks"] = sum(
        tel.counters_matching("agg_stream_fallback_total").values()
    )
    out["clip_buffered_peak_buffered"] = cb.aggregator.peak_buffered
    out["clip_stream_peak_buffered"] = cs.aggregator.peak_buffered
    out["clipped_uploads"] = cs.aggregator.defense_clipped
    _progress(
        f"defense: clip stream-vs-buffered diff {diff} "
        f"({cs.aggregator.defense_clipped} clipped)"
    )

    # -- 2: clean vs undefended-poisoned baselines --------------------
    clean, clean_stats = run_world("bench_def_clean", agg_mode="stream")
    p_clean = clean.aggregator.get_global_model_params()
    undef, undef_stats = run_world(
        "bench_def_undef", agg_mode="stream", **poison_kw
    )
    d_undef = param_dist(undef.aggregator.get_global_model_params(), p_clean)
    out["clean_loss"] = float(clean_stats["loss"])
    out["undefended_loss"] = float(undef_stats["loss"])
    out["undefended_dist"] = round(d_undef, 4)
    out["undefended_diverges"] = (
        out["undefended_loss"] > 3.0 * out["clean_loss"] and d_undef > 0.1
    )
    _progress(
        f"defense: clean loss {out['clean_loss']:.4f} vs poisoned "
        f"undefended {out['undefended_loss']:.4f}"
    )

    # -- 3: defended poisoned world under drop/dup faults -------------
    def_ck = _tempfile.mkdtemp(prefix="bench_def_ck_")
    def_td = _tempfile.mkdtemp(prefix="bench_def_td_")
    defended, def_stats = run_world(
        "bench_def_def", agg_mode="stream",
        reliable_comm=True, comm_retry_max=8, comm_retry_base_s=0.05,
        fault_injection={"drop_prob": 0.15, "duplicate_prob": 0.15, "seed": 5},
        checkpoint_dir=def_ck, checkpoint_freq=1, telemetry_dir=def_td,
        **poison_kw, **defense_kw,
    )
    tel = Telemetry.get_instance()

    def total(counter):
        return sum(tel.counters_matching(counter).values())

    d_def = param_dist(defended.aggregator.get_global_model_params(), p_clean)
    quarantined = quarantined_ranks_from(tel)
    out["defended_loss"] = float(def_stats["loss"])
    out["defended_dist"] = round(d_def, 4)
    out["defended_dist_ratio"] = round(d_def / max(d_undef, 1e-9), 4)
    out["defended_within_bound"] = (
        out["defended_loss"] < 0.5 * out["undefended_loss"]
        and d_def < 0.95 * d_undef
    )
    out["quarantined_ranks"] = quarantined
    out["attackers_quarantined"] = all(
        r in quarantined for r in attacker_ranks
    )
    out["honest_quarantined_ranks"] = [
        r for r in quarantined if r not in attacker_ranks
    ]
    out["rounds_completed"] = defended.manager.round_idx
    out["defense_clipped_total"] = total("defense_clipped_total")
    out["quarantine_rejected_uploads"] = total(
        "defense_quarantined_rejected_total"
    )
    # exactly-once under dup faults: every aggregated client == exactly
    # one fold; network duplicates are dropped by the channel and any
    # survivor is counted by the per-round fold dedup, never refolded
    folds = total("agg_folds_total")
    aggregated = total("cross_silo_clients_aggregated_total")
    out["folds_total"] = folds
    out["uploads_aggregated"] = aggregated
    out["dup_uploads_ignored"] = total("agg_dup_uploads_ignored_total")
    out["comm_dup_dropped"] = total("comm_dup_dropped_total")
    out["exactly_once"] = folds == aggregated and folds <= n_clients * rounds
    # post-hoc replay: quarantine-shrunken cohorts must be accounted by
    # the defense counters, folds by the WAL ledger
    out.update(_check_invariants(def_td, def_ck))
    _progress(
        f"defense: defended loss {out['defended_loss']:.4f}, quarantined "
        f"{quarantined} (attackers {attacker_ranks}), "
        f"{out['rounds_completed']}/{rounds} rounds"
    )

    # -- 4: async defended world --------------------------------------
    adef_ck = _tempfile.mkdtemp(prefix="bench_def_ack_")
    adef_td = _tempfile.mkdtemp(prefix="bench_def_atd_")
    asrv, async_stats = run_world(
        "bench_def_async", agg_mode="async", async_publish_every=3,
        staleness_decay=0.5, staleness_max=64,
        checkpoint_dir=adef_ck, checkpoint_freq=1, telemetry_dir=adef_td,
        **poison_kw, **defense_kw,
    )
    tel = Telemetry.get_instance()
    aq = quarantined_ranks_from(tel)
    d_async = param_dist(asrv.aggregator.get_global_model_params(), p_clean)
    stale_folds = sum(
        1 for e in asrv.manager.async_weight_log if e["staleness"] > 0
    )
    out["async"] = {
        "loss": float(async_stats["loss"]),
        "dist": round(d_async, 4),
        "quarantined_ranks": aq,
        "attacker_quarantined": any(r in aq for r in attacker_ranks),
        "honest_quarantined_ranks": [r for r in aq if r not in attacker_ranks],
        "folds_total": asrv.manager.async_folds,
        "target_folds": asrv.manager._async_target_folds(),
        "publishes": asrv.manager.version,
        "stale_folds": stale_folds,
        "clipped_uploads": asrv.aggregator.defense_clipped,
        "quarantine_rejected_uploads": sum(
            tel.counters_matching(
                "defense_quarantined_rejected_total"
            ).values()
        ),
        "defended_within_bound": (
            float(async_stats["loss"]) < 0.5 * out["undefended_loss"]
        ),
        **_check_invariants(adef_td, adef_ck),
    }
    _progress(
        f"defense: async loss {out['async']['loss']:.4f}, quarantined {aq}, "
        f"{asrv.manager.async_folds}/{asrv.manager._async_target_folds()} folds"
    )
    return out


def _check_invariants(telemetry_dir, checkpoint_dir=None) -> dict:
    """Run the post-hoc InvariantChecker over a finished world's
    artifacts and fold its verdict into the phase JSON — the shared
    tail of every chaos/straggler/defense/chaosplan world."""
    from fedml_tpu.core.invariants import InvariantChecker

    rep = InvariantChecker(
        telemetry_dir=telemetry_dir, checkpoint_dir=checkpoint_dir
    ).check()
    d = rep.to_dict()
    return {
        "invariants_ok": d["ok"],
        "invariants_checked": d["checked"],
        "invariants_violations": d["violations"],
    }


def run_chaosplan(on_cpu: bool, smoke: bool = False) -> dict:
    """Chaos-plane phase (docs/robustness.md chaos schedule DSL): the
    deterministic, schedulable fault layer as measured contracts —

    1. **determinism pair**: one LOCAL world run twice under the SAME
       ``ChaosSchedule`` + seed (exact message-N drop/dup/delay through
       the FaultInjector's plan seam, WAL IO latency + failed fsync
       through the DurableIO seam, a clock-skew barrier fault): the
       fault trace must be IDENTICAL across runs — same
       ``chaos_faults_injected_total`` counter series, same
       ``chaos.fault`` trace-event signature, every step fired.
    2. **crash-point sweep** (CrashMonkey-style, exhaustive): a short
       checkpointed world runs once under ``RecordingIO`` to enumerate
       EVERY WAL-append / checkpoint-publish write boundary, then
       re-runs once per crash point killing the server exactly there
       (before / torn-at-byte-K / after). Every re-run must recover
       (restart from checkpoint+WAL, all rounds complete) with the
       ``InvariantChecker`` clean.
    3. **combined world**: async staleness-weighted aggregation +
       norm-clipping defense, with the cohort's per-client dataset
       sizes drawn from a 100k-client ``ClientRegistry``, under a
       scripted schedule (exact upload drop recovered by retransmit,
       duplicate eaten by dedup, delayed dispatch, one scheduled
       client kill at the ``client.train`` barrier, WAL latency, clock
       skew): reaches its fold target and the checker proves
       exactly-once folds, version monotonicity and no reissued seqs
       from artifacts.

    ``smoke`` (CI gate): the same three sections at mini scale."""
    import tempfile as _tempfile
    import threading

    import jax

    import fedml_tpu
    from fedml_tpu import constants as C
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import checkpoint as ckpt_mod
    from fedml_tpu.core.chaos import (
        ProcessKilled,
        RecordingIO,
        active_chaos,
        crash_point_schedule,
        enumerate_crash_points,
        reset_chaos,
    )
    from fedml_tpu.core.invariants import InvariantChecker
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.data import load

    UPLOAD = int(C.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER)

    def mk(rank, run_id, n_clients, rounds, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = 120
        a.synthetic_test_size = 40
        a.model = "lr"
        a.partition_method = "hetero"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 1
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def build_world(run_id, n_clients, rounds, client_kw=None, **kw):
        a0, ds0, m0 = mk(0, run_id, n_clients, rounds, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, n_clients + 1):
            per = dict(kw)
            per.update((client_kw or {}).get(r, {}))
            a, ds, m = mk(r, run_id, n_clients, rounds, **per)
            clients.append(Client(a, None, ds, m))
        return server, clients

    def start_clients(clients, run_id):
        def client_thread(c):
            try:
                c.run()
            except ProcessKilled:
                pass  # a scheduled kill_client took this 'process' down

        threads = [
            threading.Thread(
                target=client_thread, args=(c,), daemon=True,
                name=f"{run_id}-c{i}",
            )
            for i, c in enumerate(clients)
        ]
        for t in threads:
            t.start()
        return threads

    def join_all(threads, note):
        for t in threads:
            t.join(timeout=120)
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"chaosplan {note}: threads hung: {hung}")

    out = {"device": str(jax.devices()[0])}

    # -- 1: determinism pair ------------------------------------------
    det_clients, det_rounds = 3, 3
    det_schedule = [
        # rank 1's first upload never leaves — the reliable channel's
        # retransmit re-traverses the injector (step is one-shot) and
        # recovers it
        {"at": {"event": "send", "msg_type": UPLOAD, "rank": 1,
                "occurrence": 1}, "fault": "drop"},
        # rank 2's second upload goes out twice — receive-side dedup
        {"at": {"event": "send", "msg_type": UPLOAD, "rank": 2,
                "occurrence": 2}, "fault": "duplicate"},
        # rank 3's first upload arrives 0.2s late
        {"at": {"event": "send", "msg_type": UPLOAD, "rank": 3,
                "occurrence": 1}, "fault": {"kind": "delay", "delay_s": 0.2}},
        # durable-IO faults: a slow append, then a refused fsync (the
        # WAL's degraded-durability OSError path, not a crash)
        {"at": {"event": "wal_append", "occurrence": 1},
         "fault": {"kind": "latency", "delay_s": 0.05}},
        {"at": {"event": "wal_append", "occurrence": 2},
         "fault": "fsync_fail"},
        # an NTP step mid-federation: the trace stitcher's problem, not
        # the monotonic-clock consumers'
        {"at": {"event": "barrier", "name": "server.round_close",
                "occurrence": 2}, "fault": {"kind": "clock_skew",
                                            "skew_s": 0.5}},
    ]

    def run_det(tag):
        reset_chaos()
        Telemetry.reset()
        ckpt_dir = _tempfile.mkdtemp(prefix=f"bench_cp_det{tag}_")
        server, clients = build_world(
            "bench_chaosplan_det", det_clients, det_rounds,
            chaos_schedule=det_schedule, chaos_seed=11,
            reliable_comm=True, comm_retry_max=8, comm_retry_base_s=0.05,
            checkpoint_dir=ckpt_dir, checkpoint_freq=1,
        )
        threads = start_clients(clients, f"det{tag}")
        server.run()
        join_all(threads, f"determinism run {tag}")
        tel = Telemetry.get_instance()
        sched = active_chaos()
        sig = InvariantChecker.fault_signature(
            tel.recorder.tail(len(tel.recorder))
        )
        fired = sorted(
            (f["step"], f["event"], f["fault"]) for f in sched.fired
        )
        counters = dict(tel.counters_matching("chaos_faults_injected_total"))
        return {
            "signature": sig,
            "fired": fired,
            "counters": counters,
            "pending": sched.pending(),
            "rounds": server.manager.round_idx,
        }

    d1 = run_det("a")
    d2 = run_det("b")
    out["determinism"] = {
        "steps": len(det_schedule),
        "faults_fired": len(d1["fired"]),
        "all_steps_fired": d1["pending"] == 0 and d2["pending"] == 0,
        "counters_identical": d1["counters"] == d2["counters"],
        "trace_signature_identical": d1["signature"] == d2["signature"],
        "identical_fault_trace": (
            d1["counters"] == d2["counters"]
            and d1["signature"] == d2["signature"]
            and d1["fired"] == d2["fired"]
        ),
        "rounds_completed": [d1["rounds"], d2["rounds"]],
    }
    _progress(
        f"chaosplan: determinism pair fired {len(d1['fired'])}/"
        f"{len(det_schedule)} steps, identical="
        f"{out['determinism']['identical_fault_trace']}"
    )

    # -- 2: crash-point sweep -----------------------------------------
    sweep_clients, sweep_rounds = 2, 2
    sweep_kw = dict(
        checkpoint_freq=1,
        heartbeat_interval_s=0.1,
        heartbeat_timeout_s=60.0,
    )

    # enumeration run: record every durable-write boundary
    reset_chaos()
    Telemetry.reset()
    recorder = RecordingIO()
    ckpt_mod.install_io_seam(recorder)
    try:
        enum_ck = _tempfile.mkdtemp(prefix="bench_cp_enum_")
        server, clients = build_world(
            "bench_chaosplan_enum", sweep_clients, sweep_rounds,
            checkpoint_dir=enum_ck, **sweep_kw,
        )
        threads = start_clients(clients, "enum")
        server.run()
        join_all(threads, "enumeration run")
    finally:
        ckpt_mod.reset_io_seam()
    points = enumerate_crash_points(recorder.events)
    _progress(
        f"chaosplan: enumerated {len(points)} crash points from "
        f"{len(recorder.events)} write boundaries"
    )

    sweep_results = []
    for point in points:
        reset_chaos()
        Telemetry.reset()
        ck = _tempfile.mkdtemp(prefix="bench_cp_sweep_")
        td = _tempfile.mkdtemp(prefix="bench_cp_sweept_")
        kill_kw = dict(
            sweep_kw,
            checkpoint_dir=ck,
            telemetry_dir=td,
            chaos_schedule=crash_point_schedule(point),
        )
        server1, clients = build_world(
            "bench_chaosplan_sweep", sweep_clients, sweep_rounds, **kill_kw
        )
        killed = {}

        def server_thread():
            try:
                server1.run()
            except ProcessKilled as e:
                killed["where"] = e.where
                # the 'process' died: its detector/watchdog threads too
                if server1.manager._failure_detector is not None:
                    server1.manager._failure_detector.stop()

        threads = start_clients(clients, "sweep")
        st = threading.Thread(
            target=server_thread, daemon=True, name="sweep-srv"
        )
        st.start()
        st.join(timeout=120)
        if st.is_alive() or not killed:
            raise RuntimeError(
                f"chaosplan sweep: crash point {point} never killed the "
                "server (or it hung)"
            )
        # restart: same schedule spec -> the already-fired one-shot
        # step is reused, so the resumed server runs fault-free
        a0b, ds0b, m0b = mk(
            0, "bench_chaosplan_sweep", sweep_clients, sweep_rounds, **kill_kw
        )
        server2 = Server(a0b, None, ds0b, m0b)
        resumed_at = server2.manager.round_idx
        server2.run()
        join_all(threads, f"sweep point {point}")
        inv = _check_invariants(td, ck)
        sweep_results.append(
            {
                **point,
                "killed_at": killed["where"],
                "resumed_at_round": resumed_at,
                "rounds_completed": server2.manager.round_idx,
                "recovered": server2.manager.round_idx >= sweep_rounds,
                "invariants_ok": inv["invariants_ok"],
                "violations": inv["invariants_violations"],
            }
        )
        _progress(
            f"chaosplan: crash point {point['event']}#"
            f"{point['occurrence']}/{point['mode']} -> resumed at "
            f"{resumed_at}, clean={inv['invariants_ok']}"
        )
    out["sweep"] = {
        "write_boundaries": len(recorder.events),
        "crash_points": len(points),
        "recovered": sum(1 for r in sweep_results if r["recovered"]),
        "all_recovered": all(r["recovered"] for r in sweep_results),
        "all_invariants_clean": all(
            r["invariants_ok"] for r in sweep_results
        ),
        "points": sweep_results,
    }

    # -- 3: combined async + defense + registry-drawn cohort ----------
    from fedml_tpu.scale.registry import ClientRegistry

    comb_clients = 3 if smoke else 4
    comb_rounds = 3
    reset_chaos()
    Telemetry.reset()
    registry = ClientRegistry(100_000, seed=17)
    cohort_ids = [int(i) for i in registry.sample_cohort(0, comb_clients)]
    # the cohort's heterogeneity comes from the registry columns: each
    # cross-silo client trains the dataset size its registry row says
    sizes = [
        int(min(max(int(registry.num_samples[cid]) * 2, 96), 320))
        for cid in cohort_ids
    ]
    comb_ck = _tempfile.mkdtemp(prefix="bench_cp_comb_")
    comb_td = _tempfile.mkdtemp(prefix="bench_cp_combt_")
    comb_schedule = [
        {"at": {"event": "send", "msg_type": UPLOAD, "rank": 1,
                "occurrence": 1}, "fault": "drop"},
        {"at": {"event": "send", "msg_type": UPLOAD, "rank": 3,
                "occurrence": 2}, "fault": "duplicate"},
        {"at": {"event": "send", "rank": 0, "occurrence": 4,
                "msg_type": int(C.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT)},
         "fault": {"kind": "delay", "delay_s": 0.1}},
        # rank 2 dies on its second dispatch (kill -9 analog at the
        # client.train barrier); the failure detector declares it and
        # async retires its outstanding work
        {"at": {"event": "barrier", "name": "client.train", "rank": 2,
                "occurrence": 2}, "fault": "kill_client"},
        {"at": {"event": "wal_append", "occurrence": 1},
         "fault": {"kind": "latency", "delay_s": 0.05}},
        {"at": {"event": "barrier", "name": "server.publish",
                "occurrence": 2}, "fault": {"kind": "clock_skew",
                                            "skew_s": 0.25}},
    ]
    comb_kw = dict(
        agg_mode="async",
        async_publish_every=2,
        staleness_decay=0.5,
        staleness_max=64,
        defense_type="norm_diff_clipping",
        norm_bound=1.0,
        reliable_comm=True,
        comm_retry_max=8,
        comm_retry_base_s=0.05,
        heartbeat_interval_s=0.1,
        heartbeat_timeout_s=1.5,
        checkpoint_dir=comb_ck,
        checkpoint_freq=1,
        telemetry_dir=comb_td,
        chaos_schedule=comb_schedule,
        chaos_seed=23,
    )
    client_kw = {
        r: {"synthetic_train_size": sizes[r - 1]}
        for r in range(1, comb_clients + 1)
    }
    aserver, aclients = build_world(
        "bench_chaosplan_comb", comb_clients, comb_rounds,
        client_kw=client_kw, **comb_kw,
    )
    t0 = time.perf_counter()
    threads = start_clients(aclients, "comb")
    aserver.run()
    comb_dt = time.perf_counter() - t0
    join_all(threads, "combined world")
    tel = Telemetry.get_instance()

    def total(counter):
        return sum(tel.counters_matching(counter).values())

    sched = active_chaos()
    inv = _check_invariants(comb_td, comb_ck)
    mgr = aserver.manager
    out["combined"] = {
        "registry_clients": registry.size,
        "cohort_client_ids": cohort_ids,
        "client_train_sizes": sizes,
        "clients": comb_clients,
        "folds_total": mgr.async_folds,
        "target_folds": mgr._async_target_folds(),
        "reached_fold_target": mgr.async_folds >= mgr._async_target_folds(),
        "publishes": mgr.version,
        # the kill is proven by the fired schedule step; the detector's
        # DECLARATION is timing-dependent (the fold target can be
        # reached by the survivors inside the heartbeat timeout) and is
        # reported separately
        "client_killed": any(
            f["fault"] == "kill_client" for f in (sched.fired if sched else [])
        ),
        "deaths_declared": total("cross_silo_clients_declared_dead_total"),
        "clipped_uploads": aserver.aggregator.defense_clipped,
        "chaos_faults": total("chaos_faults_injected_total"),
        "steps_fired": len(sched.fired) if sched is not None else 0,
        "retries_total": total("comm_retries_total"),
        "dup_dropped_total": total("comm_dup_dropped_total"),
        "wall_s": round(comb_dt, 2),
        **inv,
    }
    reset_chaos()
    _progress(
        f"chaosplan: combined world {mgr.async_folds}/"
        f"{mgr._async_target_folds()} folds, "
        f"{out['combined']['chaos_faults']:.0f} scheduled faults, "
        f"invariants_ok={inv['invariants_ok']}"
    )
    return out


def _build_planet_api(registry_size: int, cohort: int, rounds: int, **extra):
    """Registry-backed FedAvg api on the planet mini-config (LR over a
    60-dim synthetic population; the cohort is the variable, the model
    deliberately is not)."""
    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.simulation import FedAvgAPI

    args = Arguments()
    cfg = dict(
        dataset="synthetic",
        model="lr",
        client_registry_size=registry_size,
        cohort_size=cohort,
        edge_num=4,
        client_num_in_total=registry_size,
        client_num_per_round=cohort,
        comm_round=rounds,
        epochs=1,
        batch_size=32,
        learning_rate=0.1,
        frequency_of_the_test=10**9,
        synthetic_train_size=512,
        synthetic_test_size=256,
        matmul_precision="default",
    )
    cfg.update(extra)
    for k, v in cfg.items():
        setattr(args, k, v)
    args._validate()
    args = fedml_tpu.init(args)
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    return args, FedAvgAPI(args, None, dataset, model)


def run_planet(on_cpu: bool, smoke: bool = False) -> dict:
    """Planet-scale population phase (fedml_tpu/scale/,
    docs/planet_scale.md): registry-backed rounds at two registry
    sizes with the SAME cohort, proving the ROADMAP-2 claims as
    numbers:

    - rounds/s for a >=3-round sweep drawing the cohort from the
      registry (1M registry / 10k cohort; smoke: 100k / 1k);
    - host-memory flatness: warm-run RSS deltas (all jits compiled,
      same sampled cohorts) at a 10x-larger registry stay within
      cohort-scale slack of the small registry's — peak RSS rides the
      cohort, not the registry (plus ``planet_peak_rss_bytes`` via
      core/sys_stats);
    - two-tier tree aggregation (edge_num=4) bit-identical to the flat
      fold of the same per-edge terms (``edge_flat_fold`` baseline);
    - compile-trace census: one jit trace per (client-bucket, nb)
      shape key, within the pow2 bucket budget.

    ``smoke`` (CI gate): 100k registry, 1k cohort, 3 rounds."""
    import jax

    from fedml_tpu.core.sys_stats import current_rss_bytes, peak_rss_bytes
    from fedml_tpu.core.telemetry import Telemetry

    registry_big = 100_000 if smoke else 1_000_000
    registry_small = registry_big // 10
    cohort = 1_000 if smoke else 10_000
    rounds = 3
    out = {
        "registry_clients": registry_big,
        "registry_clients_small": registry_small,
        "cohort_size": cohort,
        "rounds": rounds,
        "edge_num": 4,
        "device": str(jax.devices()[0]),
    }

    def warm_delta(api):
        """RSS delta of a fully-warm re-run: train() without a
        checkpoint replays rounds [0, comm_round) — same cohorts, same
        shapes, zero new compiles — so the delta is the per-round
        transient (cohort materialization), not jit arenas."""
        api.train()  # warm every (bucket, nb) shape
        rss0 = current_rss_bytes()
        t0 = time.perf_counter()
        api.train()
        dt = time.perf_counter() - t0
        return max(0, current_rss_bytes() - rss0), dt

    _progress(f"planet: small registry ({registry_small} clients)")
    _, api_small = _build_planet_api(registry_small, cohort, rounds)
    delta_small, _ = warm_delta(api_small)
    out["rss_delta_warm_small_bytes"] = delta_small
    small_stats = api_small.pipeline_stats
    del api_small

    _progress(f"planet: big registry ({registry_big} clients)")
    rss_pre_big = current_rss_bytes()
    _, api_big = _build_planet_api(registry_big, cohort, rounds)
    delta_big, dt = warm_delta(api_big)
    stats = api_big.pipeline_stats
    out.update(
        {
            "rounds_per_sec": round(rounds / dt, 4),
            "clients_per_sec": round(rounds * cohort / dt, 1),
            "rss_delta_warm_big_bytes": delta_big,
            "rss_build_big_bytes": max(0, current_rss_bytes() - rss_pre_big),
            "registry_bytes": stats["registry_bytes"],
            "registry_bytes_small": small_stats["registry_bytes"],
            "trace_count": stats["trace_count"],
            "shape_key_count": len(stats["shape_keys"]),
            "waste_frac_mean": round(stats["waste_frac_mean"], 4),
        }
    )
    # the census budget: every jit shape is a (pow2 client bucket,
    # pow2 nb) pair — at most log2(cohort)+1 x log2(max nb)+1 keys
    max_nb = max(nb for _, nb in stats["shape_keys"])
    out["trace_budget"] = (
        (int(cohort).bit_length() + 1) * (int(max_nb).bit_length() + 1)
    )
    out["one_trace_per_shape"] = out["trace_count"] == out["shape_key_count"]
    out["trace_within_budget"] = out["trace_count"] <= out["trace_budget"]
    # flatness gate: a 10x registry must cost column bytes, not cohort
    # bytes — warm-run deltas agree within allocator-noise slack. An
    # unmeasurable RSS (current_rss_bytes() == 0) FAILS the gate: the
    # flat-memory claim is measured, never vacuously green
    slack = 64 * 1024 * 1024
    out["rss_measured"] = current_rss_bytes() > 0
    out["rss_scales_with_cohort"] = (
        out["rss_measured"] and delta_big <= delta_small + slack
    )
    _progress(
        f"planet: {out['rounds_per_sec']} rounds/s, warm RSS deltas "
        f"small={delta_small} big={delta_big}, traces={out['trace_count']}"
    )

    # tree == flat: identical per-edge terms, flat fold baseline.
    # Two train() calls to mirror the tree api's warm+timed pair (rng
    # and params chain across calls, so the trajectories must match
    # call-for-call)
    _, api_flat = _build_planet_api(
        registry_big, cohort, rounds, edge_flat_fold=True
    )
    api_flat.train()
    api_flat.train()
    diff = max(
        float(abs(a - b).max())
        for a, b in zip(
            jax.tree.leaves(api_big.global_params),
            jax.tree.leaves(api_flat.global_params),
        )
    )
    out["max_abs_diff_tree_vs_flat"] = diff
    out["tree_identical_to_flat"] = diff == 0.0
    _progress(f"planet: tree vs flat max abs diff {diff}")

    peak = peak_rss_bytes()
    Telemetry.get_instance().set_gauge("planet_peak_rss_bytes", peak)
    out["planet_peak_rss_bytes"] = peak
    return out


def _build_multichip_world(mesh_shape, cohort, rounds, n_clients):
    """One fed-mesh world on the multichip mini-config (LR over the
    MNIST-shaped synthetic stand-in; the mesh shape is the variable,
    the model/data deliberately are not)."""
    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.simulation import SimulatorMesh

    args = Arguments()
    for k, v in dict(
        dataset="mnist",
        synthetic_train_size=n_clients * 40,
        synthetic_test_size=200,
        model="lr",
        partition_method="hetero",
        client_num_in_total=n_clients,
        client_num_per_round=cohort,
        comm_round=rounds,
        epochs=1,
        batch_size=16,
        learning_rate=0.05,
        frequency_of_the_test=10**9,
        shuffle=False,
        matmul_precision="default",
        mesh_shape=mesh_shape,
    ).items():
        setattr(args, k, v)
    args._validate()
    args = fedml_tpu.init(args)  # flips threefry BEFORE the data loads
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    return SimulatorMesh(args, None, dataset, model)


def _multichip_shapes(n: int) -> list:
    """The (data, fsdp) mesh shapes the multichip phase runs on ``n``
    devices, single-chip baseline first. A four-chip host gets ``2x2``
    beside ``4x1`` so params are sharded over ``fsdp`` there as well."""
    base = ("1x1", {"data": 1, "fsdp": 1})
    if n >= 8:
        return [
            base,
            ("8x1", {"data": 8, "fsdp": 1}),
            ("4x2", {"data": 4, "fsdp": 2}),
            ("2x4", {"data": 2, "fsdp": 4}),
        ]
    if n >= 4:
        return [base, (f"{n}x1", {"data": n, "fsdp": 1}), ("2x2", {"data": 2, "fsdp": 2})]
    if n >= 2:
        return [base, (f"{n}x1", {"data": n, "fsdp": 1})]
    return [base]


def run_multichip(on_cpu: bool, smoke: bool = False) -> dict:
    """Mesh-sharded federation phase (parallel/layout.py +
    fedavg_api's fed branch, docs/multichip.md) — the REAL multi-device
    gate:

    - rounds/s and clients/s per named (data, fsdp) mesh shape,
      including the {data: 1, fsdp: 1} single-chip baseline;
    - bitwise identity: every sharded shape's final params must equal
      the single-chip vmap world's EXACTLY (``max_abs_diff == 0.0``) —
      per-client compute is never tensor-split (FSDP gathers at use)
      and the aggregation is the placement-independent exact expansion
      fold;
    - one jit trace per mesh shape (the compile census);
    - on-mesh aggregation: the streaming fold stays bitwise
      order-independent when uploads/limbs are (data, fsdp)-sharded
      device trees, raw AND int8-encoded — stream ≡ buffered holds on
      the mesh. Zero host transfers inside the round executables is a
      compile-time fact (`fedml-tpu audit --ci` over
      simulation.round_fn_mesh), not re-measured here.

    Under ``--cpu`` the child forces 8 virtual host devices; on real
    chips the same choreography runs over whatever the host has — a
    four-chip host adds the ``2x2`` shape, so ``fsdp`` is exercised
    there too. ``smoke`` (CI gate): cohort 16, 3 rounds."""
    import jax
    import numpy as np

    n = len(jax.devices())
    cohort = 16 if smoke else 64
    rounds = 3
    n_clients = max(2 * cohort, 32)
    out = {
        "n_devices": n,
        "cohort_size": cohort,
        "rounds": rounds,
        "device": str(jax.devices()[0]),
    }
    shapes = _multichip_shapes(n)
    if len(shapes) == 1:
        # one chip still exercises the fed path end to end; scaling
        # evidence then needs more chips — recorded, never silent
        out["single_device_only"] = True

    base_params = None
    entries = {}
    last_sim = None
    for key, shape in shapes:
        _progress(f"multichip: world {key} ({shape})")
        sim = _build_multichip_world(shape, cohort, rounds, n_clients)
        sim.run()  # warm: every executable compiles once
        t0 = time.perf_counter()
        sim.run()  # timed: pure steady-state rounds
        dt = time.perf_counter() - t0
        api = sim.fl_trainer
        entry = {
            "mesh_shape": shape,
            "rounds_per_sec": round(rounds / dt, 4),
            "clients_per_sec": round(rounds * cohort / dt, 1),
            "trace_count": api._round_trace_count,
        }
        params = jax.tree.map(np.asarray, api.global_params)
        if base_params is None:
            base_params = params
        else:
            diff = max(
                float(abs(a - b).max())
                for a, b in zip(
                    jax.tree.leaves(base_params), jax.tree.leaves(params)
                )
            )
            entry["max_abs_diff_vs_single_chip"] = diff
            entry["identical_to_single_chip"] = diff == 0.0
        entries[key] = entry
        last_sim = sim
        _progress(
            f"multichip: {key} {entry['rounds_per_sec']} rounds/s, "
            f"diff {entry.get('max_abs_diff_vs_single_chip', 'base')}"
        )
    out["shapes"] = entries
    out["one_trace_per_shape"] = all(
        e["trace_count"] == 1 for e in entries.values()
    )
    out["mesh_identical_to_single_chip"] = all(
        e.get("identical_to_single_chip", True) for e in entries.values()
    )

    # on-mesh streaming aggregation: raw + int8 uplink folds in two
    # arrival orders over (data, fsdp)-sharded device trees — the
    # stream ≡ buffered bitwise contract, proven ON the mesh
    from fedml_tpu.core.aggregation import StreamingAccumulator
    from fedml_tpu.core.compression import Int8Codec
    from fedml_tpu.parallel.layout import shard_tree

    mesh = last_sim.mesh
    rng = np.random.RandomState(5)
    host = jax.tree.map(np.asarray, last_sim.fl_trainer.global_params)
    uploads = [
        shard_tree(
            jax.tree.map(
                lambda x: x + np.asarray(
                    rng.standard_normal(x.shape), x.dtype
                ) * 0.01,
                host,
            ),
            mesh,
        )
        for _ in range(4)
    ]
    ws = [float(w) for w in rng.randint(1, 9, size=4)]

    def fold_diff(fold_one):
        a1 = StreamingAccumulator(uploads[0])
        a2 = StreamingAccumulator(uploads[0])
        for i in (0, 1, 2, 3):
            fold_one(a1, i)
        for i in (2, 0, 3, 1):
            fold_one(a2, i)
        return max(
            float(abs(np.asarray(x) - np.asarray(y)).max())
            for x, y in zip(
                jax.tree.leaves(a1.finalize()), jax.tree.leaves(a2.finalize())
            )
        )

    out["max_abs_diff_stream_raw"] = fold_diff(
        lambda acc, i: acc.fold(uploads[i], ws[i])
    )
    codec = Int8Codec()
    encs = [
        codec.encode(jax.tree.map(lambda x: x * 0.01, u)) for u in uploads
    ]
    out["max_abs_diff_stream_int8"] = fold_diff(
        lambda acc, i: acc.fold_encoded(codec, encs[i], uploads[0], ws[i])
    )
    out["agg_stream_raw_identical"] = out["max_abs_diff_stream_raw"] == 0.0
    out["agg_stream_int8_identical"] = out["max_abs_diff_stream_int8"] == 0.0
    # the host-transfer-freedom half of the acceptance: proven AOT by
    # the audit gate over these registrations (ci/CI-script-smoke.sh)
    out["mesh_executables_registered"] = [
        "simulation.round_fn_mesh", "planet.group_fn",
    ]
    _progress(
        f"multichip: stream raw diff {out['max_abs_diff_stream_raw']}, "
        f"int8 diff {out['max_abs_diff_stream_int8']}"
    )
    return out


def _build_elastic_world(
    mesh_shape, cohort, rounds, n_clients, ckpt_dir=None, devices=None
):
    """One fed-mesh world on the multichip mini-config plus the elastic
    knobs: a durable checkpoint dir, and (for the resume world) an
    explicit SURVIVING device subset — ``build_fed_mesh(devices=...)``
    over the survivors is exactly what a restarted process does after
    chip loss, so the bench builds its resume world the same way."""
    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.parallel.layout import build_fed_mesh
    from fedml_tpu.simulation import SimulatorMesh

    args = Arguments()
    for k, v in dict(
        dataset="mnist",
        synthetic_train_size=n_clients * 40,
        synthetic_test_size=200,
        model="lr",
        partition_method="hetero",
        client_num_in_total=n_clients,
        client_num_per_round=cohort,
        comm_round=rounds,
        epochs=1,
        batch_size=16,
        learning_rate=0.05,
        frequency_of_the_test=10**9,
        shuffle=False,
        matmul_precision="default",
        mesh_shape=mesh_shape,
    ).items():
        setattr(args, k, v)
    if ckpt_dir is not None:
        args.checkpoint_dir = ckpt_dir
    args._validate()
    args = fedml_tpu.init(args)  # flips threefry BEFORE the data loads
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    mesh = (
        build_fed_mesh(devices=devices, mesh_shape=mesh_shape)
        if devices is not None
        else None
    )
    return SimulatorMesh(args, None, dataset, model, mesh=mesh)


def run_elastic(on_cpu: bool, smoke: bool = False) -> dict:
    """Elastic-mesh preemption phase (parallel/elastic.py +
    fedavg_api's preempt/restore seam, docs/robustness.md device-loss
    section) — survive chip loss with bitwise-identical resume on a
    reshaped mesh:

    - a scripted mid-round preemption (``SimulatedPreemption`` at round
      1) drains the in-flight round, appends a WAL ``kind="preempt"``
      record write-ahead of a forced checkpoint, and exits via
      ``Preempted``;
    - a restarted world over HALF the devices (8 -> 4 forced under
      ``--cpu``) restores device-direct onto the surviving mesh,
      appends the paired ``kind="resume"`` record, and completes the
      run — final params must be **bitwise identical**
      (``max_abs_diff == 0.0``) to an uninterrupted full-device run
      (the PR-15 mesh-shape identity is what makes this provable);
    - streaming-accumulator limbs travel across the reshape
      (``export_state`` -> ``reshape_limb_state`` -> ``fold_limbs``)
      bitwise-identically for raw AND int8-encoded uplinks;
    - the offline ``InvariantChecker`` re-verifies the preempt/resume
      WAL pairing on the run's artifacts;
    - **recovery_s** (headline): wall time from starting the restarted
      process's world build to its FIRST completed round — restore +
      reshape + recompile included.

    ``smoke`` (CI gate): cohort 16, 4 rounds, 32 clients."""
    import tempfile as _tempfile

    import jax
    import numpy as np

    from fedml_tpu.core.aggregation import StreamingAccumulator
    from fedml_tpu.core.checkpoint import RoundWAL
    from fedml_tpu.core.compression import Int8Codec
    from fedml_tpu.core.invariants import InvariantChecker
    from fedml_tpu.parallel.elastic import (
        Preempted,
        SimulatedPreemption,
        reshape_limb_state,
    )
    from fedml_tpu.parallel.layout import shard_tree

    n = len(jax.devices())
    nb = 8 if n >= 8 else max(n - n % 2, 1)  # devices before the loss
    na = max(nb // 2, 1)  # survivors
    cohort = 16 if smoke else 32
    if cohort % nb:
        cohort = 2 * nb
    rounds = 4
    n_clients = max(2 * cohort, 32)
    out = {
        "n_devices": n,
        "devices_before": nb,
        "devices_after": na,
        "cohort_size": cohort,
        "rounds": rounds,
        "device": str(jax.devices()[0]),
    }
    if nb == na:
        out["single_device_only"] = True
    shape_before = {"data": nb, "fsdp": 1}
    shape_after = {"data": na, "fsdp": 1}

    # 1) the uninterrupted reference: full device set, all rounds
    _progress(f"elastic: uninterrupted {nb}-device baseline")
    sim0 = _build_elastic_world(shape_before, cohort, rounds, n_clients)
    sim0.run()
    base = jax.tree.map(np.asarray, sim0.fl_trainer.global_params)

    # 2) the preempted run: same world + checkpoint dir, a maintenance
    # notice at round 1 -> WAL preempt record, forced checkpoint,
    # controlled exit
    ckpt_dir = _tempfile.mkdtemp(prefix="bench_elastic_")
    _progress(f"elastic: preempted {nb}-device run (notice at round 1)")
    sim1 = _build_elastic_world(
        shape_before, cohort, rounds, n_clients, ckpt_dir=ckpt_dir
    )
    sim1.fl_trainer._preempt_signal = SimulatedPreemption(at_round=1)
    try:
        sim1.run()
        out["preempted"] = False  # signal never fired — a failure
    except Preempted as e:
        out["preempted"] = True
        out["preempt_round"] = int(e.round_idx)
        out["preempt_reason"] = e.notice.reason

    # 3) the restart: HALF the devices survive; restore lands
    # device-direct on the reshaped mesh and the run completes.
    # recovery_s clocks the whole restart (world build + restore +
    # recompile) to the first completed round — the metric an operator
    # actually waits on.
    class _FirstRoundProbe:
        t = None

        def poll(self, round_idx):
            if self.t is None:
                self.t = time.perf_counter()
            return None

    _progress(f"elastic: resuming on {na} surviving devices")
    t0 = time.perf_counter()
    sim2 = _build_elastic_world(
        shape_after,
        cohort,
        rounds,
        n_clients,
        ckpt_dir=ckpt_dir,
        devices=list(jax.devices())[:na],
    )
    probe = _FirstRoundProbe()
    sim2.fl_trainer._preempt_signal = probe
    sim2.run()
    recovery_s = (probe.t or time.perf_counter()) - t0
    resumed = jax.tree.map(np.asarray, sim2.fl_trainer.global_params)
    diff = max(
        float(abs(a - b).max())
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(resumed))
    )
    out["max_abs_diff_resume"] = diff
    out["resume_identical"] = diff == 0.0
    out["recovery_s"] = round(recovery_s, 3)
    out["value"] = round(recovery_s, 3)
    out["metric"] = "recovery_s"
    out["unit"] = "s"
    _progress(
        f"elastic: resume diff {diff}, recovery {out['recovery_s']}s"
    )

    # 4) limb travel across the reshape: fold half the uploads on the
    # BEFORE mesh, export the 3-limb expansion, reshard it onto the
    # AFTER mesh, fold the rest there — finalize must equal the
    # single-mesh fold of all four, raw AND int8 (the accumulator state
    # is what the elastic checkpoint carries, so its portability is a
    # bitwise contract, not a best effort)
    mesh_b, mesh_a = sim1.mesh, sim2.mesh
    rng = np.random.RandomState(7)
    host = jax.tree.map(np.asarray, resumed)
    ups = [
        jax.tree.map(
            lambda x: x + np.asarray(
                rng.standard_normal(x.shape), x.dtype
            ) * 0.01,
            host,
        )
        for _ in range(4)
    ]
    ws = [float(w) for w in rng.randint(1, 9, size=4)]

    def travel_diff(fold_one):
        """max |single-mesh fold of 0..3  -  split fold (0,1 on the
        before-mesh, limbs travel, 2,3 on the after-mesh)|."""
        ref = StreamingAccumulator(shard_tree(ups[0], mesh_b))
        for i in range(4):
            fold_one(ref, i, mesh_b)
        acc_b = StreamingAccumulator(shard_tree(ups[0], mesh_b))
        for i in (0, 1):
            fold_one(acc_b, i, mesh_b)
        state = reshape_limb_state(acc_b.export_state(), mesh_a)
        acc_a = StreamingAccumulator(shard_tree(ups[0], mesh_a))
        acc_a.fold_limbs(
            state["limbs"], state["total_w"], count=state["count"]
        )
        for i in (2, 3):
            fold_one(acc_a, i, mesh_a)
        return max(
            float(abs(np.asarray(x) - np.asarray(y)).max())
            for x, y in zip(
                jax.tree.leaves(ref.finalize()),
                jax.tree.leaves(acc_a.finalize()),
            )
        )

    out["max_abs_diff_limbs_raw"] = travel_diff(
        lambda acc, i, mesh: acc.fold(shard_tree(ups[i], mesh), ws[i])
    )
    codec = Int8Codec()
    encs = [codec.encode(jax.tree.map(lambda x: x * 0.01, u)) for u in ups]
    out["max_abs_diff_limbs_int8"] = travel_diff(
        lambda acc, i, mesh: acc.fold_encoded(
            codec, encs[i], shard_tree(ups[0], mesh), ws[i]
        )
    )
    out["limb_travel_raw_identical"] = out["max_abs_diff_limbs_raw"] == 0.0
    out["limb_travel_int8_identical"] = out["max_abs_diff_limbs_int8"] == 0.0
    _progress(
        f"elastic: limb travel raw diff {out['max_abs_diff_limbs_raw']}, "
        f"int8 diff {out['max_abs_diff_limbs_int8']}"
    )

    # 5) the offline checker re-verifies the preempt/resume ledger on
    # the run's own artifacts — same gate `fedml-tpu check` applies
    out["wal_kinds"] = [
        r.get("kind") for r in RoundWAL(ckpt_dir).records()
    ]
    rep = InvariantChecker(None, ckpt_dir).check()
    out["invariants_ok"] = rep.ok
    out["invariants_checked"] = list(rep.checked)
    if not rep.ok:
        out["invariant_violations"] = list(rep.violations)
    return out


def run_hier(on_cpu: bool, smoke: bool = False) -> dict:
    """Hierarchical server plane phase (docs/hierarchical.md): edge
    aggregators as REAL ranks over the comm seam.

    Three sections, every world's artifacts re-verified by the
    multi-tier ``InvariantChecker``:

    - **scaling** — worlds at ``edge_num`` ∈ {1, 2, 4} with a fixed
      per-edge client count and a DELIBERATELY SLOW root link (a
      scheduled chaos delay on every edge→root merge upload): the
      slow link is the fixed per-round cost, the edges multiply how
      many client uploads are folded per round at that cost, so
      uploads/s (clients folded per steady-round wall second,
      telemetry-counted) must scale ≥2x from 1 to 4 edges;
    - **bit identity** — the 2-edge world's final params vs a flat
      single-server world of the SAME clients: ``max_abs_diff == 0.0``
      (the ``StreamingAccumulator.merge`` contract across processes);
    - **edge kill/restart** — drop+dup faults + a scheduled
      ``kill_client`` at edge 1's ``edge.merge_upload`` barrier
      mid-round; a fresh edge incarnation resumes via RESYNC + its WAL
      sub-ledger and the world still lands bit-identical to flat with
      the checker green.

    ``smoke`` (CI gate): 3 clients/edge x 3 rounds on the LR mini
    cohort; same choreography in seconds."""
    import tempfile as _tempfile
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.invariants import InvariantChecker
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.cross_silo.hierarchical import (
        HierEdge,
        run_local_hier_world,
    )
    from fedml_tpu.data import load

    per_edge = 3 if (smoke or on_cpu) else 4
    rounds = 3 if (smoke or on_cpu) else 4
    train_size = 240 if smoke else 400
    delay_s = 1.0  # the deliberately slow root link, per merge upload
    edge_counts = (1, 2, 4)

    def mk_base(rank, run_id, n_clients, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = train_size
        a.synthetic_test_size = 60
        a.model = "lr"
        a.partition_method = "hetero"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 1
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def check_world(ck, td):
        rep = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        if not rep.ok:
            _progress(f"hier: INVARIANT VIOLATIONS {rep.to_dict()}")
        return rep.ok

    out = {
        "per_edge_clients": per_edge,
        "rounds": rounds,
        "root_link_delay_s": delay_s,
        "edges": {},
    }
    all_checks = []

    # -- scaling: E in {1,2,4}, slow root link ------------------------
    e2_params = None
    for e_num in edge_counts:
        n = per_edge * e_num
        Telemetry.reset()
        ck = _tempfile.mkdtemp(prefix=f"bench_hier_ck{e_num}_")
        td = _tempfile.mkdtemp(prefix=f"bench_hier_td{e_num}_")
        # one scheduled delay per merge upload: the Nth matching send
        # of the edge-report type fires the Nth step — every report of
        # every round crosses the slow link
        from fedml_tpu import constants as C

        schedule = [
            {
                "at": {
                    "event": "send",
                    "msg_type": C.MSG_TYPE_E2R_EDGE_REPORT,
                    "occurrence": k,
                },
                "fault": {"kind": "delay", "delay_s": delay_s},
            }
            for k in range(1, e_num * rounds + 1)
        ]
        kw = dict(
            edge_plane="ranks",
            edge_num=e_num,
            checkpoint_dir=ck,
            telemetry_dir=td,
            chaos_schedule=schedule,
        )

        def mk(role, rank, _rid=f"bench_hier_e{e_num}", _n=n, _kw=kw):
            return mk_base(rank, _rid, _n, **_kw)

        t0 = time.perf_counter()
        world = run_local_hier_world(mk, n, e_num)
        wall = time.perf_counter() - t0
        tel = Telemetry.get_instance()
        folded = sum(
            tel.counters_matching("hier_uploads_folded_total").values()
        )
        walls = world["root"].manager.round_walls
        # steady-state: round 0 pays every client trainer's first jit
        steady_walls = walls[1:] if len(walls) > 1 else walls
        steady_uploads = folded - n if len(walls) > 1 else folded
        ups = steady_uploads / max(sum(steady_walls), 1e-9)
        ok = check_world(ck, td)
        all_checks.append(ok)
        out["edges"][str(e_num)] = {
            "clients": n,
            "uploads_folded": folded,
            "uploads_per_sec": round(ups, 3),
            "round_walls_s": [round(w, 3) for w in walls],
            "world_wall_s": round(wall, 2),
            "merges": sum(
                tel.counters_matching("hier_edge_merges_total").values()
            ),
            "check_ok": ok,
        }
        _progress(
            f"hier: E={e_num} ({n} clients): {ups:.2f} uploads/s, "
            f"walls {[round(w, 2) for w in walls]}, check_ok={ok}"
        )
        if e_num == 2:
            e2_params = jax.tree.map(
                np.asarray,
                world["root"].aggregator.get_global_model_params(),
            )
    ups1 = out["edges"]["1"]["uploads_per_sec"]
    ups4 = out["edges"]["4"]["uploads_per_sec"]
    out["uploads_scaling_e4_vs_e1"] = round(ups4 / max(ups1, 1e-9), 3)

    # -- bit identity vs the flat single-server world -----------------
    n_id = per_edge * 2
    Telemetry.reset()
    a0, ds0, m0 = mk_base(0, "bench_hier_flat", n_id)
    server = Server(a0, None, ds0, m0)
    clients = []
    for r in range(1, n_id + 1):
        a, ds, m = mk_base(r, "bench_hier_flat", n_id)
        clients.append(Client(a, None, ds, m))
    threads = [
        threading.Thread(target=c.run, daemon=True, name=f"hierflat-c{i}")
        for i, c in enumerate(clients)
    ]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=120)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("hier: flat reference world hung")
    flat_params = jax.tree.map(
        np.asarray, server.aggregator.get_global_model_params()
    )
    diff = max(
        float(np.max(np.abs(x - y)))
        for x, y in zip(jax.tree.leaves(flat_params), jax.tree.leaves(e2_params))
    )
    out["hier_vs_flat_max_abs_diff"] = diff
    out["hier_identical_to_flat"] = diff == 0.0
    _progress(f"hier: tree-over-ranks vs flat max abs diff {diff}")

    # -- mid-round edge kill/restart under drop+dup faults ------------
    Telemetry.reset()
    ck = _tempfile.mkdtemp(prefix="bench_hier_kck_")
    td = _tempfile.mkdtemp(prefix="bench_hier_ktd_")
    kill_kw = dict(
        edge_plane="ranks",
        edge_num=2,
        checkpoint_dir=ck,
        telemetry_dir=td,
        # beats are the restarted edge's reconnect probe (it must
        # relearn its clients are online); deaths are healed by the
        # restart, not declared
        heartbeat_interval_s=0.1,
        heartbeat_timeout_s=60.0,
        reliable_comm=True,
        comm_retry_max=8,
        comm_retry_base_s=0.05,
        fault_injection={"drop_prob": 0.2, "duplicate_prob": 0.2},
        chaos_schedule=[
            {
                "at": {
                    "event": "barrier",
                    "name": "edge.merge_upload",
                    "rank": 1,
                    "occurrence": 1,
                },
                "fault": {"kind": "kill_client"},
            }
        ],
    )

    def mk_kill(role, rank):
        return mk_base(rank, "bench_hier_kill", n_id, **kill_kw)

    restarted = threading.Event()

    def edge_wrapper(rank, edge):
        if rank != 1:
            return edge.run

        def run_and_restart():
            from fedml_tpu.core.chaos import ProcessKilled

            try:
                edge.run()
            except ProcessKilled:
                time.sleep(0.3)
                a2, ds2, m2 = mk_kill("edge", 1)
                restarted.set()
                HierEdge(a2, None, ds2, m2, partition=edge.partition).run()

        return run_and_restart

    world = run_local_hier_world(mk_kill, n_id, 2, edge_wrapper=edge_wrapper)
    kill_params = jax.tree.map(
        np.asarray, world["root"].aggregator.get_global_model_params()
    )
    kdiff = max(
        float(np.max(np.abs(x - y)))
        for x, y in zip(
            jax.tree.leaves(flat_params), jax.tree.leaves(kill_params)
        )
    )
    kok = check_world(ck, td)
    all_checks.append(kok)
    out["edge_kill_fired"] = restarted.is_set()
    out["edge_kill_max_abs_diff"] = kdiff
    out["edge_kill_check_ok"] = kok
    out["invariants_ok_all"] = all(all_checks)
    _progress(
        f"hier: edge kill/restart recovered (diff {kdiff}, check {kok}); "
        f"scaling E4/E1 = {out['uploads_scaling_e4_vs_e1']}x"
    )
    return out


def run_tracing(on_cpu: bool, smoke: bool = False) -> dict:
    """Tracing phase (docs/observability.md): a LOCAL multi-client
    cross-silo world run twice — telemetry OFF, then distributed
    tracing ON with ``telemetry_dir`` export — then stitched and
    analyzed (``core/tracing.py``). Proves the acceptance contract as
    numbers:

    - every comm send span has a matched cross-process receive flow;
    - per-round critical-path segments sum to the measured round wall
      time within tolerance (``min_coverage``);
    - tracing overhead vs telemetry-off stays bounded
      (``overhead_pct``), final params are bit-identical either way,
      and ``host_syncs_per_round`` on the pipelined cohort is unchanged
      with tracing on (``host_syncs_match``).

    ``smoke`` (CI gate): 3 clients x 4 rounds on the LR mini cohort."""
    import shutil as _shutil
    import tempfile as _tempfile
    import threading

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.core.tracing import trace_run
    from fedml_tpu.cross_silo import Client, Server
    from fedml_tpu.data import load

    n_clients = 3 if (smoke or on_cpu) else 4
    rounds = 6 if (smoke or on_cpu) else 8
    train_size = 1200 if (smoke or on_cpu) else 2400

    def mk(rank, run_id, **kw):
        a = Arguments()
        a.training_type = "cross_silo"
        a.backend = "LOCAL"
        a.dataset = "mnist"
        a.synthetic_train_size = train_size
        a.synthetic_test_size = 60
        # an MLP wide enough that steady rounds run hundreds of ms:
        # the per-message tracing cost must be measured against
        # realistic round lengths — near-empty LR rounds (a few ms)
        # time scheduler jitter, not instrumentation — while compiling
        # in seconds on a 1-core CI box (a CNN would not)
        a.model = "mlp"
        a.hidden_dim = 512
        a.partition_method = "hetero"
        a.client_num_in_total = n_clients
        a.client_num_per_round = n_clients
        a.comm_round = rounds
        a.epochs = 2
        a.batch_size = 16
        a.learning_rate = 0.1
        a.frequency_of_the_test = rounds
        a.shuffle = False
        a.run_id = run_id
        a.rank = rank
        for k, v in kw.items():
            setattr(a, k, v)
        a._validate()
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    def run_world(run_id, **kw):
        a0, ds0, m0 = mk(0, run_id, **kw)
        server = Server(a0, None, ds0, m0)
        # per-round end marks: the overhead figure compares STEADY
        # rounds (1..N-1); round 0 absorbs every jit compile of its
        # world, and each world compiles its own closures, so whole-run
        # wall time measures compile variance, not tracing cost
        marks = []
        mgr = server.manager
        orig_report = mgr._report_round

        def report_and_mark(eval_round, cohort, n_aggregated):
            orig_report(eval_round, cohort, n_aggregated)
            marks.append(time.perf_counter())

        mgr._report_round = report_and_mark
        clients = []
        for r in range(1, n_clients + 1):
            a, ds, m = mk(r, run_id, **kw)
            clients.append(Client(a, None, ds, m))
        threads = [
            threading.Thread(target=c.run, daemon=True, name=f"trc-c{i}")
            for i, c in enumerate(clients)
        ]
        for t in threads:
            t.start()
        server.run()
        for t in threads:
            t.join(timeout=120)
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"tracing world {run_id}: threads hung: {hung}")
        # steady per-round walls: round 0 absorbs its world's compiles
        walls = [b - a for a, b in zip(marks, marks[1:])]
        params = jax.tree.map(
            np.asarray, server.aggregator.get_global_model_params()
        )
        return walls, params

    out = {
        "device": str(jax.devices()[0]),
        "clients": n_clients,
        "rounds": rounds,
    }
    # Overhead protocol: ALTERNATE off/on worlds — in ABBA order, so
    # each mode runs once early and once late — and pool the steady
    # per-round walls per mode, then compare medians. A single
    # off-then-on pair confounds tracing cost with process drift (the
    # later world always measures slower on a shared 1-core box), and
    # a median resists scheduler spikes a mean would average in.
    walls = {"off": [], "on": []}
    params_by_mode = {}
    tdir = _tempfile.mkdtemp(prefix="bench_tracing_")
    try:
        for rep in range(2):
            for mode in ("off", "on") if rep == 0 else ("on", "off"):
                Telemetry.reset()
                kw = (
                    dict(telemetry=False)
                    if mode == "off"
                    else dict(telemetry_dir=tdir)
                )
                w, params = run_world(f"bench_tracing_{mode}_{rep}", **kw)
                walls[mode].extend(w)
                params_by_mode[mode] = params
                if mode == "on":
                    tel = Telemetry.get_instance()
                    comm_ops = sum(
                        tel.counters_matching(
                            "comm_messages_sent_total"
                        ).values()
                    ) + sum(
                        tel.counters_matching(
                            "comm_messages_received_total"
                        ).values()
                    )
                _progress(
                    f"tracing: {mode} rep {rep} steady rounds "
                    f"{[round(x * 1e3) for x in w]} ms"
                )
        summary = trace_run(tdir)  # shards of the LAST traced world
        with open(summary["round_report"]) as fh:
            report = json.load(fh)
        # perf-plane readout (analysis/perf) over the same traced world:
        # the idle ledger + roofline join `fedml-tpu perf` computes,
        # folded into the phase record so the watcher's MFU/idle column
        # reads live series instead of re-deriving them
        try:
            from fedml_tpu.analysis import perf as _perf

            _measured = _perf.exec_seconds_from_snapshots(
                _perf.load_snapshots(tdir)
            )
            _ledger = _perf.summarize_ledger(_perf.load_ledgers(tdir))
            _roof = _perf.join_roofline(
                _perf.load_audit_report(
                    os.path.join(_capture_dir(), _perf.AUDIT_REPORT_NAME)
                ),
                _measured,
                device_kind=jax.devices()[0].device_kind,
            )
            _top = max(
                (r for r in _roof["rows"] if r.get("mfu_vs_bf16_peak")),
                key=lambda r: r["mfu_vs_bf16_peak"],
                default=None,
            )
            _recons = [
                r["recon_frac"]
                for r in _ledger["rounds"]
                if r.get("recon_frac") is not None
            ]
            perf_plane = {
                "exec_series": len(_measured),
                "coverage": _roof["coverage"],
                "top_mfu_executable": _top["executable"] if _top else None,
                "mfu_vs_bf16_peak": (
                    _top["mfu_vs_bf16_peak"] if _top else None
                ),
                "ledger_rounds": len(_ledger["rounds"]),
                "min_recon_frac": min(_recons) if _recons else None,
                "idle_totals_s": _ledger["idle_totals_s"],
                "mean_wire_utilization_frac": _ledger[
                    "mean_wire_utilization_frac"
                ],
            }
        except Exception as e:  # noqa: BLE001 — readout must not kill the phase
            perf_plane = {"error": f"{type(e).__name__}: {e}"}
    finally:
        _shutil.rmtree(tdir, ignore_errors=True)

    off_dt = sorted(walls["off"])[len(walls["off"]) // 2]
    on_dt = sorted(walls["on"])[len(walls["on"]) // 2]
    off_params, on_params = params_by_mode["off"], params_by_mode["on"]

    # Deterministic attribution: the wall-clock delta above rides ±10%
    # scheduler noise at these round lengths, so ALSO measure the
    # instrument layer's per-message cost directly (stamping + spans +
    # flows + counters through a sink transport, model-params payload)
    # and attribute it against the measured comm ops per round — the
    # stable form of the <=5% overhead claim.
    from fedml_tpu.core.comm.base import (
        BaseCommunicationManager as _BCM,
    )
    from fedml_tpu.core.comm.instrument import (
        InstrumentedCommunicationManager as _Inst,
    )
    from fedml_tpu.core.message import Message as _Msg

    class _Sink(_BCM):
        def send_message(self, m):
            pass

        def add_observer(self, o):
            pass

        def remove_observer(self, o):
            pass

        def handle_receive_message(self):
            pass

        def stop_receive_message(self):
            pass

    Telemetry.reset()
    inst = _Inst(_Sink(), Telemetry.get_instance(), rank=1)

    def _bench_send(com, n=400):
        t0 = time.perf_counter()
        for _ in range(n):
            m = _Msg(3, 1, 0)
            m.add_params(_Msg.MSG_ARG_KEY_MODEL_PARAMS, on_params)
            m.add_params("round_idx", 1)
            com.send_message(m)
        return (time.perf_counter() - t0) / n

    per_msg_s = max(_bench_send(inst) - _bench_send(_Sink()), 0.0)
    ops_per_round = comm_ops / max(rounds, 1)
    attributed_pct = per_msg_s * ops_per_round / max(off_dt, 1e-9) * 100

    diff = max(
        jax.tree.leaves(
            jax.tree.map(
                lambda x, y: float(np.max(np.abs(np.asarray(x) - y))),
                on_params,
                off_params,
            )
        )
    )
    coverages = [
        r["coverage"] for r in report["rounds"] if r["coverage"] is not None
    ]
    flows = summary["flows"]
    out.update(
        {
            "off_rounds_per_sec": round(1.0 / off_dt, 4),
            "on_rounds_per_sec": round(1.0 / on_dt, 4),
            "overhead_pct": round((on_dt - off_dt) / max(off_dt, 1e-9) * 100, 2),
            "instrument_us_per_msg": round(per_msg_s * 1e6, 1),
            "comm_ops_per_round": round(ops_per_round, 1),
            "attributed_overhead_pct": round(attributed_pct, 2),
            "overhead_within_5pct": attributed_pct <= 5.0,
            "params_match_off": diff == 0.0,
            "trace_events": summary["events"],
            "flow_starts": flows["flow_starts"],
            "flows_matched": flows["matched"],
            "all_flows_matched": flows["unmatched_starts"] == 0,
            "rounds_analyzed": summary["rounds_analyzed"],
            # named segments / round wall, worst round: 1.0 would mean
            # the critical path explains every microsecond
            "min_coverage": round(min(coverages), 4) if coverages else None,
            "segments_sum_within_5pct": bool(coverages)
            and min(coverages) >= 0.95,
            "straggler_ranks": [
                r["straggler_rank"] for r in report["rounds"]
            ],
            "perf_plane": perf_plane,
        }
    )
    _progress(
        f"tracing: {flows['matched']}/{flows['flow_starts']} flows matched, "
        f"min coverage {out['min_coverage']}, overhead {out['overhead_pct']}%"
    )

    # -- host-sync identity on the pipelined cohort -------------------
    # (the simulation hot loop must not gain a device fetch from
    # tracing; same contract the telemetry phase pins, re-proven here
    # with the tracing-era instrument layer)
    n_rounds, cohort = _pipeline_cohort(on_cpu=True, smoke=True)
    args, api = _build_pipeline_api(n_rounds, cohort, pipeline_depth=4)
    syncs = {}
    for mode in ("off", "on"):
        Telemetry.reset()
        api.telemetry = Telemetry.get_instance(args)
        api.telemetry.enabled = mode == "on"
        api.telemetry.attach_profiler(api.profiler)
        api.train()
        syncs[mode] = api.pipeline_stats.get("host_syncs_per_round")
    out["host_syncs_per_round"] = syncs["on"]
    out["host_syncs_match"] = syncs["on"] == syncs["off"]
    return out


def run_crossdevice(on_cpu: bool, smoke: bool = False) -> dict:
    """Cross-device Beehive phase (docs/cross_device.md): churn-is-
    normal connectionless federation over a 100k-device registry.

    One scripted world: every round, 30% of the sampled cohort is
    scheduled to vanish at ``device.upload`` (churn, not faults — the
    round must CLOSE ON ITS FOLD TARGET anyway, never stall), with
    pairwise-masked secure aggregation and Shamir dropout recovery for
    the vanished maskers. The gates:

    - every round closes with reason ``target`` at or above its fold
      target (a million flaky phones cannot stall a round);
    - the masked world's final params are BITWISE identical to an
      unmasked world under the same schedule (masks cancel exactly in
      the mod-p fold; recovery corrections are exact);
    - the WAL fold ledger matches the fold counter exactly
      (at-most-once fold), and ``fedml-tpu check`` (the offline
      invariant checker) exits green over the run's artifacts;
    - one jit trace per (speed tier, pow2 bucket) — the compile
      census a heterogeneous device population presents.

    ``smoke`` (CI gate): 64-device cohorts instead of 256; same
    choreography in seconds."""
    import tempfile as _tempfile

    import numpy as np

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.cli import main as cli_main
    from fedml_tpu.core.chaos import reset_chaos
    from fedml_tpu.core.invariants import InvariantChecker
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.cross_device import run_beehive_world
    from fedml_tpu.scale.registry import ClientRegistry

    registry_size = 100_000
    cohort = 64 if smoke else 256
    rounds = 3
    feature_dim, class_num = 8, 4

    # precompute each round's cohort from a twin registry and schedule
    # 30% of it to vanish mid-round (the chaos plane is deterministic:
    # both worlds replay the identical churn)
    twin = ClientRegistry(registry_size, seed=0, duty_hours=14)
    schedule = []
    vanish_per_round = {}
    for r in range(rounds):
        ids = twin.sample_available_cohort(r, cohort)
        k = max(1, int(0.3 * len(ids)))
        vanish_per_round[r] = k
        for d in ids[:k]:
            schedule.append(
                {
                    "at": {
                        "event": "device.upload",
                        "device": int(d),
                        "round": r,
                    },
                    "fault": {"kind": "vanish"},
                }
            )

    def beehive_world(masked: bool, run_id: str) -> dict:
        a = Arguments()
        a.training_type = "simulation"
        a.run_id = run_id
        a.client_registry_size = registry_size
        a.crossdevice_cohort = cohort
        a.comm_round = rounds
        a.crossdevice_secure_agg = masked
        a.chaos_schedule = schedule
        a.telemetry_dir = _tempfile.mkdtemp(prefix="bench_xdev_td_")
        a.checkpoint_dir = _tempfile.mkdtemp(prefix="bench_xdev_ck_")
        a._validate()
        fedml_tpu.init(a)
        Telemetry.reset()
        reset_chaos()
        t0 = time.perf_counter()
        world = run_beehive_world(
            a, feature_dim=feature_dim, class_num=class_num
        )
        world["wall_s"] = time.perf_counter() - t0
        world["telemetry_dir"] = a.telemetry_dir
        world["checkpoint_dir"] = a.checkpoint_dir
        tel = Telemetry.get_instance(a)
        world["counters"] = {
            name: tel.get_counter(name)
            for name in (
                "device_checkins_total",
                "device_uploads_folded_total",
                "device_uploads_late_total",
                "device_duplicate_uploads_total",
                "device_mask_recoveries_total",
                "device_mask_recovery_failures_total",
            )
        }
        return world

    masked = beehive_world(True, "bench-xdev-masked")
    _progress(
        f"crossdevice masked world: {len(masked['round_records'])} rounds "
        f"in {masked['wall_s']:.1f}s"
    )
    records = masked["round_records"]
    closes_on_target = all(
        rec["close_reason"] == "target" and rec["folds"] >= rec["fold_target"]
        for rec in records
    )
    folds_total = sum(rec["folds"] for rec in records)
    ledger_matches_counters = (
        masked["counters"]["device_uploads_folded_total"] == folds_total
    )
    one_trace_per_shape = masked["trace_count"] == len(masked["shape_keys"])
    checker = InvariantChecker(
        telemetry_dir=masked["telemetry_dir"],
        checkpoint_dir=masked["checkpoint_dir"],
    ).check()
    check_rc = cli_main(
        [
            "check",
            "--telemetry-dir", masked["telemetry_dir"],
            "--checkpoint-dir", masked["checkpoint_dir"],
        ]
    )

    unmasked = beehive_world(False, "bench-xdev-unmasked")
    diff = float(
        np.max(np.abs(masked["final_flat"] - unmasked["final_flat"]))
    )
    _progress(
        f"crossdevice identity: masked vs unmasked max_abs_diff={diff}"
    )

    out = {
        "registry_size": registry_size,
        "cohort": cohort,
        "rounds": rounds,
        "scheduled_vanish_per_round": vanish_per_round,
        "round_records": records,
        "closes_on_target": bool(closes_on_target),
        "folds_per_s": round(folds_total / max(masked["wall_s"], 1e-9), 2),
        "ledger_matches_counters": bool(ledger_matches_counters),
        "mask_recoveries": masked["counters"]["device_mask_recoveries_total"],
        "masked_vs_unmasked_max_abs_diff": diff,
        "trace_count": masked["trace_count"],
        "shape_keys": [list(k) for k in masked["shape_keys"]],
        "one_trace_per_shape": bool(one_trace_per_shape),
        "invariants_ok": bool(checker.ok),
        "check_rc": int(check_rc),
        "counters": masked["counters"],
        "ok": bool(
            closes_on_target
            and ledger_matches_counters
            and one_trace_per_shape
            and diff == 0.0
            and checker.ok
            and check_rc == 0
        ),
    }
    return out


def run_sweep_cohort(c: int) -> dict:
    """One scaling-sweep point (isolated in its own process)."""
    args, dataset, _model, api = _build_api(c, epochs=1, per_client=100)
    rps, spr, _, _ = _time_rounds(api, dataset, args, n_rounds=3)
    _progress(f"sweep cohort {c}: {rps:.3f} rounds/s")
    return {
        "clients": c,
        "rounds_per_sec": round(rps, 4),
        "samples_per_sec": round(rps * spr, 1),
    }


def _run_phase_subprocess(phase_args, timeout_s: float):
    """Run `bench.py --phase ...` in a child; returns (dict|None, note).
    One process owns the chip at a time, so every phase gets a process
    of its own. The child inherits the environment untouched: whoever
    starts the bench places it (``JAX_PLATFORMS``) and its compile cache
    (``JAX_COMPILATION_CACHE_DIR``, core/compile_cache.py)."""
    with tempfile.NamedTemporaryFile("r", suffix=".json", delete=False) as f:
        out_path = f.name
    cmd = [sys.executable, os.path.abspath(__file__)] + phase_args + ["--out", out_path]
    try:
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s
        )
        for line in (r.stderr or "").splitlines():
            print(line, file=sys.stderr, flush=True)
        if r.returncode == 0:
            with open(out_path) as fh:
                return json.load(fh), "ok"
        tail = (r.stderr or r.stdout or "").strip().splitlines()[-1:]
        return None, f"rc={r.returncode}: {tail[0] if tail else ''}"
    except subprocess.TimeoutExpired as te:
        # forward whatever breadcrumbs the child got out before it hung
        partial = te.stderr or b""
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        for line in partial.splitlines()[-20:]:
            print(line, file=sys.stderr, flush=True)
        return None, f"timeout after {timeout_s:.0f}s"
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass


# One window for every phase child: long enough for a cold compile of
# the largest phase, short enough that a hung child ends the bench the
# same day. No phase has been timed on the current chip yet, so there
# is nothing to size per-phase windows from.
_PHASE_TIMEOUT_S = 900.0
# phases whose record lands under detail.<phase> as is, in run order
_DETAIL_PHASES = (
    # compute-dense cohort (ResNet-18/CIFAR-10, bf16): the MFU figure
    "dense",
    # round pipeline at K in {1,2,4}; flight-recorder overhead
    "pipeline", "telemetry",
    # serving plane: latency/throughput per bucket, mesh + fleet variants
    "serving",
    # fault tolerance, tracing, streaming aggregation, defenses, the
    # deterministic chaos plane — contracts measured as numbers
    "chaos", "tracing", "straggler", "defense", "chaosplan",
    # population plane, hierarchical server plane, (data, fsdp) mesh,
    # Beehive check-in plane, elastic-mesh preemption
    "planet", "hier", "multichip", "crossdevice", "elastic",
)
# 512 became feasible when stand-in cohorts moved on-device (the
# cohort is a compute knob now, not a transfer one; 1024 would push
# the vmapped cohort's activations toward the 16 GB HBM ceiling).
_SWEEP_COHORTS = [8, 32, 256, 512]
# forced host devices of a --cpu child: the mesh phase shards over 2
# (more drowns a small box in collective emulation); multichip needs
# the full 8-device (data, fsdp) world, serving 8 for its
# (1,1)-vs-(2,2) submeshes, elastic 8 so the scripted loss is a real
# 8 -> 4 reshape; every other phase runs on 1
_CPU_DEVICES = {"mesh": 2, "multichip": 8, "serving": 8, "elastic": 8}


def main() -> int:
    """The parent: never imports JAX (one process per chip), runs each
    phase child in turn, prints the ONE JSON line, and returns non-zero
    if any child failed. Without a headline there is no line at all."""
    failed = []

    def run(key: str, phase_args=None):
        out, note = _run_phase_subprocess(
            phase_args or ["--phase", key], _PHASE_TIMEOUT_S
        )
        if out is None:
            failed.append({"phase": key, "reason": note})
            _progress(f"{key} FAILED ({note})")
        return out

    result = run("headline")
    if result is None:
        print(
            f"bench: headline phase failed ({failed[0]['reason']}); "
            "no result",
            file=sys.stderr,
        )
        return 1
    detail = result["detail"]
    headline_rps = max(result["value"], 1e-9)

    for key in _DETAIL_PHASES:
        out = run(key)
        if out is not None:
            detail[key] = out

    # scaling sweep, one child per cohort
    scaling = [
        e for c in _SWEEP_COHORTS
        if (e := run(f"sweep[{c}]", ["--phase", "sweep", "--cohort", str(c)]))
        is not None
    ]
    if scaling:
        base = min(scaling, key=lambda e: e["clients"])
        base_sps = max(base["samples_per_sec"], 1e-9)
        for e in scaling:
            e["throughput_retention_vs_base"] = round(
                e["samples_per_sec"] / base_sps, 3
            )
            e["per_client_efficiency"] = round(
                (e["samples_per_sec"] / e["clients"])
                / (base_sps / base["clients"]),
                3,
            )
        detail["scaling"] = scaling
        detail["retention_base_clients"] = base["clients"]

    # mixed-precision point: bf16 vs the f32 headline
    if (out := run("bf16")) is not None:
        out["speedup_vs_f32"] = round(out["rounds_per_sec"] / headline_rps, 2)
        detail["bf16"] = out
    # long-context kernel point: pallas flash attention vs naive XLA
    # attention at T=4096
    if (out := run("longctx")) is not None:
        detail["longctx"] = out
    # mesh-simulator point: the headline cohort through SimulatorMesh
    if (out := run("mesh")) is not None:
        out["vs_vmap_engine"] = round(out["rounds_per_sec"] / headline_rps, 3)
        detail["mesh"] = out

    if failed:
        result["failed_phases"] = failed
    _emit(result)
    return 1 if failed else 0


def _phase_main(argv) -> None:
    """Child entry: run one phase, write its JSON to --out."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--phase", required=True, choices=list(PHASE_CHOICES))
    p.add_argument("--cohort", type=int, default=0)
    # the explicit CPU placement of the CI smoke children: forced host
    # devices and tiny shapes. Without it a CPU platform is an error.
    p.add_argument("--cpu", action="store_true")
    # pipeline phase, CI gate: K=2 only, 6 rounds (seconds, not minutes)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    if a.cpu:
        _force_cpu(_CPU_DEVICES.get(a.phase, 1))
    else:
        import jax

        if jax.default_backend() == "cpu":
            raise RuntimeError(
                f"bench phase {a.phase!r} was started without --cpu but "
                "JAX found only the CPU platform: a rate measured here "
                "would not be a device number. Run on the chip, or pass "
                "--cpu for the CI smoke shapes."
            )
    if a.phase == "headline":
        out = run_headline(on_cpu=a.cpu)
    elif a.phase == "bf16":
        out = run_bf16(on_cpu=a.cpu)
    elif a.phase == "dense":
        out = run_dense(on_cpu=a.cpu)
    elif a.phase == "longctx":
        out = run_longctx(on_cpu=a.cpu)
    elif a.phase == "mesh":
        out = run_mesh(on_cpu=a.cpu)
    elif a.phase == "pipeline":
        out = run_pipeline(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "telemetry":
        out = run_telemetry(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "serving":
        out = run_serving(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "chaos":
        out = run_chaos(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "tracing":
        out = run_tracing(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "straggler":
        out = run_straggler(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "defense":
        out = run_defense(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "chaosplan":
        out = run_chaosplan(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "planet":
        out = run_planet(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "hier":
        out = run_hier(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "multichip":
        out = run_multichip(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "crossdevice":
        out = run_crossdevice(on_cpu=a.cpu, smoke=a.smoke)
    elif a.phase == "elastic":
        out = run_elastic(on_cpu=a.cpu, smoke=a.smoke)
    else:
        out = run_sweep_cohort(a.cohort)
    if isinstance(out, dict):
        # the meta block is attached HERE, once, so every producer —
        # the parent's children and the CI smoke children — names its
        # backend and device_kind without per-phase plumbing
        out.setdefault("meta", _bench_meta(a.phase, a.smoke, out))
    with open(a.out, "w") as fh:
        json.dump(out, fh)


if __name__ == "__main__":
    if "--phase" in sys.argv:
        _phase_main(sys.argv[1:])
    else:
        sys.exit(main())
