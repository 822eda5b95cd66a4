"""``training_type: distributed`` — mesh-parallel LM training through
the one-line API.

The reference has no counterpart (its parallelism vocabulary stops at
FL process-parallelism + in-silo DDP, SURVEY.md §2.9 census); this
scenario is where the framework's green-field parallel subsystems
become user-reachable product: the YAML picks a mesh and the trainer
runs one jitted step over it.

YAML surface::

    common_args: {training_type: distributed}
    train_args:  {mesh_shape: {dp: 2, tp: 2, ep: 2}, epochs: 2, ...}
    model_args:  {model: moe_transformer, ...}
    data_args:   {dataset: shakespeare, ...}

Modes (inferred from the mesh axes):

- **sharded** (axes ⊆ {dp, tp, ep}): one jitted train step; batch over
  ``dp``, Megatron dense layout over ``tp`` (parallel/tensor.py),
  expert stacks over ``ep`` (parallel/expert.py). XLA SPMD inserts the
  collectives; numerics match the single-device program exactly.
- **sequence** ({sp} or {dp, sp}): ring / Ulysses attention
  (parallel/sequence.py) with the token axis sharded over ``sp`` —
  the long-context path; an optional ``dp`` axis shards the batch so
  each replica runs its own sequence collectives. sp must divide the
  sequence length, dp the batch size.
- **pipeline** ({pp} or {dp, pp}): the block stack is cut into pp
  stages and scheduled GPipe-style under shard_map
  (parallel/pipeline.py); the batch is streamed as microbatches, and
  an optional ``dp`` axis shards the examples within every microbatch
  (each dp replica streams its slice through an identical pipeline).
  ``num_layers % pp == 0``.

sp and pp each compose with dp (the batch axis rides untouched through
their shard_maps) but remain exclusive with tp/ep and each other: pp
restructures the program (stage functions under shard_map) and the sp
attention's shard_map pins the head/model axes unsharded, so those
combinations silently degrade to gathers — better to refuse loudly.
dp x tp x ep compose freely.

Training data: the dataset's global packed batches (``[nb, bs, T]``
int tokens) — this is centralized mesh training, the "distributed"
platform of the reference's vocabulary, not federated averaging.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .core.local_trainer import _cast_floats, compute_dtype_from_args
from .core.optimizers import create_client_optimizer
from .parallel.expert import shard_params_tp_ep
from .parallel.mesh import build_mesh

_SHARDED_AXES = {"dp", "tp", "ep"}
_ALL_AXES = _SHARDED_AXES | {"sp", "pp"}


def _resolve_mesh(args) -> Mesh:
    devices = jax.devices()
    shape = getattr(args, "mesh_shape", None)
    if not shape:
        shape = {"dp": len(devices)}
    shape = {str(k): int(v) for k, v in dict(shape).items()}
    unknown = set(shape) - _ALL_AXES
    if unknown:
        raise ValueError(
            f"mesh_shape axes {sorted(unknown)} unknown; pick from {sorted(_ALL_AXES)}"
        )
    for special in ("sp", "pp"):
        if special in shape and not set(shape) <= {special, "dp"}:
            raise ValueError(
                f"mesh axis {special!r} composes only with 'dp' (its "
                f"shard_map program pins the other axes); got {shape}"
            )
    n = int(np.prod(list(shape.values())))
    if n > len(devices):
        raise ValueError(f"mesh_shape {shape} needs {n} devices, have {len(devices)}")
    if jax.process_count() > 1 and n != len(devices):
        # a device subset could exclude every addressable device of
        # some process, which then holds no shard of anything — refuse
        # loudly
        raise ValueError(
            f"multi-controller run ({jax.process_count()} processes): "
            f"mesh_shape {shape} must span all {len(devices)} global "
            f"devices, not {n}"
        )
    return build_mesh(devices=devices[:n], mesh_shape=shape)


class DistributedTrainer:
    """One-line distributed LM training over a device mesh."""

    def __init__(self, args, device=None, dataset=None, model=None) -> None:
        self.args = args
        self.dataset = dataset
        self.model = model
        self.mesh = _resolve_mesh(args)
        axes = set(self.mesh.axis_names)
        self.mode = (
            "pipeline" if "pp" in axes
            else "sequence" if "sp" in axes
            else "sharded"
        )
        self.compute_dtype = compute_dtype_from_args(args)
        self.optimizer = create_client_optimizer(args)
        from .core.telemetry import Telemetry
        from .core.tracking import MetricsReporter, ProfilerEvent

        self.metrics_reporter = MetricsReporter(args)
        # the epoch loop's phase spans (run()) land in the process-wide
        # flight recorder, as the round loops' do
        self.profiler = ProfilerEvent(args)
        Telemetry.get_instance(args).attach_profiler(self.profiler)
        init_rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
        # distinct stream for the per-epoch shuffle permutations
        self._shuffle_key = jax.random.fold_in(init_rng, 0x51)
        builder = getattr(self, f"_build_{self.mode}")
        builder(init_rng)
        # checkpoint/resume (core/checkpoint.py): save {params,
        # opt_state, epoch}; a restarted process resumes mid-training
        # with the restored leaves placed back onto this mode's
        # shardings. Single-controller saves host copies; under
        # multi-controller the leaves stay (possibly non-addressable)
        # jax.Arrays and orbax writes/reads each process's shards
        # collectively
        self._ckpt = None
        self._start_epoch = 0
        ckpt_dir = getattr(args, "checkpoint_dir", None)
        if ckpt_dir:
            from flax.serialization import from_state_dict, to_state_dict

            from .core.checkpoint import RoundCheckpointer
            from .parallel.mesh import is_multi_controller

            multihost = is_multi_controller(self.mesh)
            self._ckpt = RoundCheckpointer(ckpt_dir, multihost=multihost)
            # None = this scenario's historical cadence (every epoch)
            self._ckpt_freq = max(
                1, int(getattr(args, "checkpoint_freq", None) or 1)
            )

            def norm_sharding(c):
                # mesh-placed leaves keep their layout; leaves optax
                # created fresh (adam's scalar count has a single-device
                # sharding) go in replicated — committing them to one
                # device would conflict with the mesh-sharded params
                # under jit
                s = c.sharding if isinstance(
                    c.sharding, NamedSharding
                ) else NamedSharding(self.mesh, P())
                return jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=s)

            # sharding-targeted restore: leaves land directly on this
            # mode's mesh layout. Under multi-controller every process
            # participates and reads only its shards (orbax collective)
            # — the state-dict view keeps optax namedtuple fields
            # name-paired, not positionally zipped.
            target = {
                "params": jax.tree.map(norm_sharding, self.params),
                "opt_state": jax.tree.map(
                    norm_sharding, to_state_dict(self.opt_state)
                ),
                "epoch": 0,
            }
            state = self._ckpt.restore(target=target)
            if state is not None:
                self._start_epoch = int(state["epoch"]) + 1
                self.params = state["params"]
                self.opt_state = from_state_dict(
                    self.opt_state, state["opt_state"]
                )
                logging.info(
                    "distributed trainer resumed at epoch %d from %s",
                    self._start_epoch, ckpt_dir,
                )

    # -- shared pieces -------------------------------------------------
    def _check_dp_divides_batch(self) -> None:
        """Every mode with a dp axis shards the batch over it."""
        if "dp" not in self.mesh.axis_names:
            return
        bs = int(self.dataset.train_data_global.x.shape[1])
        dp = self.mesh.shape["dp"]
        if bs % dp:
            raise ValueError(f"mesh axis dp={dp} must divide batch_size {bs}")

    def _loss(self, logits, y, mask):
        loss, metrics = self.model.loss_fn(logits.astype(jnp.float32), y, mask)
        return loss, metrics

    def _apply_with_aux(self, params, x):
        """Forward that also surfaces the Switch load-balancing aux
        loss (models/moe.py sows it): returns (logits, mean aux). A
        model with no routed layers yields aux = 0 — the mutable apply
        costs nothing there."""
        logits, mods = self.model.module.apply(
            {"params": params}, x, mutable=["intermediates"]
        )
        auxes = [
            leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                mods.get("intermediates", {})
            )[0]
            if any(getattr(k, "key", None) == "moe_aux_loss" for k in path)
        ]
        aux = sum(auxes) / len(auxes) if auxes else jnp.float32(0.0)
        return logits, aux

    def _epoch_scanner(self, apply_fn):
        """(params, opt_state, batches) -> scan of optimizer steps.
        ``apply_fn(p, x) -> (logits, aux)``; the Switch aux loss rides
        into the optimized objective with weight ``moe_aux_weight``
        (the reported per-batch loss stays the pure cross-entropy).

        ``grad_accum_steps > 1`` splits each batch into chunks whose
        gradients accumulate (weighted by their masked token counts, so
        the result is EXACTLY the full-batch masked-mean gradient)
        before one optimizer update — the HBM lever when a batch's
        activations don't fit. With MoE the router sees chunk-sized
        token pools, so capacity granularity shrinks accordingly.
        """
        optimizer = self.optimizer
        dtype = self.compute_dtype
        # defaults live in arguments._DEFAULTS; fall back to disabled
        # for args objects built outside the Arguments layer
        aux_w = float(getattr(self.args, "moe_aux_weight", 0.0) or 0.0)
        accum = int(getattr(self.args, "grad_accum_steps", 1) or 1)
        if accum < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {accum}")

        def loss_fn(p, x, y, m):
            if dtype is not None:
                p = _cast_floats(p, dtype)
                x = _cast_floats(x, dtype)
            logits, aux = apply_fn(p, x)
            loss, metrics = self._loss(logits, y, m)
            return loss + aux_w * aux.astype(jnp.float32), metrics

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def batch_grads(params, x, y, m):
            """(grads, metrics) for one batch, chunked when accum>1."""
            if accum <= 1:
                (_, metrics), grads = grad_fn(params, x, y, m)
                return grads, metrics
            if x.shape[0] % accum:
                raise ValueError(
                    f"grad_accum_steps={accum} must divide batch_size "
                    f"{x.shape[0]}"
                )

            def split(a):
                return a.reshape(accum, a.shape[0] // accum, *a.shape[1:])

            def chunk(carry, ch):
                gsum, lsum, csum, nsum = carry
                cx, cy, cm = ch
                (_, metrics), grads = grad_fn(params, cx, cy, cm)
                w = metrics["count"]
                gsum = jax.tree.map(lambda g_, gs: gs + g_ * w, grads, gsum)
                return (
                    gsum,
                    lsum + metrics["loss"] * w,
                    csum + metrics["correct"],
                    nsum + w,
                ), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (gsum, lsum, csum, nsum), _ = jax.lax.scan(
                chunk,
                (zeros, jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0)),
                (split(x), split(y), split(m)),
            )
            denom = jnp.maximum(nsum, 1.0)
            grads = jax.tree.map(lambda gs: gs / denom, gsum)
            return grads, {
                "loss": lsum / denom, "correct": csum, "count": nsum,
            }

        def step(carry, batch):
            params, opt_state = carry
            x, y, m = batch
            grads, metrics = batch_grads(params, x, y, m)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), metrics

        shuffle = bool(getattr(self.args, "shuffle", True))

        def epoch(params, opt_state, batches, rng):
            if shuffle:
                from .core.local_trainer import _shuffle_batches

                batches = _shuffle_batches(batches, rng)
            (params, opt_state), metrics = jax.lax.scan(
                step, (params, opt_state), (batches.x, batches.y, batches.mask)
            )
            return params, opt_state, {
                "loss_sum": (metrics["loss"] * metrics["count"]).sum(),
                "correct": metrics["correct"].sum(),
                "count": metrics["count"].sum(),
            }

        return epoch

    # -- sharded: dp x tp x ep ----------------------------------------
    def _build_sharded(self, init_rng) -> None:
        self._check_dp_divides_batch()
        params = self.model.init(init_rng)
        self.params = shard_params_tp_ep(params, self.mesh)
        self.opt_state = self.optimizer.init(self.params)
        from .parallel.mesh import place_global

        batch_spec = P(None, "dp") if "dp" in self.mesh.axis_names else P()
        self._place_data = lambda b: jax.tree.map(
            lambda a: place_global(a, NamedSharding(self.mesh, batch_spec)), b
        )
        self._epoch = jax.jit(
            # carried (params, opt_state) donated: the epoch loop
            # rebinds both every call, so XLA updates in place
            # instead of copying the model per epoch (audited)
            self._epoch_scanner(self._apply_with_aux),
            donate_argnums=(0, 1),
        )
        self._eval_apply = self.model.apply

    # -- sequence: sp (ring / Ulysses attention) ----------------------
    def _build_sequence(self, init_rng) -> None:
        import dataclasses

        from .parallel.sequence import make_sequence_sharded_attention

        module = self.model.module
        if not hasattr(module, "attn_fn"):
            raise ValueError(
                f"model {self.model.name!r} has no pluggable attention; "
                "sequence parallelism needs the transformer family"
            )
        sp = self.mesh.shape["sp"]
        has_dp = "dp" in self.mesh.axis_names
        strategy = str(getattr(self.args, "sp_strategy", "ring") or "ring")
        ring_bk = getattr(self.args, "sp_ring_block", None)
        attn = make_sequence_sharded_attention(
            self.mesh, strategy=strategy, causal=True,
            batch_axis="dp" if has_dp else None,
            ring_block_k=int(ring_bk) if ring_bk else None,
        )
        sp_module = module.clone(attn_fn=attn)
        self.model = dataclasses.replace(self.model, module=sp_module)
        seq_len = int(self.dataset.train_data_global.x.shape[-1])
        if seq_len % sp:
            raise ValueError(f"mesh axis sp={sp} must divide seq_len {seq_len}")
        self._check_dp_divides_batch()
        # example batch = dp size: the attention shard_map inside the
        # module requires the batch axis divisible by dp even at init
        params = self.model.init(
            init_rng,
            example_x=jnp.zeros(
                (self.mesh.shape.get("dp", 1), seq_len), jnp.int32
            ),
        )
        from .parallel.mesh import replicate

        self.params = replicate(params, self.mesh)
        self.opt_state = self.optimizer.init(self.params)
        # x/y [nb, bs, T]: token axis over sp, batch over dp when
        # present; the per-example mask [nb, bs] (and any rank<3 leaf)
        # shards over dp only — the attention shard_map pins the
        # head/model axes anyway
        from .parallel.mesh import place_global

        batch = "dp" if has_dp else None

        def place(b):
            return jax.tree.map(
                lambda a: place_global(
                    a,
                    NamedSharding(
                        self.mesh,
                        P(None, batch, "sp") if a.ndim >= 3
                        else P(None, batch) if a.ndim == 2
                        else P(),
                    ),
                ),
                b,
            )

        self._place_data = place
        self._epoch = jax.jit(
            # carried (params, opt_state) donated: the epoch loop
            # rebinds both every call, so XLA updates in place
            # instead of copying the model per epoch (audited)
            self._epoch_scanner(self._apply_with_aux),
            donate_argnums=(0, 1),
        )
        self._eval_apply = self.model.apply

    # -- pipeline: pp (GPipe over the block stack) --------------------
    def _build_pipeline(self, init_rng) -> None:
        from .models.transformer import TransformerLM
        from .parallel.pipeline import stack_stage_params

        module = self.model.module
        if type(module) is not TransformerLM:
            raise ValueError(
                f"pipeline mode supports the plain TransformerLM block "
                f"stack, got {type(module).__name__}"
            )
        S = self.mesh.shape["pp"]
        L = int(module.num_layers)
        if L % S:
            raise ValueError(f"pp={S} must divide num_layers {L}")
        self._layers_per_stage = L // S
        self._pp_module = module
        params = self.model.init(
            init_rng, example_x=jnp.zeros((1, 8), jnp.int32)
        )
        blocks = [params[f"Block_{i}"] for i in range(L)]
        # [S, L/S, ...] — stage-major stacking
        stages = stack_stage_params(
            [
                jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *blocks[s * self._layers_per_stage:(s + 1) * self._layers_per_stage],
                )
                for s in range(S)
            ]
        )
        outer = {k: v for k, v in params.items() if not k.startswith("Block_")}
        # _pp_apply mirrors TransformerLM.__call__'s embed/head halves;
        # refuse loudly if the model grows top-level params this mirror
        # doesn't know about (silent divergence otherwise)
        expected = {"Embed_0", "Embed_1", "LayerNorm_0", "Dense_0"}
        if set(outer) != expected:
            raise ValueError(
                "pipeline mode mirrors TransformerLM's embed/head "
                f"structure; unexpected params: {sorted(set(outer) ^ expected)}"
            )
        self.params = {"outer": outer, "stages": stages}
        self.opt_state = self.optimizer.init(self.params)
        from .parallel.mesh import place_global

        has_dp = "dp" in self.mesh.axis_names
        self._check_dp_divides_batch()
        # batch axis (leaf axis 1: [nb, bs, ...]) over dp when present;
        # the pipeline shard_map streams each dp slice independently
        self._place_data = lambda b: jax.tree.map(
            lambda a: place_global(
                a,
                NamedSharding(
                    self.mesh,
                    P(None, "dp") if has_dp and a.ndim >= 2 else P(),
                ),
            ),
            b,
        )
        self._epoch = jax.jit(
            self._epoch_scanner(
                # pp rejects MoE modules, so there is no aux loss here
                lambda p, x: (self._pp_apply(p, x), jnp.float32(0.0))
            ),
            # same carried-state donation contract as the other builds
            donate_argnums=(0, 1),
        )
        self._eval_apply = self._pp_apply

    def _pp_apply(self, params, tokens):
        """TransformerLM forward with the block stack pipelined.
        Mirrors ``TransformerLM.__call__`` (embed -> blocks -> LN ->
        head) with the middle replaced by the GPipe schedule; the
        embed/LN/head math is flax's own layer modules applied to the
        original param subtrees, and the structure mirror is guarded by
        the ``expected`` check in ``_build_pipeline``."""
        import flax.linen as nn

        from .models.transformer import Block, resolve_attention
        from .parallel.pipeline import pipeline_apply, split_microbatches

        m = self._pp_module
        outer, stages = params["outer"], params["stages"]
        attn = m.attn_fn or resolve_attention(m.attention)
        block = Block(num_heads=m.num_heads, attn_fn=attn)
        B, T = tokens.shape
        x = nn.Embed(m.vocab_size, m.embed_dim).apply(
            {"params": outer["Embed_0"]}, tokens.astype(jnp.int32)
        )
        pos = nn.Embed(m.max_len, m.embed_dim).apply(
            {"params": outer["Embed_1"]}, jnp.arange(T)
        )
        x = x + pos[None]

        def one_block(h, bp):
            return block.apply({"params": bp}, h), None

        if m.remat:
            # honor the model's remat flag in the pipelined stack too:
            # recompute each block's activations in the backward pass
            one_block = jax.checkpoint(one_block)

        def stage_fn(stage_params, h):
            h, _ = jax.lax.scan(one_block, h, stage_params)
            return h

        dp = self.mesh.shape.get("dp", 1)
        micro = int(getattr(self.args, "pp_microbatches", 0) or 0)
        if micro <= 0:
            # microbatch size must also split across the dp replicas
            micro = min(B // dp if B >= dp else B, max(2 * self.mesh.shape["pp"], 1))
            while micro > 1 and (B % micro or (B // micro) % dp):
                micro -= 1
        out = pipeline_apply(
            stage_fn, stages, split_microbatches(x, micro), self.mesh,
            batch_axis="dp" if dp > 1 else None,
        )
        x = out.reshape(B, T, -1)
        x = nn.LayerNorm().apply({"params": outer["LayerNorm_0"]}, x)
        return nn.Dense(m.vocab_size).apply({"params": outer["Dense_0"]}, x)

    # -- run loop ------------------------------------------------------
    def run(self) -> Dict[str, float]:
        args, ds = self.args, self.dataset
        train = self._place_data(ds.train_data_global)
        test = self._place_data(ds.test_data_global)
        epochs = int(getattr(args, "epochs", 1))
        stats: Dict[str, float] = {}
        eval_every = int(getattr(args, "frequency_of_the_test", 1) or 1)
        from .core.tracking import device_trace

        try:
            if self._start_epoch > 0 and self._start_epoch >= epochs:
                # resumed from a checkpoint taken at/after the final
                # epoch: nothing left to train, produce the terminal eval
                logging.info(
                    "resumed at epoch %d >= epochs %d; evaluating only",
                    self._start_epoch, epochs,
                )
                with self.mesh:
                    stats = {"epoch": epochs - 1, **self._evaluate(test)}
                self.metrics_reporter.report(
                    {"kind": "distributed_train", **stats}
                )
                return stats
            # phase spans (docs/observability.md): `epoch` and its
            # children tile the loop, so a stalled epoch says which
            # phase stalled, with gc, compile and steal beside it
            span = self.profiler.span
            with device_trace(args), self.mesh, self.profiler.watch_stalls():
                for ep in range(self._start_epoch, epochs):
                    with self.profiler.iteration_span("epoch", epoch=ep):
                        with span("epoch.place"):
                            t0 = time.perf_counter()
                            # epoch-INDEXED stream (fold_in, not
                            # sequential split): a resumed run replays
                            # exactly the permutations the interrupted
                            # run would have used; every process derives
                            # the same host value, so the shuffle is
                            # multi-controller consistent
                            ep_rng = np.asarray(
                                jax.random.fold_in(self._shuffle_key, ep)
                            )
                        with span("epoch.dispatch"):
                            self.params, self.opt_state, sums = self._epoch(
                                self.params, self.opt_state, train, ep_rng
                            )
                        with span("epoch.wait"):
                            jax.block_until_ready(jax.tree.leaves(self.params)[0])
                            dt = time.perf_counter() - t0
                        with span("epoch.fetch"):
                            train_m = self.model.metrics_from_sums(
                                jax.tree.map(np.asarray, sums)
                            )
                        stats = {
                            "epoch": ep,
                            "train_loss": train_m["loss"],
                            "train_acc": train_m["acc"],
                            "epoch_time_s": dt,
                            "tokens_per_sec": train_m["count"] / max(dt, 1e-9),
                        }
                        if (ep + 1) % eval_every == 0 or ep == epochs - 1:
                            with span("epoch.eval"):
                                stats.update(self._evaluate(test))
                        with span("epoch.report"):
                            self.metrics_reporter.report(
                                {"kind": "distributed_train", **stats}
                            )
                            logging.info("distributed epoch %d: %s", ep, stats)
                        if self._ckpt and (
                            (ep + 1) % self._ckpt_freq == 0 or ep == epochs - 1
                        ):
                            with span("epoch.ckpt"):
                                from flax.serialization import to_state_dict

                                self._ckpt.save(
                                    ep,
                                    {
                                        "params": self.params,
                                        "opt_state": to_state_dict(self.opt_state),
                                        "epoch": ep,
                                    },
                                )
        finally:
            if self._ckpt is not None:
                self._ckpt.close()
        return stats

    def _evaluate(self, test) -> Dict[str, float]:
        from .core.local_trainer import make_eval_fn

        if not hasattr(self, "_eval_jit"):
            self._eval_jit = jax.jit(
                make_eval_fn(
                    self._eval_apply, self.model.loss_fn,
                    compute_dtype=self.compute_dtype,
                )
            )
        m = self.model.metrics_from_sums(
            jax.tree.map(np.asarray, self._eval_jit(self.params, test))
        )
        return {"test_loss": m["loss"], "test_acc": m["acc"]}
