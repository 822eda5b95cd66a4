"""The client-side hot loop: jitted, scan-based local training.

TPU-native replacement for the reference's per-client torch loop
(``simulation/single_process/fedavg/my_model_trainer_classification.py:18-93``
— the [HOT LOOP] in SURVEY.md §3.1). Design:

- one ``lax.scan`` over epochs wrapping one step loop over packed
  batches — a single XLA computation per client round, no Python in the
  loop, params never leave the device (the reference round-trips through
  ``.cpu().state_dict()`` every round);
- a step on a fully-masked (padding) batch changes nothing: both params
  and optimizer state are reverted via ``where``, so padded clients match
  the reference's ragged iteration bit-for-bit under any optimizer. That
  revert is the guard; what is *not computed at all* is the tail: handed
  ``steps`` (an upper bound on the index of the last batch that holds a
  sample — ``last_real_step`` reads it from the mask; the round engine
  hands each lane of a ragged cohort its own), the loop ends there, and
  only an empty batch *before* the bound (a hole in the mask) is still
  stepped and reverted. Without ``steps`` the loop is a ``lax.scan``
  over all ``num_batches`` with a static trip count: a client packed to
  its own length has nothing to skip, and a dynamic loop could only
  cost it;
- per-epoch reshuffle over the flattened example axis reproduces
  ``DataLoader(shuffle=True)`` semantics inside jit;
- the returned function is **vmappable over a leading client axis**
  (in_axes: params=None, batches=0, rng=0) — that single property turns
  this one implementation into the sequential simulator (python loop),
  the vectorized simulator (vmap), and the mesh simulator
  (shard_map(vmap)) without code changes;
- optional FedProx proximal term (mu/2 ||w - w_global||^2,
  ``fedprox`` trainer semantics) so FedProx is a config flag, not a fork;
- optional mixed precision (``args.dtype: bfloat16``): the forward/
  backward matmuls run in bf16 — the MXU's native format — while master
  params, optimizer state, the loss reduction, and the prox term stay
  f32 (params are cast INSIDE the loss so autodiff returns f32 grads to
  the f32 master copy; logits are cast back to f32 before the softmax).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from .types import Batches, flat_examples, rebatch

Params = Any

# float16 is deliberately absent: without loss scaling its ~6e-5 normal
# floor flushes small gradients to zero; bf16 keeps f32's exponent range
# and is the MXU's native input format, so it needs no scaling
_DTYPES = {"float32": None, "bfloat16": jnp.bfloat16}


def compute_dtype_from_args(args) -> Optional[Any]:
    """``args.dtype`` -> compute dtype for the hot loop (None = f32,
    i.e. no casting). The single validation choke point for the knob."""
    name = str(getattr(args, "dtype", "float32") or "float32")
    if name not in _DTYPES:
        raise ValueError(
            f"dtype {name!r}: pick one of {sorted(_DTYPES)} (float16 is "
            "unsupported — no loss scaling; use bfloat16 on TPU)"
        )
    return _DTYPES[name]


def _cast_floats(tree: Any, dtype) -> Any:
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else a,
        tree,
    )


def _shuffle_batches(b: Batches, rng: jax.Array) -> Batches:
    """Random permutation of the REAL examples, padding kept compacted
    at the tail: permute, then stable-sort by validity so real examples
    land (in random order) in the leading slots. This preserves the
    reference's ``DataLoader(shuffle=True)`` step count — a client with
    n samples still takes ceil(n/bs) optimizer steps per epoch, and the
    fully-masked tail batches stay no-ops."""
    flat = flat_examples(b)
    n = flat.mask.shape[-1]
    perm = jax.random.permutation(rng, n)
    order = jnp.argsort(1.0 - jnp.take(flat.mask, perm, axis=0), stable=True)
    idx = jnp.take(perm, order, axis=0)
    shuffled = Batches(
        x=jnp.take(flat.x, idx, axis=0),
        y=jnp.take(flat.y, idx, axis=0),
        mask=jnp.take(flat.mask, idx, axis=0),
    )
    return rebatch(shuffled, b.num_batches, b.batch_size)


def last_real_step(mask: jax.Array) -> jax.Array:
    """``[..., num_batches, batch_size]`` mask -> int32 ``[...]``: one
    past the index of the last batch that holds a sample (0 where none
    does). An upper bound on the steps of every epoch with or without
    the reshuffle, which compacts the real samples to the head and so
    only lowers the index: what ``local_train`` takes as ``steps``."""
    nb = mask.shape[-2]
    real = mask.sum(axis=-1) > 0
    return jnp.max(
        jnp.where(real, jnp.arange(1, nb + 1, dtype=jnp.int32), 0), axis=-1
    )


def make_local_train_fn(
    apply_fn: Callable[[Params, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    optimizer: optax.GradientTransformation,
    epochs: int,
    prox_mu: float = 0.0,
    shuffle: bool = True,
    compute_dtype=None,
) -> Callable[[Params, Batches, jax.Array], Tuple[Params, Dict[str, jax.Array]]]:
    """Build ``local_train(params, batches, rng) -> (new_params, metrics)``.

    ``metrics`` carries the last epoch's summed ``loss_sum`` /
    ``correct`` / ``count`` so callers can weight by true sample count.
    ``apply_fn`` may return ``(logits, counters)`` (``FedModel.
    apply_counted``: a dict of float32 scalars, e.g. an expert layer's
    token counts); they are summed over the steps the epoch ran and ride
    in ``metrics`` under their own names.

    ``local_train(params, batches, rng, lr_mult=None, steps=None)``:
    ``steps`` is an int32 scalar, at least ``last_real_step`` of this
    client's mask. Any such bound gives the parameters of the full loop bit
    for bit; the metrics are then summed in the loop's carry, step by
    step (a model's counters over the steps run, so one that counts
    padding batches too reads less).

    Donation contract: the function is pure in its arguments — it never
    aliases ``params`` into its outputs' buffers itself, so the round
    engine may donate the global params/opt-state buffers it closes
    over, and the round-pipeline executor (``core/round_pipeline.py``)
    may keep K dispatched rounds in flight. Metric leaves are f32
    device scalars regardless of ``compute_dtype`` — the deferred-
    metrics ring accumulates them across rounds, and bf16 sums would
    drift.
    """

    def batch_loss(params, global_params, x, y, mask):
        if compute_dtype is not None:
            out = apply_fn(_cast_floats(params, compute_dtype), _cast_floats(x, compute_dtype))
        else:
            out = apply_fn(params, x)
        logits, counters = out if isinstance(out, tuple) else (out, {})
        loss, metrics = loss_fn(logits.astype(jnp.float32), y, mask)
        metrics = {**metrics, "counters": counters}
        if prox_mu > 0.0:
            sq = sum(
                jnp.vdot(p - g, p - g)
                for p, g in zip(jax.tree.leaves(params), jax.tree.leaves(global_params))
            )
            loss = loss + 0.5 * prox_mu * sq
        return loss, metrics

    def local_train(
        params: Params, batches: Batches, rng: jax.Array, lr_mult=None, steps=None
    ):
        global_params = params
        opt_state = optimizer.init(params)

        def train_step(carry, batch):
            p, s = carry
            x, y, m = batch
            # HLO op metadata only (see build_round_fn's scopes)
            with jax.named_scope("fwd_bwd"):
                (loss, metrics), grads = jax.value_and_grad(
                    batch_loss, has_aux=True
                )(p, global_params, x, y, m)
            with jax.named_scope("opt"):
                updates, s_new = optimizer.update(grads, s, p)
                if lr_mult is not None:
                    # round-indexed LR: every _CLIENT_OPTS optimizer ends
                    # in scale_by_learning_rate, so scaling the final
                    # updates == running it with lr * lr_mult this round
                    updates = jax.tree.map(lambda u: u * lr_mult, updates)
                p_new = optax.apply_updates(p, updates)
                nonempty = m.sum() > 0
                p = jax.tree.map(
                    lambda a, b2: jnp.where(nonempty, a, b2), p_new, p
                )
                s = jax.tree.map(
                    lambda a, b2: jnp.where(nonempty, a, b2), s_new, s
                )
            return (p, s), metrics

        def summed(metrics):
            # of one step's metrics, or of a scan's stacked ones
            return {
                "loss_sum": (metrics["loss"] * metrics["count"])
                .sum()
                .astype(jnp.float32),
                "correct": metrics["correct"].sum().astype(jnp.float32),
                "count": metrics["count"].sum().astype(jnp.float32),
                **{k: v.sum().astype(jnp.float32) for k, v in metrics["counters"].items()},
            }

        def epoch(carry, ep_rng):
            b = _shuffle_batches(batches, ep_rng) if shuffle else batches
            data = (b.x, b.y, b.mask)
            # THE place that chooses the loop: a static trip count is a
            # scan over the packed batches, a handed bound a while loop
            # that indexes them (one step body either way)
            if steps is None:
                carry, metrics = jax.lax.scan(train_step, carry, data)
                return carry, summed(metrics)

            def step(i, loop_carry):
                state, sums = loop_carry
                state, m = train_step(
                    state,
                    jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                        data,
                    ),
                )
                return state, jax.tree.map(jnp.add, sums, summed(m))

            # what a step adds, as shapes: the sums start from zero
            zeros = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(
                    lambda c, d: summed(train_step(c, d)[1]),
                    carry,
                    jax.tree.map(lambda a: a[0], data),
                ),
            )
            return jax.lax.fori_loop(0, steps, step, (carry, zeros))

        ep_rngs = jax.random.split(rng, epochs)
        (params, _), per_epoch = jax.lax.scan(epoch, (params, opt_state), ep_rngs)
        last = jax.tree.map(lambda x: x[-1], per_epoch)
        return params, last

    return local_train


# what the round engine adds to a round's summed metrics itself
# (``simulation/fedavg_api.build_round_fn``): no model's counters
LANE_STEPS = ("steps_run", "steps_packed")


def model_counters(summed) -> Dict[str, float]:
    """What a counting model (``FedModel.apply_counted``) added to a round's
    summed training metrics, as host floats: the round's record carries
    them beside the loss. ``summed`` is already on the host or is being
    fetched with the loss at an evaluation round."""
    return {
        k: float(v)  # lint: host-sync-ok — the eval-round fetch, with the loss
        for k, v in summed.items()
        if k not in ("loss_sum", "correct", "count") + LANE_STEPS
    }


def lane_steps(summed) -> Dict[str, float]:
    """The round engine's two counts out of a round's summed metrics, as
    host floats for the round's record (a sequential round has none)."""
    return {k: float(summed[k]) for k in LANE_STEPS if k in summed}  # lint: host-sync-ok — with the loss, as above


def make_eval_fn(
    apply_fn: Callable[[Params, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    compute_dtype=None,
) -> Callable[[Params, Batches], Dict[str, jax.Array]]:
    """Build ``evaluate(params, batches) -> summed metrics`` (scan over
    packed batches; parity with the reference trainers' ``test``,
    my_model_trainer_classification.py:95-154)."""

    def evaluate(params: Params, batches: Batches) -> Dict[str, jax.Array]:
        if compute_dtype is not None:
            params = _cast_floats(params, compute_dtype)

        def step(_, batch):
            x, y, m = batch
            if compute_dtype is not None:
                x = _cast_floats(x, compute_dtype)
            logits = apply_fn(params, x)
            if compute_dtype is not None:
                logits = logits.astype(jnp.float32)
            loss, metrics = loss_fn(logits, y, m)
            out = {
                "loss_sum": (loss * metrics["count"]),
                "correct": metrics["correct"],
                "count": metrics["count"],
            }
            # task-specific extras ride along (tag prediction's tp/fp/fn
            # feed precision/recall/F1 in metrics_from_sums)
            for k in ("tp", "fp", "fn"):
                if k in metrics:
                    out[k] = metrics[k]
            return None, out

        _, out = jax.lax.scan(step, None, (batches.x, batches.y, batches.mask))
        return jax.tree.map(lambda x: x.sum(), out)

    return evaluate
