"""Pipeline parallelism: GPipe schedule over a mesh ``pp`` axis.

The reference's only model-partition story is SplitNN/FedGKT activation
exchange over the comm layer — per-batch Python round-trips, no
schedule (SURVEY.md §2.9: "split/pipeline-style model partition only as
SplitNN ... not true PP scheduling"). This is the TPU-native upgrade:
the whole pipeline is ONE jitted SPMD computation under ``shard_map`` —

- stage weights live in stacked arrays (leading axis S) sharded over
  ``pp``: each device holds exactly its stage;
- microbatches stream through a ``lax.scan`` over M + S - 1 ticks; at
  every tick each device runs its stage on what it holds, then the
  activation hops to the next stage via ``lax.ppermute`` (one ICI
  neighbor exchange — no host involvement);
- the classic GPipe bubble (S - 1 idle ticks) is the only overhead;
  arithmetic on garbage ticks is masked out of the result, and because
  masked values never reach the loss, autodiff assigns them zero
  gradient — the backward pass is the mirrored pipeline XLA derives
  from the scan/ppermute transpose rules.

Everything is static-shaped and data-independent: jit traces one tick
body; there is no per-microbatch Python.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage: list) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading axis S."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage)


def split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible by {num_microbatches} microbatches")
    return x.reshape(num_microbatches, B // num_microbatches, *x.shape[1:])


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
    batch_axis: str = None,
) -> jax.Array:
    """Run ``y_i = stage_{S-1}(... stage_0(x_i))`` for microbatches
    ``x: [M, mb, ...]`` on an ``S``-stage pipeline; returns [M, mb, ...].

    ``stage_params`` leaves have leading axis S == mesh.shape[axis];
    ``stage_fn(params_s, h) -> h`` must preserve the activation shape
    (uniform stages — the transformer-block case).

    ``batch_axis`` composes data parallelism with the pipeline: the
    microbatch examples axis (``x`` axis 1) is sharded over that mesh
    axis, so each dp replica streams its own slice through an identical
    pipeline (stage weights replicated across dp — the spec simply
    doesn't mention it); gradient reduction across dp belongs to the
    caller's jit (XLA SPMD inserts it).
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    leading = jax.tree.leaves(stage_params)[0].shape[0]
    if leading != S:
        raise ValueError(f"stage_params leading axis {leading} != pp axis {S}")
    if batch_axis is not None and x.shape[1] % mesh.shape[batch_axis]:
        raise ValueError(
            f"batch_axis {batch_axis}={mesh.shape[batch_axis]} must divide "
            f"microbatch size {x.shape[1]}"
        )
    x_spec = P(None, batch_axis) if batch_axis else P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=x_spec,
    )
    def run(params, x):
        params = jax.tree.map(lambda a: a[0], params)  # this device's stage
        # x arrives replicated (device-invariant); the scan carry is
        # device-varying (each stage holds different activations), so
        # mark everything feeding it as varying over the pp axis
        x = lax.pcast(x, axis, to="varying")
        s = lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(S - 1)]  # non-cyclic: stage s -> s+1

        def tick(carry, t):
            recv, outs = carry
            inp = jnp.where(
                s == 0, lax.dynamic_index_in_dim(x, jnp.minimum(t, M - 1), 0, False), recv
            )
            y = stage_fn(params, inp)
            idx = jnp.clip(t - (S - 1), 0, M - 1)
            outs = jnp.where(
                t >= S - 1, lax.dynamic_update_index_in_dim(outs, y, idx, 0), outs
            )
            return (lax.ppermute(y, axis, perm), outs), None

        outs0 = jnp.zeros_like(x)
        (_, outs), _ = lax.scan(
            tick, (jnp.zeros_like(x[0]), outs0), jnp.arange(M + S - 1)
        )
        # only the last stage holds real outputs; replicate them
        return lax.psum(jnp.where(s == S - 1, outs, jnp.zeros_like(outs)), axis)

    return run(stage_params, x)
