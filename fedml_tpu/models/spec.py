"""Model spec: a flax module + task type + loss, as one handle.

The reference couples models (torch ``nn.Module``) to per-task trainer
classes picked by dataset name (``simulation/single_process/fedavg/
fedavg_api.py:44-60`` choosing classification / nwp / tag-prediction
trainers). Here the coupling is explicit data: ``FedModel`` names the
task, and the functional core looks the loss up in ``core.losses``.
Params are the bare ``variables['params']`` pytree (pure, no mutable
collections — all models use GroupNorm/LayerNorm, never BatchNorm
running stats, so FedAvg averages true parameters only; cf. the
reference's ``vectorize_weight`` BN skip, robust_aggregation.py:30-38).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.losses import LOSSES

Params = Any


def ensure_float(x: jax.Array) -> jax.Array:
    """Promote integer/bool inputs to f32; leave float inputs ALONE.

    Model entry points must not force f32: under mixed precision the
    trainer hands the model bf16 inputs and bf16-cast params, and a
    blanket ``astype(float32)`` silently promotes every conv/matmul
    back to f32 (one bf16 operand + one f32 operand -> f32 compute),
    forfeiting the MXU's 2x bf16 throughput."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.float32)
    return x


@dataclasses.dataclass(frozen=True)
class FedModel:
    name: str
    module: Any  # flax nn.Module
    task: str = "classification"
    example_shape: Tuple[int, ...] = ()  # one example, no batch dim
    example_dtype: Any = jnp.float32

    def init(self, rng: jax.Array, example_x: jax.Array | None = None) -> Params:
        if example_x is None:
            example_x = jnp.zeros((1,) + tuple(self.example_shape), self.example_dtype)
        variables = self.module.init(rng, example_x)
        return variables["params"]

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        return self.module.apply({"params": params}, x)

    def apply_counted(self, params: Params, x: jax.Array):
        """``(logits, counters)``: what the module sowed into its
        ``counters`` collection during this call, as float32 scalars
        summed over its layers (an expert layer's token counts); ``{}``
        from a module that counts nothing, whose lowered program is
        then ``apply``'s. The round engine trains through this one; the
        local trainer sums the counters over a round's steps beside the
        loss."""
        logits, state = self.module.apply({"params": params}, x, mutable=["counters"])
        flat = {}
        for path, value in jax.tree_util.tree_leaves_with_path(state.get("counters", {})):
            name = path[-1].key
            flat[name] = flat.get(name, 0.0) + value
        return logits, flat

    @property
    def loss_fn(self) -> Callable:
        return LOSSES[self.task]

    def param_count(self, params: Params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def metrics_from_sums(self, sums: Dict[str, jax.Array]) -> Dict[str, float]:
        count = float(sums["count"])
        out = {
            "loss": float(sums["loss_sum"]) / max(count, 1.0),
            "count": count,
        }
        if self.task == "tag_prediction" and "tp" in sums:
            tp, fp, fn = float(sums["tp"]), float(sums["fp"]), float(sums["fn"])
            prec = tp / max(tp + fp, 1.0)
            rec = tp / max(tp + fn, 1.0)
            out["precision"] = prec
            out["recall"] = rec
            out["acc"] = 2 * prec * rec / max(prec + rec, 1e-12)
        else:
            out["acc"] = float(sums["correct"]) / max(count, 1.0)
        return out
