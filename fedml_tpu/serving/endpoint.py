"""Versioned model endpoint: jit-once forward, zero-recompile hot swap.

The endpoint owns the served params and the jitted forward fn. Two
invariants keep latency flat under continuous retraining:

- **One trace per batch bucket.** The forward fn is jitted once; the
  micro-batcher only ever calls it with power-of-two-bucketed batch
  shapes (``core/bucketing.py`` — the same buckets as the training
  cohort cache), so XLA compiles once per bucket and every later batch
  is a cache hit. The trace-time counter below is the proof: healthy
  runs show exactly one trace per bucket (``trace_counts``), mirroring
  the round engine's ``pipeline_retraces_total`` discipline.
- **Swaps never retrace.** ``swap`` replaces the params pytree
  atomically under a lock, after asserting the new tree has identical
  structure/shapes/dtypes/**shardings** — the jit cache keys on
  abstract values *including placement*, so only a fully
  abstract-identical swap is invisible to XLA. Weights published by the
  round pipeline / ``CheckpointManager`` always satisfy this (same
  model config), and a mismatched tree fails loudly BEFORE any request
  can hit a retrace storm.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..analysis.compiled import auditable, pow2_budget

__all__ = ["ModelEndpoint", "build_forward"]

Params = Any


@auditable(
    "serving.forward",
    census_budget=lambda ctx: pow2_budget(ctx.serve_buckets),
)
def _audit_forward_cases(ctx):
    """`fedml-tpu audit` provider: the EXACT served forward the
    endpoint jits, lowered across the serve-bucket census. No
    donation claim (the served params persist across requests); the
    hot rule proves a request can never stall on a host transfer."""
    from ..analysis.compiled import LoweringCase

    fn = jax.jit(build_forward(ctx.model().apply))
    params = ctx.abstract_params()
    return [
        LoweringCase(
            key=f"b{b}",
            fn=fn,
            args=(params, ctx.sds((b, ctx.feature_dim), "float32")),
        )
        for b in ctx.serve_buckets
    ]


def build_forward(apply_fn, on_trace=None):
    """The served forward pass, as a pure function of the model's
    ``apply``. Module-level so the jitted body never closes over the
    endpoint (mutable-``self`` retrace hazard) and so the
    compiled-artifact auditor can AOT-lower the exact served
    computation across the serve-bucket census without an endpoint.
    ``on_trace(bucket)`` fires at TRACE time only — the per-bucket
    compile-count seam; it is not part of the lowered module. Returns
    the UNjitted function; callers own the ``jax.jit``."""

    def fwd(p, x):
        if on_trace is not None:
            on_trace(int(x.shape[0]))
        return apply_fn(p, x)

    return fwd


def _tree_spec(tree):
    """Structure + per-leaf (shape, dtype, sharding) — metadata only,
    no device reads — for the swap compatibility check. Sharding is
    part of the jit cache key exactly like shape/dtype: a
    differently-placed pytree of identical shapes still retraces, so
    it must fail the swap the same way."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [
        (
            tuple(getattr(a, "shape", ())),
            str(getattr(a, "dtype", type(a).__name__)),
            getattr(a, "sharding", None),
        )
        for a in leaves
    ]


class ModelEndpoint:
    """The served (model, params, version) triple behind the engine."""

    #: serve buckets must be a multiple of this (1 = no constraint;
    #: the mesh endpoint overrides it with the data-axis lane count so
    #: every micro-batch tiles the cohort axis)
    shard_multiple: int = 1

    def __init__(self, model, params: Params, version: int = 0) -> None:
        self.model = model
        self._lock = threading.Lock()
        self._params = self._place(params)
        self.version = int(version)
        self.swaps = 0
        # bucket -> trace count, incremented at TRACE time only (the
        # python body runs when jit retraces) — the compile-count
        # regression surface for tests, like _round_trace_count
        self.trace_counts: Dict[int, int] = {}

        def on_trace(bucket: int) -> None:
            self.trace_counts[bucket] = self.trace_counts.get(bucket, 0) + 1
            from ..core.telemetry import Telemetry

            tel = Telemetry.get_instance()
            if tel.enabled:
                # one per bucket is the expected first compile; more is
                # a retrace storm — visible as a counter and a timeline
                # instant instead of silent latency spikes
                tel.inc("serving_retraces_total", bucket=bucket)
                tel.recorder.instant(
                    "serve.jit_trace", cat="compile", bucket=bucket
                )

        # kept for re-jits (the mesh endpoint's remesh rebuilds the
        # forward over a new mesh through the same trace-count seam)
        self._on_trace = on_trace
        self._fwd = jax.jit(self._build_forward(on_trace))

    def _build_forward(self, on_trace):
        """Hook: the (unjitted) function the endpoint jits. The mesh
        endpoint overrides this with the sharding-constrained mesh
        forward; the trace-count seam stays identical either way."""
        return build_forward(self.model.apply, on_trace)

    # -- placement -----------------------------------------------------
    def _place(self, params: Params) -> Params:
        """Device placement for incoming params — both the initial tree
        and every published swap go through the SAME placement, so the
        sharding half of the swap identity check compares like with
        like. The base endpoint is single-device (``jnp.asarray`` →
        default device); the mesh endpoint overrides this with the
        SpecLayout at-rest placement."""
        return jax.tree.map(jnp.asarray, params)

    # -- inference -----------------------------------------------------
    def params(self) -> Params:
        with self._lock:
            return self._params

    def infer(self, x) -> jax.Array:
        """Forward one (already bucket-padded) batch. The params read
        and the dispatch use the same snapshot — a swap landing midway
        affects the NEXT batch, never tears this one."""
        return self._fwd(self.params(), x)

    # -- hot swap ------------------------------------------------------
    def swap(self, new_params: Params, version: Optional[int] = None) -> int:
        """Atomically replace the served params; returns the new
        version (``version`` or the old version + 1). Raises
        ``ValueError`` when the new tree would change any abstract
        value — the caller published weights for a different model
        config (or a differently-placed tree), which would silently
        retrace every bucket."""
        new_params = self._place(new_params)
        old_def, old_leaves = _tree_spec(self._params)
        new_def, new_leaves = _tree_spec(new_params)
        if old_def != new_def or old_leaves != new_leaves:
            raise ValueError(
                "hot swap rejected: published params do not match the "
                "served model's tree/shapes/dtypes/shardings (a swap "
                "must never retrace). "
                f"served={old_leaves[:3]}... got={new_leaves[:3]}..."
            )
        with self._lock:
            self._params = new_params
            self.version = int(version) if version is not None else self.version + 1  # lint: host-sync-ok — version is the publisher's python int, never a device array
            self.swaps += 1
            v = self.version
        from ..core.telemetry import Telemetry

        tel = Telemetry.get_instance()
        if tel.enabled:
            tel.inc("serving_swaps_total")
            tel.set_gauge("serving_model_version", v)
            tel.recorder.instant("serve.swap", cat="serving", version=v)
        return v

    def swap_from_checkpoint_state(self, state: Dict[str, Any], version: int) -> int:
        """Swap in a ``CheckpointWatcher``-published state dict (the
        round loop's ``{params, server_state, rng, round_idx}``): the
        raw restored params tree is rebuilt onto the served tree's
        structure first, so msgpack'd dicts round-trip cleanly."""
        from flax.serialization import from_state_dict

        restored = from_state_dict(self.params(), state["params"])
        return self.swap(restored, version=version)
