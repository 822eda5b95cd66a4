"""Mesh-sharded serving endpoint: serve exactly where we train.

The trained params already live fsdp-sharded at rest under the
canonical ``SpecLayout`` table (``parallel/layout.py``); this endpoint
serves them from that layout instead of gathering them onto one chip —
models bigger than a single chip's HBM become servable, and a round's
published weights land on the serving mesh with zero host round-trips.

Three properties carry over from the training mesh, deliberately:

- **Same constraint discipline.** ``build_mesh_forward`` applies the
  fed-mesh entry rules (``fed_compute_constraints``' serving half):
  params gather REPLICATED (the FSDP at-use gather), the request batch
  and the result shard along ``data``. Per-example compute is never
  tensor-split, so a response is **bitwise identical** across mesh
  shapes — the serving analog of the multichip round identity, held by
  tests/test_serving_fleet.py (``TestMeshEndpoint``).
- **Device-direct publish.** ``restore_target`` hands
  ``CheckpointWatcher`` an abstract state tree whose params leaves
  carry the mesh ``NamedSharding``s, so orbax restores each shard
  straight onto its device (no host gather); ``swap`` then re-places
  through ``shard_tree`` (a no-op for already-placed leaves) and the
  inherited identity check — now covering *sharding* — guarantees the
  swap can never retrace.
- **Version-gated swaps.** Publishes carry the round step as the
  version; a stale explicit version (<= the last published one) is
  dropped and counted (``serving_swaps_rejected_total``), so
  out-of-order deliveries from a republisher can never roll the fleet
  backward. Latest-wins, like the watcher.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from ..analysis.compiled import auditable, pow2_budget
from ..parallel.layout import (
    cohort_axis_size,
    constrain_cohort,
    constrain_replicated,
    is_fed_mesh,
    shard_tree,
    tree_shardings,
)
from .endpoint import ModelEndpoint, build_forward

__all__ = ["MeshModelEndpoint", "build_mesh_forward"]

Params = Any


@auditable(
    "serving.forward_mesh",
    census_budget=lambda ctx: pow2_budget(ctx.serve_buckets),
)
def _audit_mesh_forward_cases(ctx):
    """`fedml-tpu audit` provider: the EXACT mesh-constrained forward
    the endpoint jits, lowered across the serve-bucket census on a
    (data, fsdp) mesh over the visible devices, with the params lowered
    at their at-rest shardings (an unsharded abstract input would lower
    a different module). No donation claim — served params persist; the
    hot rule proves a request can never stall on a host transfer."""
    from ..analysis.compiled import LoweringCase
    from ..parallel.layout import build_fed_mesh

    n = len(jax.devices())
    fsdp = 2 if n % 2 == 0 else 1
    mesh = build_fed_mesh(
        mesh_shape={"data": n // fsdp, "fsdp": fsdp},
        warn_nonpartitionable=False,
    )
    fn = jax.jit(build_mesh_forward(ctx.model().apply, mesh))
    abstract = ctx.abstract_params()
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract,
        tree_shardings(abstract, mesh),
    )
    return [
        LoweringCase(
            key=f"b{b}",
            fn=fn,
            args=(params, ctx.sds((b, ctx.feature_dim), "float32")),
        )
        for b in ctx.serve_buckets
    ]


def build_mesh_forward(apply_fn, mesh, on_trace=None):
    """The mesh-served forward pass: ``build_forward`` plus the fed-mesh
    entry discipline. Params gather replicated (FSDP at-use), the batch
    and the result pin to cohort (``data``) sharding so a downstream
    consumer can never propagate a param-dim sharding backward into the
    per-example compute — the same rule that keeps the training round
    bitwise identical across mesh shapes keeps every served response
    bitwise identical across mesh shapes. Returns the UNjitted
    function; callers own the ``jax.jit``."""
    base = build_forward(apply_fn, on_trace)

    def fwd(p, x):
        p = constrain_replicated(p, mesh)
        x = constrain_cohort(x, mesh)
        return constrain_cohort(base(p, x), mesh)

    return fwd


class MeshModelEndpoint(ModelEndpoint):
    """A ``ModelEndpoint`` whose params live sharded on a named
    (data, fsdp) mesh and whose forward is pjit'd over it."""

    def __init__(self, model, params: Params, mesh, version: int = 0) -> None:
        if not is_fed_mesh(mesh):
            raise ValueError(
                f"MeshModelEndpoint needs a named (data, fsdp) mesh, got "
                f"axes {getattr(mesh, 'axis_names', None)!r} — build one "
                "with parallel.layout.build_fed_mesh"
            )
        self.mesh = mesh
        # serve buckets must tile the data axis so constrain_cohort
        # never sees a ragged leading dim; the engine's micro-batcher
        # reads this and lifts every bucket to a multiple
        self.shard_multiple = cohort_axis_size(mesh)
        self._last_published: Optional[int] = None
        super().__init__(model, params, version=version)

    # -- placement -----------------------------------------------------
    def _place(self, params: Params) -> Params:
        """SpecLayout at-rest placement: fsdp-shard what tiles,
        replicate the rest. For leaves that already carry the right
        ``NamedSharding`` (a device-direct watcher restore) the
        underlying ``device_put`` is a no-op — no host gather, no
        device copy."""
        return shard_tree(params, self.mesh)

    def _build_forward(self, on_trace):
        return build_mesh_forward(self.model.apply, self.mesh, on_trace)

    # -- inference -----------------------------------------------------
    def infer(self, x) -> jax.Array:
        m = self.shard_multiple
        if m > 1 and int(x.shape[0]) % m != 0:
            raise ValueError(
                f"mesh serving batch of {int(x.shape[0])} does not tile "
                f"the data axis ({m} lanes) — bucket micro-batches with "
                f"shard_multiple={m} (the engine does this automatically)"
            )
        return super().infer(x)

    # -- hot swap ------------------------------------------------------
    def swap(self, new_params: Params, version: Optional[int] = None) -> int:
        """Version-gated sharded swap. A stale explicit ``version``
        (<= the last explicitly published one) is dropped — counted,
        never applied — so re-deliveries and out-of-order publishes
        keep latest-wins semantics end to end. Placement + the
        tree/shape/dtype/sharding identity check are inherited."""
        if (
            version is not None
            and self._last_published is not None
            and int(version) <= self._last_published
        ):
            from ..core.telemetry import Telemetry

            tel = Telemetry.get_instance()
            if tel.enabled:
                tel.inc("serving_swaps_rejected_total", reason="stale_version")
            return self.version
        v = super().swap(new_params, version=version)
        if version is not None:
            self._last_published = int(version)
        return v

    # -- elastic re-mesh -----------------------------------------------
    def remesh(self, devices=None, mesh_shape=None) -> None:
        """Rebuild this endpoint over the SURVIVING device set (the
        elastic plane's serving half — a chip died, or the pod shrank):
        a new (data, fsdp) mesh over ``devices``, the served params
        re-placed onto it (``device_put`` reshard — device-to-device
        where the runtime can), and the forward re-jitted over the new
        mesh through the same trace-count seam. The response identity
        across mesh shapes (module docstring) is what makes this safe:
        the re-meshed endpoint answers bitwise identically.

        Caller contract: quiesce the engine first (``stop()`` or
        ``pause()``) — the fleet's ``remesh`` does, shedding queued
        requests counted so the rest of the fleet absorbs the stream
        while this endpoint rebuilds. Counted
        ``serving_remesh_total``."""
        from ..parallel.layout import build_fed_mesh

        new_mesh = build_fed_mesh(devices=devices, mesh_shape=mesh_shape)
        new_fwd = jax.jit(
            build_mesh_forward(self.model.apply, new_mesh, self._on_trace)
        )
        with self._lock:
            params = self._params
        placed = shard_tree(params, new_mesh)
        with self._lock:
            self.mesh = new_mesh
            self.shard_multiple = cohort_axis_size(new_mesh)
            self._params = placed
            self._fwd = new_fwd
        from ..core.telemetry import Telemetry

        tel = Telemetry.get_instance()
        if tel.enabled:
            tel.inc("serving_remesh_total")
            tel.recorder.instant(
                "serve.remesh", cat="serving",
                devices=len(new_mesh.devices.flatten()),
            )

    # -- device-direct publish -----------------------------------------
    def restore_target(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """Build the ``CheckpointWatcher`` restore target from one
        published state tree (the round loop's ``{params, server_state,
        rng, round_idx}``): params leaves become abstract
        ``ShapeDtypeStruct``s carrying the mesh ``NamedSharding``s —
        orbax restores them shard-by-shard onto their devices — while
        the other leaves restore host-side as before."""
        target = dict(state)
        target["params"] = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(
                tuple(a.shape), a.dtype, sharding=sh
            ),
            state["params"],
            tree_shardings(state["params"], self.mesh),
        )
        return target
