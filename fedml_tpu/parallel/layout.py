"""Canonical ``(data, fsdp)`` federation mesh + PartitionSpec layout.

The legacy mesh simulator (``parallel/mesh.py``) names its axes
``(clients, data)`` and only ever shards the cohort — params ride
replicated, so the largest trainable model is whatever fits one chip's
HBM. This module is the production vocabulary (ROADMAP item 1,
"Automatic Cross-Replica Sharding of Weight Update" 2004.13336):

- ``data``  — the cohort axis. The sampled clients' batches shard
  along it; each lane trains a disjoint slice of the cohort.
- ``fsdp``  — the parameter axis. Params and server-optimizer state
  are sharded AT REST along it (each chip holds ``1/fsdp`` of the
  model) and gathered at use, ZeRO-3 style — which is what unlocks
  models larger than one chip's HBM while keeping per-client compute
  bitwise identical to the single-chip run (no tensor-parallel
  partial-sum reductions are ever introduced; see
  ``simulation/fedavg_api.build_round_fn``).

The layout table is a ``SpecLayout`` (SNIPPETS [2]): one canonical
PartitionSpec per PARAMETER CLASS, where the class of a leaf is a pure
function of its name and rank (``classify_param``). The frame models'
whole vocabulary is four classes (``dense_kernel`` / ``conv_kernel`` /
``embedding`` / ``vector``, plus rank-0 ``scalar`` for optimizer
counts); an unknown leaf fails LOUDLY — silently replicating a new
parameter family would quietly forfeit the HBM win.

A spec whose fsdp axis does not divide the leaf's sharded dimension
degrades to replication for that leaf (SNIPPETS [3] ``shard_params``):
layout is a placement choice and must never constrain model geometry.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional, Sequence, Tuple

import numpy as np

Params = Any

# the fed-mesh axis vocabulary; a mesh carrying BOTH names is a fed
# mesh (is_fed_mesh) and routes every placement through this module
AXIS_COHORT = "data"
AXIS_PARAM = "fsdp"

# the closed parameter-class vocabulary of the frame models
# (models/*.py: flax leaves are kernel/embedding/bias/scale; optimizer
# state mirrors param shapes plus rank-0 counts)
PARAM_CLASSES = (
    "dense_kernel",  # rank >= 2 'kernel' (Dense / DenseGeneral)
    "conv_kernel",   # rank-4 'kernel' (Conv HWIO)
    "embedding",     # 'embedding' tables (vocab x width)
    "vector",        # rank-1 bias / norm scale
    "scalar",        # rank-0 (optax counts, schedules)
)


def classify_param(name: str, ndim: int) -> str:
    """Leaf (name, rank) -> parameter class. LOUD on unknowns: a new
    parameter family must be added to the layout table deliberately,
    not silently replicated."""
    if ndim == 0:
        return "scalar"
    if ndim == 1:
        return "vector"
    if name == "embedding":
        return "embedding"
    if name == "kernel":
        return "conv_kernel" if ndim == 4 else "dense_kernel"
    raise ValueError(
        f"unknown parameter class for leaf {name!r} (rank {ndim}): not in "
        f"the layout vocabulary {PARAM_CLASSES} — add a canonical "
        "PartitionSpec for this family to parallel/layout.SpecLayout"
    )


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs per parameter class on a (data, fsdp)
    mesh (SNIPPETS [2] ``SpecLayout``). One table, consulted by the
    round engine, the planet group fn, the simulators' placement and
    the layout tests — never re-derived ad hoc at a call site."""

    data_axis: str = AXIS_COHORT
    fsdp_axis: str = AXIS_PARAM

    def dense_kernel(self, ndim: int = 2):
        """[in, out] (or DenseGeneral [..., out]): shard the leading
        (reduction) axis at rest; gathered at use, so the matmul itself
        is never tensor-split."""
        from jax.sharding import PartitionSpec as P

        return P(self.fsdp_axis, *(None,) * (ndim - 1))

    def conv_kernel(self, ndim: int = 4):
        """HWIO: shard output channels — the largest axis of every
        frame conv and the one FSDP gathers cheapest."""
        from jax.sharding import PartitionSpec as P

        return P(*(None,) * (ndim - 1), self.fsdp_axis)

    def embedding(self, ndim: int = 2):
        """[vocab, width]: shard the vocab rows."""
        from jax.sharding import PartitionSpec as P

        return P(self.fsdp_axis, *(None,) * (ndim - 1))

    def vector(self, ndim: int = 1):
        from jax.sharding import PartitionSpec as P

        return P()

    def scalar(self, ndim: int = 0):
        from jax.sharding import PartitionSpec as P

        return P()

    def spec_for(self, cls: str, ndim: int):
        """Parameter class -> canonical PartitionSpec (validated
        against PARAM_CLASSES — the loud-unknown contract)."""
        if cls not in PARAM_CLASSES:
            raise ValueError(
                f"unknown parameter class {cls!r}; the layout table "
                f"covers {PARAM_CLASSES}"
            )
        return getattr(self, cls)(ndim)

    def cohort(self, ndim: int):
        """Cohort-shaped leaves [C, ...]: client axis over ``data``,
        everything within a client unsharded."""
        from jax.sharding import PartitionSpec as P

        return P(self.data_axis, *(None,) * (ndim - 1))

    def sharded_axis(self, cls: str, ndim: int) -> Optional[int]:
        """Which axis the class shards (None = replicated) — the
        divisibility check and the tests read the table through this."""
        spec = self.spec_for(cls, ndim)
        for i, s in enumerate(spec):
            if s is not None:
                return i
        return None


def _leaf_name(path) -> str:
    """Last dict key on a tree path ('' for bare leaves — classified
    by rank alone, the optimizer-state case)."""
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return ""


def param_spec(
    layout: SpecLayout, name: str, shape: Tuple[int, ...], fsdp_size: int
):
    """Canonical spec for one leaf, degraded to replication when the
    fsdp axis does not divide the sharded dimension (SNIPPETS [3]):
    placement must never constrain model geometry."""
    cls = classify_param(name, len(shape))
    spec = layout.spec_for(cls, len(shape))
    axis = layout.sharded_axis(cls, len(shape))
    if axis is not None and shape[axis] % max(fsdp_size, 1) != 0:
        return layout.vector()  # P(): replicated
    return spec


def tree_specs(tree: Params, mesh, layout: Optional[SpecLayout] = None):
    """Param pytree -> pytree of PartitionSpecs via the layout table.
    Works on concrete arrays and ShapeDtypeStructs alike (shapes only).
    """
    import jax

    layout = layout or SpecLayout()
    fsdp = int(mesh.shape.get(layout.fsdp_axis, 1))
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: param_spec(
            layout, _leaf_name(p), tuple(np.shape(leaf)), fsdp
        ),
        tree,
    )


def tree_shardings(tree: Params, mesh, layout: Optional[SpecLayout] = None):
    """Param pytree -> pytree of NamedShardings (the placement form of
    :func:`tree_specs`)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree_specs(tree, mesh, layout),
        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)),
    )


def shard_tree(tree: Params, mesh, layout: Optional[SpecLayout] = None) -> Params:
    """Place a param/optimizer pytree on the mesh per the layout table
    — FSDP at-rest sharding. Single- and multi-controller (reuses
    ``parallel.mesh.place_global``'s placement seam)."""
    import jax
    from jax.sharding import NamedSharding

    from .mesh import _put, is_multi_controller

    layout = layout or SpecLayout()
    fsdp = int(mesh.shape.get(layout.fsdp_axis, 1))
    multi = is_multi_controller(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: _put(
            leaf,
            NamedSharding(
                mesh,
                param_spec(layout, _leaf_name(p), tuple(np.shape(leaf)), fsdp),
            ),
            multi,
        ),
        tree,
    )


def constrain_tree(tree: Params, mesh, layout: Optional[SpecLayout] = None) -> Params:
    """In-jit: pin a param-shaped pytree to the layout's at-rest
    shardings (``with_sharding_constraint``). The round engine applies
    this to the aggregated output so the new global params land
    fsdp-sharded without a reshard after the fact."""
    import jax
    from jax.sharding import NamedSharding

    layout = layout or SpecLayout()
    fsdp = int(mesh.shape.get(layout.fsdp_axis, 1))
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: jax.lax.with_sharding_constraint(
            leaf,
            NamedSharding(
                mesh,
                param_spec(layout, _leaf_name(p), tuple(np.shape(leaf)), fsdp),
            ),
        ),
        tree,
    )


def constrain_cohort(tree: Params, mesh, layout: Optional[SpecLayout] = None) -> Params:
    """In-jit: shard cohort-shaped leaves [C, ...] along ``data``."""
    import jax
    from jax.sharding import NamedSharding

    layout = layout or SpecLayout()
    return jax.tree.map(
        lambda leaf: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, layout.cohort(leaf.ndim))
        ),
        tree,
    )


def constrain_replicated(tree: Params, mesh) -> Params:
    """In-jit: gather a pytree replicated — the FSDP all-gather at use.

    Per-client local training runs against the FULL parameter tree on
    every data lane (each lane trains its cohort slice with identical
    per-client HLO), which is what keeps the mesh round bitwise
    identical to the single-chip vmap path: no cross-client or
    cross-shard reduction is introduced anywhere in a client's
    compute."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(
        lambda leaf: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, P())
        ),
        tree,
    )


def fed_compute_constraints(mesh, params: Params, cohort: Params, *aux):
    """THE fed-mesh in-jit entry discipline, in one place (shared by
    ``fedavg_api.build_round_fn`` and ``scale.engine.build_group_fn``
    — the bitwise-identity proof depends on both engines applying the
    identical sequence, so it must never be hand-synchronized):

    - ``cohort`` (leading client axis) shards along ``data``;
    - ``params`` gather REPLICATED — the FSDP at-use gather, so every
      client's local training runs whole on its lane, never
      tensor-split;
    - every ``aux`` leaf (sample counts, validity masks, routing
      one-hots) gathers replicated too, so weight normalization sees
      lane-invariant bits.

    Returns ``(params, cohort, aux...)``. Pair with
    :func:`pin_cohort_outputs` on the vmap result."""
    out_aux = constrain_replicated(aux, mesh) if aux else ()
    return (
        constrain_replicated(params, mesh),
        constrain_cohort(cohort, mesh),
        *out_aux,
    )


def pin_cohort_outputs(mesh, stacked: Params) -> Params:
    """Pin per-client vmap outputs to cohort-only sharding: a
    downstream fsdp constraint (the aggregated carry, the groupwise
    einsum) must not propagate a param-dim sharding BACKWARD into the
    per-client matmuls — partial sums + psum there would break the
    bitwise identity with the single-chip run (measured)."""
    return constrain_cohort(stacked, mesh)


# ---------------------------------------------------------------------
# fed-mesh construction / introspection
# ---------------------------------------------------------------------


def is_fed_mesh(mesh) -> bool:
    """True for the (data, fsdp) production mesh; False for the legacy
    (clients[, data]) simulator mesh and for None."""
    if mesh is None:
        return False
    names = set(mesh.axis_names)
    return AXIS_PARAM in names and AXIS_COHORT in names


def fed_mesh_shape(mesh_shape: Optional[dict]) -> bool:
    """Does a ``mesh_shape`` knob value ask for the fed vocabulary?
    (an ``fsdp`` axis, or ``data`` without the legacy ``clients``)."""
    if not mesh_shape:
        return False
    return AXIS_PARAM in mesh_shape or (
        AXIS_COHORT in mesh_shape and "clients" not in mesh_shape
    )


def build_fed_mesh(
    devices: Optional[Sequence] = None, mesh_shape: Optional[dict] = None,
    *, warn_nonpartitionable: bool = True,
):
    """Build the named (data, fsdp) mesh. ``mesh_shape`` e.g.
    ``{"data": 4, "fsdp": 2}``; a missing axis defaults to size 1 (both
    axes always exist, so the layout table's specs always resolve).
    Default: all devices on ``data``. ``warn_nonpartitionable=False``
    is for lowering-only callers (the audit provider) where nothing
    executes and the random-stream warning below would be noise."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    if warn_nonpartitionable and not jax.config.jax_threefry_partitionable:
        # the in-client shuffle (and any other in-jit randomness) must
        # be SHARDING-INVARIANT for the mesh round to be bitwise
        # identical to the single-chip run — measured: the legacy
        # non-partitionable threefry produces different permutation
        # values when the vmapped client axis is sharded. The flag is
        # flipped by fedml_tpu.init() when args.mesh_shape asks for a
        # fed mesh — BEFORE any data synthesis, so every world of a
        # process draws from one stream. A direct build_fed_mesh
        # caller who skipped init() gets a loud warning instead of a
        # silent mid-process value shift (flipping HERE would change
        # the stream between a world built before and after).
        logging.warning(
            "fed mesh built with jax_threefry_partitionable=False: "
            "in-jit random draws (client shuffle) are NOT "
            "sharding-invariant — mesh results will not be bitwise "
            "identical to the single-chip run. Set mesh_shape in args "
            "and go through fedml_tpu.init(), or enable the flag "
            "before generating any data."
        )
    n = len(devices)
    shape = dict(mesh_shape or {})
    unknown = set(shape) - {AXIS_COHORT, AXIS_PARAM}
    if unknown:
        raise ValueError(
            f"fed mesh axes are ({AXIS_COHORT!r}, {AXIS_PARAM!r}); got "
            f"unknown axes {sorted(unknown)} — the legacy simulator "
            "vocabulary is {'clients', 'data'} (parallel/mesh.build_mesh)"
        )
    for axis in (AXIS_COHORT, AXIS_PARAM):
        if axis in shape and int(shape[axis]) < 1:
            # the null-naming rule: an explicit 0 must be rejected,
            # never silently auto-sized
            raise ValueError(
                f"fed mesh axis {axis!r}={shape[axis]!r}: must be >= 1 "
                "(omit the axis to auto-size it)"
            )
    fsdp = int(shape.get(AXIS_PARAM, 1))
    if fsdp > n:
        raise ValueError(
            f"fed mesh fsdp={fsdp} exceeds the {n} available devices"
        )
    data = int(shape.get(AXIS_COHORT, 0) or (n // max(fsdp, 1)))
    if data * fsdp > n:
        raise ValueError(
            f"fed mesh shape {{'data': {data}, 'fsdp': {fsdp}}} needs "
            f"{data * fsdp} devices, have {n}"
        )
    if data * fsdp < n and AXIS_COHORT not in shape:
        raise ValueError(
            f"fed mesh shape {{'data': {data}, 'fsdp': {fsdp}}} != "
            f"{n} devices"
        )
    # an EXPLICIT smaller shape takes a device-prefix sub-mesh — the
    # single-chip {'data': 1, 'fsdp': 1} baseline world that
    # tests/test_mesh_simulator.py compares every sharded shape against
    arr = np.array(devices[: data * fsdp]).reshape((data, fsdp))
    return Mesh(arr, (AXIS_COHORT, AXIS_PARAM))


def cohort_axis_size(mesh) -> int:
    """How many lanes the cohort shards over — 'data' on a fed mesh,
    'clients' on the legacy simulator mesh, 1 otherwise. Cohort sizes
    and compile buckets must tile this."""
    if mesh is None:
        return 1
    if is_fed_mesh(mesh):
        return int(mesh.shape[AXIS_COHORT])
    return int(mesh.shape.get("clients", 1))
