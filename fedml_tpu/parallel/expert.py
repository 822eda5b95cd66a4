"""Expert parallelism: shard stacked expert weights over a mesh ``ep`` axis.

Companion to ``models.moe`` (Switch-style MoE). The TPU idiom mirrors
``parallel.tensor``: no hand-written all-to-alls — the stacked expert
arrays (leading dim E) get ``NamedSharding(P("ep", ...))`` and XLA's
SPMD partitioner splits the dispatch einsums
(``[N,E,cap] x [N,C] -> [E,cap,C]`` etc.) across the axis, inserting
the token all-to-all exactly where GShard places it manually. SPMD is
semantics-preserving, so an ep-sharded layer computes the same function
as the replicated one (asserted in tests).

Composes with the Megatron tp rules: apply ``tensor.tp_specs`` to the
dense blocks and these rules to the expert stacks on a
``{dp, tp/ep}``-axis mesh.

**A held share.** Where the chips that share a layer are not all in
this process — the benchmark's one-chip cut of an expert-parallel
deployment — the expert layer (``models.decoder.HeldExperts``) is told
which experts it holds: ``experts_held(num_experts, ep, rank)``. It
routes over all of them and computes its own experts' part; the layer
runs without its exchange, and the parts of all ``ep`` shares add up
to the whole layer (``tests/test_moe_decoder.py``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# stacked expert leaves (leading dim E) by the module that owns them:
# models.moe.SwitchFFN (auto-named SwitchFFN_<i>) and
# models.decoder.HeldExperts (named "moe" inside each decoder layer)
_EXPERT_LEAVES = {
    "SwitchFFN": {"wi", "bi", "wo", "bo"},
    "moe": {"gate_proj", "up_proj", "down_proj"},
}


class ExpertsHeld(NamedTuple):
    """The experts one chip holds of a layer: ``count`` from ``first``."""

    first: int
    count: int


def experts_held(num_experts: int, ep: int = 1, rank: int = 0) -> ExpertsHeld:
    """The share of chip ``rank`` among the ``ep`` chips that share each
    expert layer: a contiguous ``num_experts / ep`` of the experts."""
    if ep <= 0 or num_experts % ep or not 0 <= rank < ep:
        raise ValueError(
            f"{num_experts} experts over {ep} chips, rank {rank}: the chips that share "
            "a layer hold equal whole shares"
        )
    count = num_experts // ep
    return ExpertsHeld(rank * count, count)


def _spec_for(path, leaf, axis: str) -> P:
    names = [p.key if hasattr(p, "key") else str(p) for p in path]
    for owner, leaves in _EXPERT_LEAVES.items():
        if names[-1] in leaves and any(n == owner or n.startswith(owner + "_") for n in names[:-1]):
            return P(axis, *([None] * (leaf.ndim - 1)))
    return P()


def ep_specs(params: Any, axis: str = "ep") -> Any:
    """PartitionSpec pytree: expert stacks sharded on E, rest replicated."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(path, leaf, axis), params
    )


def shard_params_ep(params: Any, mesh: Mesh, axis: str = "ep") -> Any:
    """Place an MoE param tree on ``mesh`` with experts split over
    ``axis``. Expert counts that don't divide the axis — or a mesh
    without the axis at all — fall back to replicated (same policy as
    ``tensor.shard_params_tp``)."""
    if axis not in mesh.axis_names:
        from .mesh import replicate

        return replicate(params, mesh)
    ep = mesh.shape[axis]

    from .mesh import place_global

    def place(path, leaf):
        spec = _spec_for(path, leaf, axis)
        if spec and spec[0] == axis and leaf.shape[0] % ep != 0:
            spec = P()
        return place_global(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def tp_ep_specs(params: Any, tp_axis: str = "tp", ep_axis: str = "ep") -> Any:
    """Composed layout for an MoE transformer: expert stacks ride
    ``ep``, dense layers ride the Megatron ``tp`` rules, the rest is
    replicated. (Chaining ``shard_params_tp`` THEN ``shard_params_ep``
    would clobber the tp placement — ep's P() re-placement of every
    non-expert leaf wins — hence a single merged spec tree.)"""
    from .tensor import tp_specs

    return jax.tree.map(
        lambda t, e: e if e != P() else t,
        tp_specs(params, tp_axis),
        ep_specs(params, ep_axis),
        is_leaf=lambda s: isinstance(s, P),
    )


def shard_params_tp_ep(
    params: Any, mesh: Mesh, tp_axis: str = "tp", ep_axis: str = "ep"
) -> Any:
    """Place an MoE transformer param tree with the composed tp x ep
    layout; any dim that doesn't divide its mesh axis falls back to
    replicated for that leaf."""

    from .mesh import place_global

    def place(leaf, spec):
        for dim, name in enumerate(spec):
            if name is not None and (
                name not in mesh.axis_names
                or leaf.shape[dim] % mesh.shape[name] != 0
            ):
                spec = P()
                break
        return place_global(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, params, tp_ep_specs(params, tp_axis, ep_axis))
