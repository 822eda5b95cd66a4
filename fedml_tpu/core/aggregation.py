"""Server-side aggregation as pure pytree ops.

Replaces the reference's python-dict weighted averaging
(``simulation/single_process/fedavg/fedavg_api.py:206-221`` and
``simulation/mpi_p2p_mp/fedavg/FedAVGAggregator.py:68-97``) with a single
einsum over a stacked client axis — which XLA maps onto the MXU — and the
reference's ``RobustAggregator``
(``python/fedml/core/robustness/robust_aggregation.py:41-99``: norm-diff
clipping, weak-DP Gaussian noise, coordinate-wise median) with vectorized
equivalents.

All functions treat "a set of client models" as ONE pytree whose leaves
carry a leading client axis ``C`` (``stack_pytrees``). That layout is what
lets aggregation run on-device with zero host round-trips, and is shared
by the vmap simulator (client axis = vmap axis) and the mesh simulator
(client axis sharded over the mesh).
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants
from ..analysis.compiled import auditable
from .devtime import measure as _devtime

Params = Any  # pytree of jax.Array


# -- compiled-artifact audit (fedml_tpu/analysis/compiled.py) ---------
# Abstract-input builders for the registered term/fold executables:
# `fedml-tpu audit` AOT-lowers each one against these ShapeDtypeStruct
# trees (no data, nothing executed) and verifies donation aliasing /
# host-transfer-freedom / baked-constant budgets on the lowered HLO.
# The encoded/decoded codec variants are not registered: their static
# codec argument binds a live instance, and they lower to the same
# fold currency these cover.

def _audit_term_inputs(ctx):
    p = ctx.abstract_params_f32()
    return [("model", (p, ctx.sds((), "float32")), {})]


def _audit_term_clipped_inputs(ctx):
    p = ctx.abstract_params_f32()
    s = ctx.sds((), "float32")
    return [("model", (p, p, s, s), {})]


def _audit_delta_term_clipped_inputs(ctx):
    p = ctx.abstract_params_f32()
    s = ctx.sds((), "float32")
    return [("model", (p, s, s), {})]


def _audit_fold_inputs(ctx):
    p = ctx.abstract_params_f32()
    return [("model", ((p, p, p), p), {})]


def stack_pytrees(trees: Sequence[Params]) -> Params:
    """[tree, tree, ...] -> tree with leading axis C."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_pytrees(stacked: Params, count: int) -> List[Params]:
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(count)]


def normalize_weights(
    sample_nums: jax.Array, valid: Optional[jax.Array] = None
) -> jax.Array:
    """Sample counts -> normalized FedAvg weights.

    ``valid`` (optional, [C] in {0,1}) zeroes the weight of padded
    cohort slots — the shape-bucketed compile cache
    (``core/round_pipeline.py``) pads cohorts up to bucket sizes and
    padding must be aggregation-invisible. Runs inside the donated
    round computation: pure, no aliasing of its inputs."""
    w = sample_nums.astype(jnp.float32)
    if valid is not None:
        w = w * valid.astype(jnp.float32)
    return w / jnp.maximum(w.sum(), 1.0)


def weighted_average(stacked: Params, weights: jax.Array) -> Params:
    """FedAvg: sum_c w_c * theta_c (fedavg_api.py:206-221 semantics).

    ``weights`` must already be normalized (see ``normalize_weights``).
    """

    def avg(leaf: jax.Array) -> jax.Array:
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return (w * leaf).sum(axis=0)

    return jax.tree.map(avg, stacked)


def is_device_tree(tree: Params) -> bool:
    """True when the tree has leaves and they are jax device arrays."""
    leaves = jax.tree.leaves(tree)
    # lint: host-sync-ok — list truthiness + type check, host metadata
    return bool(leaves) and isinstance(leaves[0], jax.Array)


def reconcile_to_device(tree: Params, device=None) -> Params:
    """``device_put`` only when the tree's device arrays live somewhere
    other than ``device`` (default: the process's first device). Keeps
    the in-process zero-copy path zero-copy while letting payloads from
    a hierarchical silo's private device subset land on the server."""
    device = device if device is not None else jax.devices()[0]
    leaves = jax.tree.leaves(tree)
    if (
        leaves
        and isinstance(leaves[0], jax.Array)
        and leaves[0].sharding.device_set != {device}
    ):
        return jax.device_put(tree, device)
    return tree


def pytree_sub(a: Params, b: Params) -> Params:
    return jax.tree.map(jnp.subtract, a, b)


def pytree_add(a: Params, b: Params) -> Params:
    return jax.tree.map(jnp.add, a, b)


def pytree_scale(a: Params, s) -> Params:
    return jax.tree.map(lambda x: x * s, a)


def global_norm(tree: Params) -> jax.Array:
    """L2 norm over all leaves (reference ``vectorize_weight``,
    robust_aggregation.py:7-38, flattens to one vector; BN running stats
    are skipped there — flax GN/LN params are true params, so no skip
    list is needed)."""
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.vdot(l, l) for l in leaves))


def _stacked_norms(stacked: Params) -> jax.Array:
    """Per-client L2 norms of a stacked pytree -> [C]."""
    leaves = jax.tree.leaves(stacked)
    sq = sum(jnp.sum(jnp.square(l.reshape(l.shape[0], -1)), axis=1) for l in leaves)
    return jnp.sqrt(sq)


# ---------------------------------------------------------------------
# Streaming aggregate-on-arrival (ROADMAP items 3/5)
# ---------------------------------------------------------------------
#
# The buffered server stacks the whole cohort before reducing —
# O(cohort x model) memory and the reduce runs only after the slowest
# client reports. The streaming fold below accumulates each upload the
# moment it lands, in O(model) memory, and is ORDER-INDEPENDENT at the
# bit level: two worlds whose uploads arrive in different thread orders
# finalize to identical float32 params. That property is what lets the
# tests (test_robustness.py) assert sync-streaming == buffered
# bit-for-bit even though arrival order is nondeterministic.
#
# Order independence comes from an error-free transformation split
# into two jitted executables:
#
# 1. the TERM step rounds each upload's contribution once —
#    ``t = fl32(w * theta)`` (for quantized uplinks: decode +
#    reconstruct + weight in one fused step). Whatever FMA contraction
#    or fusion XLA applies inside it is fine: the step is a pure
#    function of (upload, w), so its bits are identical no matter when
#    the upload arrives — and the buffered fallback routes through the
#    SAME executable, which is what makes buffered == streaming
#    bit-for-bit.
# 2. the FOLD step accumulates terms into a 3-limb float32 expansion
#    with Knuth two-sums. It contains only adds/subtracts — no multiply
#    exists for XLA to contract into an FMA — so every add is exact
#    except the lowest limb's, and reorderings agree to ~2^-60
#    relative, far below float32's 2^-24 rounding boundary at finalize.
#
# The two steps MUST stay separate executables: measured on this
# jaxlib, XLA:CPU contracts ``s + w*x`` into ``fma(w, x, s)`` whenever
# both live in one computation (optimization_barrier and
# reduce_precision do not prevent it), which silently re-introduces
# arrival-order dependence at full float32 ulp scale.


def _two_sum(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Knuth two-sum: s + e == a + b exactly (IEEE round-to-nearest);
    branch-free, valid for any magnitudes."""
    s = a + b
    v = s - a
    e = (a - (s - v)) + (b - v)
    return s, e


def _fold_leaf(s0, s1, s2, t):
    s0, e = _two_sum(s0, t)
    s1, e = _two_sum(s1, e)
    s2 = s2 + e  # only inexact add; error ~2^-48 of the term
    return s0, s1, s2


@auditable(
    "agg.fold_tree", _audit_fold_inputs, donate=(0,), round_shaped=True,
)
@functools.partial(jax.jit, donate_argnums=(0,))
def _fold_tree(limbs, term: Params):
    """Exact expansion fold of an already-weighted term tree. Adds
    only — keep any multiply (term computation) OUT of this jit, or
    XLA's FMA contraction breaks the error-free transformation.

    ``limbs`` is DONATED (audited by ``fedml-tpu audit``): every call
    site rebinds ``self._limbs = _fold_tree(self._limbs, ...)``, so the
    old expansion buffers are dead the moment the fold dispatches —
    XLA updates the 3-limb accumulators in place instead of allocating
    a fresh O(model) triple per upload. ``term`` is NOT donated: merge
    folds another live accumulator's limbs through this argument."""
    s0, s1, s2 = limbs
    out = jax.tree.map(_fold_leaf, s0, s1, s2, term)
    # tree-of-triples -> triple-of-trees (transpose keeps arbitrary
    # model pytrees — including ones that themselves contain tuples —
    # out of harm's way)
    return jax.tree.transpose(
        jax.tree.structure(term), jax.tree.structure((0, 0, 0)), out
    )


def exact_weighted_mean(stacked: Params, weights: jax.Array) -> Params:
    """Placement-independent weighted mean over a stacked client axis
    — the mesh round engine's aggregation (``parallel/layout.py``).

    ``weighted_average`` leaves the cross-client reduction order to
    XLA, so sharding the client axis turns it into partial sums + a
    psum whose bits differ from the single-chip reduction. This
    version pins the bits instead, with the SAME error-free
    transformation the streaming fold uses:

    1. per-client terms ``t_c = fl32(w_c * theta_c)`` — elementwise,
       so their bits are identical under any sharding;
    2. a ``lax.scan`` folds the terms in client-index order into a
       3-limb float32 expansion (Knuth two-sums, adds only — nothing
       for XLA to contract into an FMA across clients);
    3. the limbs collapse elementwise (``s0 + s1 + s2``).

    Every step is either elementwise or a fixed-order sequential fold,
    so a (data, fsdp)-sharded cohort finalizes to EXACTLY the bits of
    the unsharded vmap run — tests/test_mesh_simulator.py's
    bitwise gate over every mesh shape. Runs inside the donated round jit.
    """
    w32 = weights.astype(jnp.float32)

    def leaf_mean(leaf: jax.Array) -> jax.Array:
        wl = w32.reshape((-1,) + (1,) * (leaf.ndim - 1))
        terms = wl * leaf.astype(jnp.float32)  # [C, ...], rounded once

        def step(limbs, t):
            # THE limb fold — same ops, same order as the streaming
            # accumulator's executable, never a re-implementation
            return _fold_leaf(*limbs, t), None

        z = jnp.zeros(leaf.shape[1:], jnp.float32)
        (s0, s1, s2), _ = jax.lax.scan(step, (z, z, z), terms)
        return (s0 + s1 + s2).astype(leaf.dtype)

    return jax.tree.map(leaf_mean, stacked)


@auditable("agg.weighted_term", _audit_term_inputs)
@jax.jit
def _weighted_term(theta: Params, w: jax.Array) -> Params:
    """t = w * theta, rounded once per upload — deterministic per
    (theta, w) regardless of arrival order."""
    return jax.tree.map(lambda x: w * x.astype(jnp.float32), theta)


@functools.partial(jax.jit, static_argnums=0)
def _weighted_term_encoded(codec, encoded, like: Params, w: jax.Array) -> Params:
    """Fused decompress + reconstruct + weight: decode the wire payload
    against the pre-round global tree and produce the weighted term in
    one jitted step — the quantized buffers never materialize a second
    full-precision host copy. ``codec`` is a static arg (one trace per
    codec instance); both the streaming and the buffered paths call
    THIS executable, so their terms agree bitwise."""
    from .compression import decode_delta

    delta = decode_delta(codec, encoded, like)
    return jax.tree.map(
        lambda g, d: w * (g.astype(jnp.float32) + d.astype(jnp.float32)),
        like,
        delta,
    )


@functools.partial(jax.jit, static_argnums=0)
def _weighted_term_decoded(codec, encoded, like: Params, w: jax.Array) -> Params:
    """Fused decompress + weight of an update DELTA (async mode folds
    deltas, never full models — the server does not keep the stale base
    params a staleness>0 client trained from). ``like`` supplies
    shapes only (topk scatter)."""
    from .compression import decode_delta

    delta = decode_delta(codec, encoded, like)
    return jax.tree.map(lambda d: w * d.astype(jnp.float32), delta)


# -- streamable defenses (norm_diff_clipping / weak_dp) ----------------
#
# The reference's RobustAggregator clips each client's DELTA against
# the global model, then averages — a per-client operation that never
# needed the stacked cohort. These executables move the clip INSIDE the
# per-upload term step, so the defenses ride the aggregate-on-arrival
# fold at O(model) memory: term_i = w_i * (g + delta_i * min(1,
# bound/||delta_i||)). The clip's multiplies live in the TERM jit (pure
# function of one upload — deterministic per (upload, g, bound, w)
# regardless of arrival order), never in the add-only FOLD jit, so the
# error-free-transformation argument above is untouched and
# stream == buffered stays bitwise. weak_dp = the same clip + Gaussian
# noise on the FINALIZED aggregate (see RobustAggregator.add_noise;
# the cross-silo aggregator draws the key from run seed + round via
# ``derive_defense_rng`` at finalize). Each executable also returns the
# pre-clip delta norm and whether the clip bound actually bit — the
# on-arrival anomaly screen and ``defense_clipped_total`` read them
# without a second pass over the model.


def _clip_scale(norm: jax.Array, bound: jax.Array) -> jax.Array:
    """min(1, bound/||delta||) — robust_aggregation.py:47-58 semantics
    (shared with RobustAggregator.clip_updates; eps guards a zero
    delta)."""
    return jnp.minimum(1.0, bound / jnp.maximum(norm, 1e-12))


@auditable("agg.weighted_term_clipped", _audit_term_clipped_inputs)
@jax.jit
def _weighted_term_clipped(
    theta: Params, g: Params, bound: jax.Array, w: jax.Array
):
    """Clip-against-global + weight, fused: t = w * (g + delta *
    min(1, bound/||delta||)). Returns (term, pre-clip norm, clipped?)."""
    delta = jax.tree.map(
        lambda t, gg: t.astype(jnp.float32) - gg.astype(jnp.float32), theta, g
    )
    norm = global_norm(delta)
    scale = _clip_scale(norm, bound)
    term = jax.tree.map(
        lambda gg, d: w * (gg.astype(jnp.float32) + d * scale), g, delta
    )
    return term, norm, norm > bound


@functools.partial(jax.jit, static_argnums=0)
def _weighted_term_encoded_clipped(
    codec, encoded, like: Params, bound: jax.Array, w: jax.Array
):
    """Fused decode + clip + reconstruct + weight: the wire payload IS
    the delta against the broadcast global, so the clip applies to the
    decoded tree directly."""
    from .compression import decode_delta

    delta = jax.tree.map(
        lambda d: d.astype(jnp.float32), decode_delta(codec, encoded, like)
    )
    norm = global_norm(delta)
    scale = _clip_scale(norm, bound)
    term = jax.tree.map(
        lambda gg, d: w * (gg.astype(jnp.float32) + d * scale), like, delta
    )
    return term, norm, norm > bound


@auditable(
    "agg.weighted_delta_term_clipped", _audit_delta_term_clipped_inputs,
)
@jax.jit
def _weighted_delta_term_clipped(delta: Params, bound: jax.Array, w: jax.Array):
    """Async-mode clip: the fold currency is the delta itself, so the
    clipped term is w * delta * min(1, bound/||delta||) — the staleness
    discount rides ``w`` and never changes the clip geometry."""
    d32 = jax.tree.map(lambda x: x.astype(jnp.float32), delta)
    norm = global_norm(d32)
    scale = _clip_scale(norm, bound)
    term = jax.tree.map(lambda d: w * (d * scale), d32)
    return term, norm, norm > bound


@functools.partial(jax.jit, static_argnums=0)
def _weighted_delta_term_decoded_clipped(
    codec, encoded, like: Params, bound: jax.Array, w: jax.Array
):
    """Fused decode + clip + weight of an async update delta (``like``
    supplies shapes only)."""
    from .compression import decode_delta

    d32 = jax.tree.map(
        lambda d: d.astype(jnp.float32), decode_delta(codec, encoded, like)
    )
    norm = global_norm(d32)
    scale = _clip_scale(norm, bound)
    term = jax.tree.map(lambda d: w * (d * scale), d32)
    return term, norm, norm > bound


@jax.jit
def _tree_scaled(tree: Params, denom: jax.Array) -> Params:
    return jax.tree.map(lambda x: x / denom, tree)


def derive_defense_rng(seed, index) -> jax.Array:
    """THE defense rng convention: fold the round/publish index into the
    run seed. Every weak_dp call site derives its key here — the seed's
    ``rng=None -> PRNGKey(0)`` default added the IDENTICAL "noise"
    every round, which is no privacy at all (satellite fix)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(int(seed)), int(index) % (2**31)  # lint: host-sync-ok — host ints
    )


class StreamingAccumulator:
    """Incremental weighted-sum fold over model uploads: O(model)
    memory, order-independent finalize.

    ``fold(theta, w)`` the moment an upload lands; ``finalize()`` once
    the round closes returns ``sum_i w_i * theta_i / sum_i w_i`` as the
    template's dtype — weights renormalize over whatever was folded, so
    a quorum-closed partial cohort needs no special casing. The
    buffered path folds its sorted buffer through this same class,
    which is what makes buffered and streaming bit-identical.
    """

    def __init__(self, template: Params) -> None:
        self._template = template
        self.reset()

    def fold(self, theta: Params, w: float) -> None:
        with _devtime("agg.weighted_term"):
            term = _weighted_term(theta, jnp.float32(w))
        self._fold_term(term, w)

    def fold_weighted_term(self, term: Params, w: float) -> None:
        """Fold an ALREADY-WEIGHTED partial sum ``term = sum_i w_i *
        theta_i`` carrying total weight ``w = sum_i w_i`` — the
        registry-backed simulator's client->edge hop, where a whole
        vmap group's per-edge partial is computed in one fused jitted
        reduction (term rounding happens there, once, deterministically
        per group) and lands in the tree as a single fold."""
        self._fold_term(term, w)

    def fold_encoded(self, codec, encoded: Params, like: Params, w: float) -> None:
        """Fold a compressed upload: decode + reconstruct + weight in
        one fused jitted step against the pre-round global tree."""
        self._fold_term(
            _weighted_term_encoded(codec, encoded, like, jnp.float32(w)), w
        )

    def fold_encoded_delta(
        self, codec, encoded: Params, like: Params, w: float
    ) -> None:
        """Fold a compressed update DELTA without reconstructing a full
        model (async mode; ``like`` supplies shapes only)."""
        self._fold_term(
            _weighted_term_decoded(codec, encoded, like, jnp.float32(w)), w
        )

    # -- defense folds (norm_diff_clipping / weak_dp in the stream) ---
    # Each clips the upload's delta against the broadcast global INSIDE
    # the fused term step, folds the clipped term, and reports
    # (pre-clip delta norm, clip bound bit?) so the caller can feed the
    # anomaly screen and defense_clipped_total without re-walking the
    # model. The buffered path folds through these SAME executables at
    # close, which is what keeps stream == buffered bitwise for
    # clipping configs.

    def fold_clipped(
        self, theta: Params, against: Params, bound: float, w: float
    ) -> Tuple[float, bool]:
        with _devtime("agg.weighted_term_clipped"):
            term, norm, clipped = _weighted_term_clipped(
                theta, against, jnp.float32(bound), jnp.float32(w)
            )
        self._fold_term(term, w)
        # the screen needs (norm, clipped?) on host per upload: one
        # deliberate fetch, counted by the caller
        return float(norm), bool(clipped)  # lint: host-sync-ok

    def fold_encoded_clipped(
        self, codec, encoded: Params, like: Params, bound: float, w: float
    ) -> Tuple[float, bool]:
        term, norm, clipped = _weighted_term_encoded_clipped(
            codec, encoded, like, jnp.float32(bound), jnp.float32(w)
        )
        self._fold_term(term, w)
        # the screen needs (norm, clipped?) on host per upload: one
        # deliberate fetch, counted by the caller
        return float(norm), bool(clipped)  # lint: host-sync-ok

    def fold_delta_clipped(
        self, delta: Params, bound: float, w: float
    ) -> Tuple[float, bool]:
        with _devtime("agg.weighted_delta_term_clipped"):
            term, norm, clipped = _weighted_delta_term_clipped(
                delta, jnp.float32(bound), jnp.float32(w)
            )
        self._fold_term(term, w)
        # the screen needs (norm, clipped?) on host per upload: one
        # deliberate fetch, counted by the caller
        return float(norm), bool(clipped)  # lint: host-sync-ok

    def fold_encoded_delta_clipped(
        self, codec, encoded: Params, like: Params, bound: float, w: float
    ) -> Tuple[float, bool]:
        term, norm, clipped = _weighted_delta_term_decoded_clipped(
            codec, encoded, like, jnp.float32(bound), jnp.float32(w)
        )
        self._fold_term(term, w)
        # the screen needs (norm, clipped?) on host per upload: one
        # deliberate fetch, counted by the caller
        return float(norm), bool(clipped)  # lint: host-sync-ok

    def running_mean(self) -> Optional[Params]:
        """Approximate mean of everything folded so far (top limb only
        — a scoring aid for the on-arrival anomaly screen, NOT the
        exact finalize). None before the first fold."""
        if self.count == 0:
            return None
        return _tree_scaled(self._limbs[0], jnp.float32(self.total_w))

    def export_state(self) -> dict:
        """Wire-portable snapshot of the fold state: the exact 3-limb
        float32 expansion (as host numpy trees — msgpack-ready), the
        folded weight total and the fold count. The hierarchical server
        plane ships this edge→root once per round close; ``merge`` of a
        ``load_state``-restored shell is bitwise identical to merging
        the live accumulator, because the limbs ARE the state (no
        rounding happens at export — numpy conversion is a byte-exact
        device fetch)."""
        return {
            "limbs": [
                jax.tree.map(lambda x: np.asarray(x), limb)  # lint: host-sync-ok — export IS the deliberate fetch
                for limb in self._limbs
            ],
            "total_w": float(self.total_w),  # lint: host-sync-ok — python-float bookkeeping, not device values
            "count": int(self.count),  # lint: host-sync-ok — python-int bookkeeping
        }

    def load_state(self, state: dict) -> "StreamingAccumulator":
        """Restore an ``export_state`` snapshot onto this accumulator
        (template must match the exporter's). Limbs stay as delivered —
        the fold/merge jits device-put them unchanged, so a root-side
        merge of an imported edge state is bitwise identical to merging
        the edge's live accumulator."""
        limbs = state["limbs"]
        if len(limbs) != 3:
            raise ValueError(
                f"edge fold state carries {len(limbs)} limbs, expected 3"
            )
        self._limbs = tuple(limbs)
        self.total_w = float(state["total_w"])  # lint: host-sync-ok — wire scalar
        self.count = int(state["count"])  # lint: host-sync-ok — wire scalar
        return self

    def fold_limbs(self, limbs, w: float, count: int = 1) -> None:
        """Fold an exported 3-limb expansion carrying total weight
        ``w`` over ``count`` underlying uploads — the device-resident
        limb-set handoff (an on-mesh partial fold, or ``merge``'s edge
        -> root hop, which routes through here so the ordering-
        critical fold loop exists ONCE). Each limb is folded as a term
        through the SAME add-only exact jit, so feeding limb-sets is
        bitwise identical to having folded the underlying terms here.
        ``w``/``count`` add exactly (the per-upload f32 rounding
        already happened when each term folded at its source);
        quorum/fold accounting reads ``count``, so it must reflect
        uploads, not handoffs. The limbs may be (data, fsdp)-sharded
        device trees; nothing is fetched to host."""
        if len(limbs) != 3:
            raise ValueError(f"expected a 3-limb expansion, got {len(limbs)}")
        if count < 0:
            raise ValueError(
                f"count={count}: a limb-set represents >= 0 uploads"
            )
        for limb in limbs:
            with _devtime("agg.fold_tree"):
                self._limbs = _fold_tree(self._limbs, limb)
        self.total_w += float(w)  # lint: host-sync-ok — host scalar bookkeeping
        self.count += int(count)  # lint: host-sync-ok — host int bookkeeping

    def merge(self, other: "StreamingAccumulator") -> None:
        """Fold another accumulator's state into this one — the edge ->
        root hop of a two-tier aggregation tree (``fedml_tpu/scale/
        tree.py``). Routes through :meth:`fold_limbs` (one copy of the
        exact-expansion fold loop): the merged expansion represents
        the union's sum to the usual ~2^-48 lowest-limb error and the
        float32 finalize stays bitwise independent of how uploads were
        partitioned across accumulators (tree == flat, asserted in
        tests/test_planet_scale.py). ``total_w``/``count``
        add exactly (python floats over integer sample counts); an
        empty other (count 0) is a no-op fold of zero limbs."""
        self.fold_limbs(other._limbs, other.total_w, count=other.count)

    def _fold_term(self, term: Params, w: float) -> None:
        with _devtime("agg.fold_tree"):
            self._limbs = _fold_tree(self._limbs, term)
        # float32 first (the term used fl32(w)); python-float sums of
        # integer sample counts are exact in any order
        self.total_w += float(jnp.float32(w))  # lint: host-sync-ok — w is a host scalar; fl32 rounding only
        self.count += 1

    def finalize(self) -> Params:
        """Weighted average of everything folded so far. The limb sums
        collapse on host in extended precision (longdouble where the
        platform has it) so the final float32 rounding sees the exact
        expansion value — the one place a digit of precision could
        leak order back in."""
        if self.count == 0:
            raise RuntimeError("finalize() with no folded uploads")
        s0, s1, s2 = self._limbs
        wide = np.longdouble  # x86-64: 80-bit; elsewhere degrades to f64
        w_total = wide(self.total_w)

        def leaf(a0, a1, a2, t):
            acc = (
                np.asarray(a0, dtype=wide)  # lint: host-sync-ok
                + np.asarray(a1, dtype=wide)  # lint: host-sync-ok
                + np.asarray(a2, dtype=wide)  # lint: host-sync-ok — THE deliberate host collapse (docstring)
            )
            out = (acc / w_total).astype(np.float32)
            return jnp.asarray(out, dtype=t.dtype)

        return jax.tree.map(leaf, s0, s1, s2, self._template)

    def reset(self) -> None:
        zeros = lambda: jax.tree.map(  # noqa: E731
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), self._template
        )
        self._limbs = (zeros(), zeros(), zeros())
        # python float: sample counts are integers, exactly summed in
        # float64 in any order; async staleness weights make no
        # bit-identity claim
        self.total_w = 0.0
        self.count = 0


def staleness_weight(sample_num: float, staleness: int, decay: float) -> float:
    """FedBuff-style staleness discount: an update trained against a
    model ``staleness`` publishes old contributes ``n * decay^s`` —
    the unit oracle tests/test_async_agg.py pins against."""
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    # lint: host-sync-ok — pure host arithmetic (the unit oracle)
    return float(sample_num) * float(decay) ** int(staleness)


def needs_full_cohort(args, server_aggregator) -> Optional[str]:
    """Why streaming aggregation cannot serve this config, or None.

    The incremental fold is a weighted sum; an aggregator that needs
    the whole cohort at once (coordinate-wise median, a custom
    ``ServerAggregator`` reduction) must keep the buffered path —
    loudly, never silently. ``norm_diff_clipping`` and ``weak_dp`` are
    per-upload operations (clip inside the term step, noise at
    finalize) and STREAM — see the clipped term executables above.
    Unknown defense strings are rejected here, not quietly averaged."""
    if server_aggregator is not None:
        return "custom ServerAggregator reduces over the stacked cohort"
    defense = getattr(args, "defense_type", None) or None
    if defense is not None and defense not in constants.DEFENSE_TYPES:
        raise ValueError(
            f"unknown defense_type {defense!r}; pick one of "
            f"{constants.DEFENSE_TYPES} (or None) — refusing to fall "
            "through to an UNDEFENDED plain mean"
        )
    if defense == constants.DEFENSE_MEDIAN:
        return "defense_type=median needs the full cohort at once"
    return None


class RobustAggregator:
    """Vectorized port of ``RobustAggregator``
    (robust_aggregation.py:41-99). Operates on a stacked client axis.

    defense_type: ``norm_diff_clipping`` | ``weak_dp`` | ``median`` | None
    """

    def __init__(self, args) -> None:
        defense = getattr(args, "defense_type", None) or None
        if defense is not None and defense not in constants.DEFENSE_TYPES:
            # the seed's aggregate() silently fell through to a plain
            # mean on a typo'd defense — a no-defense footgun. Reject
            # at construction instead.
            raise ValueError(
                f"unknown defense_type {defense!r}; pick one of "
                f"{constants.DEFENSE_TYPES} (or None)"
            )
        self.defense_type = defense
        self.norm_bound = float(getattr(args, "norm_bound", 5.0))
        self.stddev = float(getattr(args, "stddev", 0.158))
        if self.norm_bound <= 0:
            raise ValueError(
                f"norm_bound={self.norm_bound}: must be > 0 (the clip "
                "radius around the global model)"
            )
        if self.stddev < 0:
            raise ValueError(f"stddev={self.stddev}: must be >= 0")

    def clip_updates(self, stacked: Params, global_params: Params) -> Params:
        """Norm-difference clipping (robust_aggregation.py:47-58):
        scale each client's delta so ||theta_c - theta_g|| <= norm_bound."""
        deltas = jax.tree.map(lambda s, g: s - g[None], stacked, global_params)
        norms = _stacked_norms(deltas)  # [C]
        scale = jnp.minimum(1.0, self.norm_bound / jnp.maximum(norms, 1e-12))

        def apply(d, g):
            s = scale.reshape((-1,) + (1,) * (d.ndim - 1)).astype(d.dtype)
            return g[None] + d * s

        return jax.tree.map(apply, deltas, global_params)

    def add_noise(self, params: Params, rng: jax.Array) -> Params:
        """Weak DP: Gaussian noise on the aggregate
        (robust_aggregation.py:60-63)."""
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(rng, len(leaves))
        noised = [
            l + self.stddev * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)
        ]
        return jax.tree.unflatten(treedef, noised)

    @staticmethod
    def coordinate_median(stacked: Params) -> Params:
        """Coordinate-wise median across clients
        (robust_aggregation.py:65-99)."""
        return jax.tree.map(lambda l: jnp.median(l, axis=0), stacked)

    def aggregate(
        self,
        stacked: Params,
        weights: jax.Array,
        global_params: Params,
        rng: Optional[jax.Array] = None,
    ) -> Params:
        """Full robust-FedAvg path, mirroring
        ``FedAvgRobustAggregator.aggregate``
        (simulation/mpi_p2p_mp/fedavg_robust/FedAvgRobustAggregator.py)."""
        if self.defense_type == "median":
            return self.coordinate_median(stacked)
        if self.defense_type in ("norm_diff_clipping", "weak_dp"):
            stacked = self.clip_updates(stacked, global_params)
        out = weighted_average(stacked, weights)
        if self.defense_type == "weak_dp":
            if rng is None:
                # the seed defaulted to PRNGKey(0) here, so every round
                # added the IDENTICAL "noise" — zero privacy. Callers
                # must derive the key from run seed + round index
                # (``derive_defense_rng``).
                raise ValueError(
                    "weak_dp needs a per-round rng; pass "
                    "derive_defense_rng(args.random_seed, round_idx) — "
                    "a fixed key re-adds the same noise every round"
                )
            out = self.add_noise(out, rng)
        return out
