"""FedAvg-family simulation: one jitted round engine, four algorithms.

Reference parity (``simulation/single_process/fedavg/fedavg_api.py:83-141``
round loop; ``fedopt/fedopt_api.py``; ``fednova/fednova_trainer.py:136-165``;
``mpi_p2p_mp/fedavg/FedAVGAggregator.py:68-113``), redesigned TPU-first:

- The reference trains sampled clients one-by-one in Python and averages
  python dicts on host. Here the ENTIRE round — gather the sampled
  cohort, vmap the local-training scan across clients, aggregate — is a
  single jitted XLA computation; global params and server-optimizer
  state are donated buffers that never leave the device.
- Client sampling keeps the reference's determinism contract:
  ``np.random.seed(round_idx)`` then ``choice`` without replacement
  (FedAVGAggregator.py:99-113).
- Robust aggregation (clip / weak-DP / median) plugs in via
  ``args.defense_type`` exactly where ``fedavg_robust`` puts it.
- ``mesh`` mode shards the cohort's client axis over a
  ``jax.sharding.Mesh`` — XLA turns the weighted reduction into an ICI
  all-reduce; see ``fedml_tpu/parallel/mesh.py``.
"""

from __future__ import annotations

import logging
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.compiled import auditable, pow2_budget
from ..core.devtime import measure as _devtime
from ..core.frame import bind_operator
from ..core.aggregation import (
    RobustAggregator,
    exact_weighted_mean,
    normalize_weights,
    weighted_average,
)
from ..core.local_trainer import (
    compute_dtype_from_args,
    lane_steps,
    last_real_step,
    make_eval_fn,
    make_local_train_fn,
    model_counters,
)
from ..core.optimizers import (
    create_client_optimizer,
    create_server_optimizer,
    resolve_round_lr_schedule,
)
from ..core import sample_store
from ..core.types import Batches
from ..data.loader import FederatedDataset
from ..models.spec import FedModel

Params = Any


def _take(b: Batches, idx: jax.Array) -> Batches:
    return Batches(
        x=jnp.take(b.x, idx, axis=0),
        y=jnp.take(b.y, idx, axis=0),
        mask=jnp.take(b.mask, idx, axis=0),
    )


# multiply-adds a lane's step does at least -- one per parameter and
# sample: a floor, which a convolution's reuse of its weights or a
# sequence axis only raises -- from which the lanes of a ragged cohort
# run one after another (build_round_fn's ``ragged``). On the v5e that
# halved a round at 8.9e7 (ResNet-18 at batch 8) and at 7.2e8 (at batch
# 64, the benchmark's cells); at 1.35e7 (a 420k-parameter CNN at batch
# 32) it won at a bucket of 32 and was measured at no wider one, and at
# 2.5e5 it lost (PERF.md §6, PR 31). Whatever reads under the constant,
# a model this floor undercounts too, keeps the static scan. Not a knob
_HEAVY_LANE_STEP = 5e7


def build_round_fn(
    local_train,
    aggregate,
    preprocess=None,
    *,
    mesh=None,
    use_round_lr: bool = False,
    keep_stacked: bool = False,
    on_trace=None,
    sample_shape: Optional[Tuple[int, ...]] = None,
    ragged: bool = False,
):
    """THE round engine, as a pure function of its collaborators.

    Module-level on purpose: the engine must never close over a
    mutable ``self`` (retrace hazard — the lint suite's rule), and the
    compiled-artifact auditor (``fedml_tpu/analysis/compiled.py``)
    AOT-lowers this exact computation across the pow2 cohort census
    without constructing an API instance. ``aggregate`` /
    ``preprocess`` may be bound methods (FedOpt/FedNova/defense
    subclasses plug in here); ``on_trace`` fires at TRACE time only —
    the compile-count/telemetry seam, never part of the lowered HLO.

    Donation contract (audited): argnums 0 and 1 — the carried global
    params and server-optimizer state — are donated by every caller's
    ``jax.jit(round_fn, donate_argnums=(0, 1))``; the round pipeline
    chains K rounds in flight on those buffers.

    Mesh dispatch: a legacy ``(clients[, data])`` mesh keeps the
    original client-axis sharding; a fed ``(data, fsdp)`` mesh
    (``parallel/layout.py``) shards the cohort along ``data``, keeps
    the params fsdp-sharded AT REST while gathering them replicated
    for per-client compute (FSDP at-use gather — no tensor-parallel
    reduction ever splits a client's math, which is what keeps the
    mesh round bitwise identical to the single-chip vmap path), and
    pins the aggregated output back onto the fsdp layout so the
    chained/donated carry never leaves the mesh.

    ``sample_shape``: the static per-sample shape of a federation whose
    ``packed`` argument is the flat sample store
    (``core/sample_store.py``: ``x`` as ``[N, nb, B, F]``, a client one
    contiguous block); the gathered cohort is reshaped back to it, so
    everything after ``fed.gather`` sees the cohort it always saw. None
    where a sample is one-dimensional and ``packed`` is the dataset's
    own arrays.

    ``ragged``: a fact about the federation, the trainer and the
    model, read off all three by the caller (``FedAvgAPI.
    _build_jitted``) -- some client leaves whole batches of the shared
    ``num_batches`` empty, ``local_train`` is the stock one that takes
    ``steps`` (``core/local_trainer.py``), and one lane's step is work
    enough for the chip. Then the cohort is a ``lax.map`` over its
    lanes in ``idx`` order, each lane's loop ending at its own last
    real batch (``last_real_step`` of the mask it trains on): batches
    after that are not stepped, and a padded lane runs no step. What is
    still stepped and reverted is an empty batch before a lane's last
    real one. A lane's steps, batches, random stream and place in the
    stacked outputs are what they were; one executable per bucket.
    Without ``ragged`` -- or under any mesh, whose lane axis a scan
    would cut -- the cohort is one vmap over a static scan of all
    ``num_batches``, as it always lowered.

    The round's summed metrics carry ``steps_run`` and ``steps_packed``
    (float32) beside the loss: the lane-steps an epoch's loops run, and
    ``bucket x num_batches``, what the lanes were packed to.
    """
    from ..parallel.layout import is_fed_mesh

    fed = mesh is not None and is_fed_mesh(mesh)
    ragged = ragged and mesh is None

    def round_fn(
        global_params, server_state, packed: Batches, nsamples, idx, rng,
        lr_mult=1.0, valid=None,
    ):
        if on_trace is not None:
            on_trace(idx)
        # the three scopes below are names in the HLO's op metadata and
        # nothing else: the device trace's readers
        # (benchmark/layer_metrics/_scopes.py) find the round's parts
        # by them whatever the compiler numbers its instructions
        with jax.named_scope("fed.gather"):
            cohort = _take(packed, idx)
            if sample_shape is not None:
                cohort = cohort.replace(
                    x=cohort.x.reshape(cohort.mask.shape + sample_shape)
                )
            ns = jnp.take(nsamples, idx)
            if valid is not None:
                # shape-bucketed cohorts (core/round_pipeline.py): the
                # padded slots repeat a real client index; zeroing their
                # batch mask makes every batch fully-masked (local
                # training reverts params exactly, metrics count 0) and
                # normalize_weights(..., valid) gives them aggregation
                # weight 0 — the same invisibility contract as
                # parallel/mesh.py's pad_federation
                vm = valid.reshape((-1,) + (1,) * (cohort.mask.ndim - 1))
                cohort = Batches(
                    x=cohort.x,
                    y=cohort.y,
                    mask=cohort.mask * vm.astype(cohort.mask.dtype),
                )
        train_params = global_params
        if fed:
            from ..parallel.layout import fed_compute_constraints

            # the shared fed entry discipline (cohort along 'data',
            # params + sample counts + validity mask gathered
            # replicated — the FSDP at-use gather; params stay
            # fsdp-sharded at rest in the carry). valid MUST be
            # lane-invariant too: normalize_weights reduces w * valid,
            # and a data-sharded [C] vector there would turn the
            # normalizer into shape-dependent partial sums + psum
            if valid is not None:
                train_params, cohort, ns, valid = fed_compute_constraints(
                    mesh, global_params, cohort, ns, valid
                )
            else:
                train_params, cohort, ns = fed_compute_constraints(
                    mesh, global_params, cohort, ns
                )
        elif mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import federation_spec

            spec = NamedSharding(mesh, federation_spec(mesh))
            cohort = Batches(
                x=jax.lax.with_sharding_constraint(cohort.x, spec),
                y=jax.lax.with_sharding_constraint(cohort.y, spec),
                mask=jax.lax.with_sharding_constraint(cohort.mask, spec),
            )
            ns = jax.lax.with_sharding_constraint(
                ns, NamedSharding(mesh, P("clients"))
            )
        if preprocess is not None:
            cohort, server_state = preprocess(cohort, server_state)
        rngs = jax.random.split(rng, idx.shape[0])
        with jax.named_scope("fed.local_train"):
            if not ragged:
                # round-indexed LR: one multiplier for the whole cohort
                extra = (lr_mult,) if use_round_lr else ()
                new_stacked, train_metrics = jax.vmap(
                    local_train, in_axes=(None, 0, 0) + (None,) * len(extra)
                )(train_params, cohort, rngs, *extra)
                steps_run = idx.shape[0] * cohort.num_batches
            else:
                lr = lr_mult if use_round_lr else None
                steps = last_real_step(cohort.mask)
                steps_run = steps.sum()
                new_stacked, train_metrics = jax.lax.map(
                    lambda lane: local_train(train_params, lane[0], lane[1], lr, lane[2]),
                    (cohort, rngs, steps),
                )
        if fed:
            from ..parallel.layout import pin_cohort_outputs

            # per-client compute stays whole; only the at-rest carry
            # is fsdp-sharded (see pin_cohort_outputs)
            new_stacked = pin_cohort_outputs(mesh, new_stacked)
        with jax.named_scope("fed.aggregate"):
            weights = normalize_weights(ns, valid)
            new_global, new_state = aggregate(
                global_params, server_state, new_stacked, weights, cohort, rng
            )
            if fed:
                from ..parallel.layout import constrain_tree

                # the aggregated carry lands fsdp-sharded at rest — the
                # donated (0, 1) chain never leaves the mesh, so zero host
                # hops at any cohort size
                new_global = constrain_tree(new_global, mesh)
            summed = {k: v.sum() for k, v in train_metrics.items()}
            summed["steps_run"] = jnp.asarray(steps_run, jnp.float32)
            summed["steps_packed"] = jnp.float32(idx.shape[0] * cohort.num_batches)
        if keep_stacked:
            return new_global, new_state, summed, new_stacked
        return new_global, new_state, summed

    return round_fn


def build_eval_all(eval_fn):
    """vmap-over-lanes eval reduction, module-level for the same
    no-self-closure reason as :func:`build_round_fn`: ``eval_fn``'s scan
    over ``num_batches`` on every lane of the leading axis at once, the
    lanes' sums added up. Its outputs are sums over all samples, so the
    leading axis need not be the clients: the evaluation hands it
    :func:`dense_eval_split`'s arrays, not the per-client packing,
    whose ``num_batches`` is the largest client's."""

    def eval_all(params, packed: Batches):
        sums = jax.vmap(eval_fn, in_axes=(None, 0))(params, packed)
        return jax.tree.map(lambda x: x.sum(), sums)

    return eval_all


@jax.jit
def real_slots(*masks: jax.Array) -> jax.Array:
    """How many slots of each ``[L, nb, bs]`` mask hold a sample, as one
    int32 vector (one fetch for all of them)."""
    return jnp.stack([jnp.sum(m != 0, dtype=jnp.int32) for m in masks])


def dense_eval_split(packed: Batches, n_real: int) -> Tuple[Batches, Dict[str, int]]:
    """``(split, facts)``: what an evaluation reads of ``packed``, a
    ``[L, nb, bs, ...]`` split of which ``n_real`` slots hold a sample.

    Packed per client, every client has the largest one's
    ``num_batches`` and the evaluation runs the forward pass of all the
    slots that only pad (half of them in a Dirichlet(0.5) federation).
    An evaluation sums over all samples, so nothing in it depends on
    which client a sample sits with: the real slots move to the head --
    the three leading axes flattened, a stable order by ``1 - mask`` as
    ``_shuffle_batches`` has it, the first ``L x nb' x bs`` kept -- and
    the split is ``[L, nb', bs, ...]`` with ``nb' = max(1, ceil(n_real
    / (L x bs)))``: the same lanes, the same batch size, fewer batches.
    One jitted pass over global arrays that returns global arrays (a
    mesh-placed split keeps its placement: the leading axis has its old
    length). Where ``nb' == nb`` the split is ``packed`` itself, no
    copy. ``facts`` are the ``eval.staged`` instant's counts."""
    lanes, nb, bs = packed.mask.shape
    dense_nb = min(nb, max(1, -(-n_real // (lanes * bs))))
    facts = {"nb": nb, "nb_dense": dense_nb, "real": n_real,
             "slots": lanes * dense_nb * bs, "bytes": 0}
    if dense_nb == nb:
        return packed, facts
    keep = lanes * dense_nb * bs

    def to_head(b: Batches) -> Batches:
        order = jnp.argsort(1 - b.mask.reshape(-1), stable=True)[:keep]

        def move(a: jax.Array) -> jax.Array:
            # a slot is one row (half the v5e's temporaries of a gather
            # of image-shaped slots: a sandbox compile, PR 33)
            rows = jnp.take(a.reshape((lanes * nb * bs, -1)), order, axis=0)
            return rows.reshape((lanes, dense_nb, bs) + a.shape[3:])

        return jax.tree.map(move, b)

    from jax.sharding import NamedSharding

    placed = [getattr(a, "sharding", None) for a in (packed.x, packed.y, packed.mask)]
    on_mesh = all(isinstance(s, NamedSharding) for s in placed)
    dense = jax.jit(to_head, out_shardings=Batches(*placed) if on_mesh else None)(packed)
    facts["bytes"] = sum(a.nbytes for a in jax.tree.leaves(dense))
    return dense, facts


def _audit_cases(ctx, ragged: bool):
    from ..analysis.compiled import LoweringCase

    params = ctx.abstract_params()

    def aggregate(global_params, server_state, stacked, weights, cohort, rng):
        # the stock FedAvg reduction — the shape every _aggregate
        # override (FedOpt/FedNova/defenses) is generic over
        return weighted_average(stacked, weights), server_state

    fn = jax.jit(
        build_round_fn(ctx.local_train_fn(), aggregate, ragged=ragged),
        donate_argnums=(0, 1),
    )
    n_total = max(ctx.cohort_buckets) * 2
    packed = ctx.abstract_batches(n_total)
    nsamples = ctx.sds((n_total,), "float32")
    return [
        LoweringCase(
            key=f"b{b}",
            fn=fn,
            args=(
                params, (), packed, nsamples,
                ctx.sds((b,), "int32"), ctx.abstract_key(),
            ),
            kwargs={"valid": ctx.sds((b,), "float32")},
        )
        for b in ctx.cohort_buckets
    ]


@auditable(
    "simulation.round_fn",
    donate=(0, 1),
    round_shaped=True,
    census_budget=lambda ctx: pow2_budget(ctx.cohort_buckets),
)
def _audit_round_fn_cases(ctx):
    """`fedml-tpu audit` provider: the EXACT round engine the runtime
    jits (same builder, same donation), lowered across the pow2 cohort
    census against ShapeDtypeStruct trees — no dataset, no params,
    nothing executed. The donation checker verifies the (0, 1)
    aliasing contract the round pipeline's K-in-flight chaining rides
    on; the host-transfer checker proves the hot loop is device-pure."""
    return _audit_cases(ctx, ragged=False)


@auditable(
    "simulation.round_fn_ragged",
    donate=(0, 1),
    round_shaped=True,
    census_budget=lambda ctx: pow2_budget(ctx.cohort_buckets),
)
def _audit_round_fn_ragged_cases(ctx):
    """The same engine as a ragged federation of a heavy model runs it
    (``ragged``: a ``lax.map`` over the lanes, each step loop ending at
    a bound read from the mask), under the same two checkers."""
    return _audit_cases(ctx, ragged=True)


@auditable(
    "simulation.round_fn_mesh",
    donate=(0, 1),
    round_shaped=True,
    census_budget=lambda ctx: pow2_budget(ctx.cohort_buckets),
)
def _audit_round_fn_mesh_cases(ctx):
    """`fedml-tpu audit` provider for the MESH round engine: the same
    builder the runtime jits, with the fed (data, fsdp) mesh built
    over whatever devices exist (CI lowers on one CPU device — a 1x1
    mesh; the sharding annotations, the (0, 1) donation aliasing and
    the host-transfer freedom of the lowered module are checked
    identically at any mesh size). The aggregation lowered here is the
    exact expansion fold the mesh path really runs
    (``exact_weighted_mean``) — zero host hops inside the round is a
    compile-time fact, not a benchmark observation."""
    import jax

    from ..analysis.compiled import LoweringCase
    from ..parallel.layout import build_fed_mesh, tree_shardings

    n = len(jax.devices())
    fsdp = 2 if n % 2 == 0 else 1
    mesh = build_fed_mesh(
        mesh_shape={"data": n // fsdp, "fsdp": fsdp},
        # lowering only — nothing executes, so the threefry stream
        # warning would be CI noise
        warn_nonpartitionable=False,
    )
    # lower against fsdp-AT-REST input shardings — what the runtime
    # commits (SimulatorMesh.shard_tree). Donation aliasing only
    # exists when the donated input's layout matches the constrained
    # output's, so an unsharded abstract input would under-report the
    # aliasing the real executable has (observed on the 8-device test
    # world: 0 of 2 aliased without this)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        ctx.abstract_params(),
        tree_shardings(ctx.abstract_params(), mesh),
    )

    def aggregate(global_params, server_state, stacked, weights, cohort, rng):
        return exact_weighted_mean(stacked, weights), server_state

    fn = jax.jit(
        build_round_fn(ctx.local_train_fn(), aggregate, mesh=mesh),
        donate_argnums=(0, 1),
    )
    n_total = max(ctx.cohort_buckets) * 2
    packed = ctx.abstract_batches(n_total)
    nsamples = ctx.sds((n_total,), "float32")
    return [
        LoweringCase(
            key=f"b{b}",
            fn=fn,
            args=(
                params, (), packed, nsamples,
                ctx.sds((b,), "int32"), ctx.abstract_key(),
            ),
            kwargs={"valid": ctx.sds((b,), "float32")},
        )
        for b in ctx.cohort_buckets
    ]


def deterministic_client_sampling(
    round_idx: int, client_num_in_total: int, client_num_per_round: int
) -> np.ndarray:
    """Reference determinism contract (FedAVGAggregator.py:99-113):
    MT19937 seeded with ``round_idx``, ``choice`` without replacement —
    via a local ``RandomState`` so the draws are identical to the
    reference's ``np.random.seed(round_idx)`` without clobbering the
    caller's global NumPy RNG state."""
    if client_num_in_total == client_num_per_round:
        return np.arange(client_num_in_total, dtype=np.int32)
    rs = np.random.RandomState(round_idx)
    # lint: host-sync-ok — rs.choice output is host numpy, no device value
    return np.asarray(
        rs.choice(range(client_num_in_total), client_num_per_round, replace=False),
        dtype=np.int32,
    )


class FedAvgAPI:
    """Single-host simulator for the FedAvg family.

    ``mode``: ``"vectorized"`` (default; vmap over the cohort) or
    ``"sequential"`` (python loop per client — the reference's §3.1
    shape, kept for debugging/parity runs).
    """

    algorithm = "FedAvg"
    # subclasses that need per-client params on the host (Shapley
    # scoring, secure aggregation) flip this to get the stacked cohort
    # params as a 4th round output
    _keep_stacked = False
    # subclasses whose server step IS the algorithm (FedOpt's optax
    # update, FedNova's normalized combine) flip this off so a custom
    # server_aggregator errors instead of being silently dropped
    _accepts_custom_aggregator = True

    def __init__(
        self,
        args,
        device,
        dataset: FederatedDataset,
        model: FedModel,
        mesh=None,
        client_trainer=None,
        server_aggregator=None,
    ) -> None:
        self.args = args
        self.device = device
        self.dataset = dataset
        self.model = model
        self.mesh = mesh
        if mesh is not None:
            from ..parallel.layout import is_fed_mesh
            from ..parallel.mesh import is_multi_controller

            self._multi_controller = is_multi_controller(mesh)
            # fed (data, fsdp) mesh: params shard at rest, the cohort
            # shards along 'data', and the plain-FedAvg aggregation
            # switches to the exact placement-independent expansion
            # fold (core/aggregation.exact_weighted_mean)
            self._fed_mesh = is_fed_mesh(mesh)
        else:
            self._multi_controller = False
            self._fed_mesh = False
        # persistent XLA compilation cache (core/compile_cache.py):
        # idempotent process-wide (fedml_tpu.init() already enabled it
        # for one-line runs), so every engine (sync loop, round
        # pipeline, planet loop, serving) shares one warm-start ledger
        from ..core.compile_cache import maybe_enable_compile_cache

        maybe_enable_compile_cache(args)
        if server_aggregator is not None and not self._accepts_custom_aggregator:
            raise ValueError(
                f"{self.algorithm} defines its own server aggregation; a "
                "custom server_aggregator would be ignored — not supported"
            )
        self.client_trainer = bind_operator(client_trainer, model, args)
        self.server_aggregator = bind_operator(server_aggregator, model, args)
        self.mode = getattr(args, "sim_mode", "vectorized")
        if self.mode == "sequential" and (
            self._keep_stacked
            or type(self)._preprocess is not FedAvgAPI._preprocess
        ):
            raise NotImplementedError(
                f"{self.algorithm} uses in-round hooks that only run in "
                "vectorized mode; sim_mode='sequential' is not supported"
            )
        self.history: List[Dict[str, float]] = []
        # populated by core/round_pipeline.py after train(): depth,
        # bucket, flushes, host_syncs_per_round; train() adds
        # store_stagings
        self.pipeline_stats: Dict[str, Any] = {}
        # (dataset.packed_train.x it was made from, the store): on the
        # object, so that dropping the API frees the store's copy too
        self._store: Optional[Tuple[jax.Array, Batches]] = None
        self._store_stagings = 0
        # (packed_train.x, packed_test.x they were made from, the two
        # splits an evaluation reads): held and freed the same way;
        # samples over slots in them
        self._eval_held: Optional[Tuple[jax.Array, jax.Array, Tuple[Batches, Batches]]] = None
        self._eval_real_share: Optional[float] = None

        self.rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
        self.rng, init_rng = jax.random.split(self.rng)
        self.global_params = model.init(init_rng)

        # round-indexed LR schedule (decay across the federation, not
        # within one local fit): None for lr_schedule=constant; loud
        # ValueError on the ambiguous step-indexed configuration
        self._round_lr = resolve_round_lr_schedule(args)
        if client_trainer is not None:
            if self._round_lr is not None:
                raise ValueError(
                    "lr_schedule with a custom client_trainer: the "
                    "trainer owns its optimizer, so the engine cannot "
                    "apply the round-indexed LR — implement the "
                    "schedule inside the trainer or use "
                    "lr_schedule=constant"
                )
            # L3 operator seam (core/frame.py): the custom trainer's
            # pure train fn replaces the stock one; the engine vmaps /
            # mesh-shards it identically.
            client_trainer.set_id(0)
            self._local_train = client_trainer.make_train_fn(args)
        else:
            prox_mu = (
                float(getattr(args, "fedprox_mu", 0.0))
                if self.algorithm == "FedProx"
                else 0.0
            )
            self._local_train = make_local_train_fn(
                # what a model counts (an expert layer's token loads;
                # most count nothing and lower as through apply) rides
                # in the round's metric outputs, fetched with the
                # deferred ones
                model.apply_counted,
                model.loss_fn,
                create_client_optimizer(
                    args,
                    lr=float(args.learning_rate)
                    if self._round_lr is not None
                    else None,
                ),
                epochs=int(args.epochs),
                prox_mu=prox_mu,
                shuffle=bool(getattr(args, "shuffle", True)),
                compute_dtype=compute_dtype_from_args(args),
            )
        self._eval = make_eval_fn(
            model.apply, model.loss_fn,
            compute_dtype=compute_dtype_from_args(args),
        )
        self.robust = (
            RobustAggregator(args) if getattr(args, "defense_type", None) else None
        )
        if self._fed_mesh and (
            self.robust is not None
            or self.server_aggregator is not None
            or type(self)._aggregate is not FedAvgAPI._aggregate
        ):
            # the mesh-shape bitwise-identity guarantee rides the exact
            # expansion fold, which only the plain FedAvg/FedProx
            # reduction uses; every other aggregation reduces the
            # sharded cohort through weighted_average-style ops whose
            # psum order depends on the mesh shape. Results are still
            # correct to float tolerance — but the degradation must be
            # LOUD, never discovered in a diff (docs/multichip.md)
            logging.warning(
                "(data, fsdp) mesh with %s: aggregation does not go "
                "through the exact expansion fold, so final params are "
                "correct to float tolerance but NOT bitwise identical "
                "across mesh shapes (the mesh-shape bitwise identity "
                "gate covers the plain FedAvg/FedProx path only)",
                "defense_type" if self.robust is not None
                else ("a custom server_aggregator"
                      if self.server_aggregator is not None
                      else f"algorithm {self.algorithm}"),
            )
        self.server_state = self._init_server_state()
        self._build_jitted()

        from ..core.telemetry import Telemetry
        from ..core.tracking import MetricsReporter, ProfilerEvent

        self.profiler = ProfilerEvent(args)
        # self.history is the round record of truth; the reporter only
        # fans out to sinks
        self.metrics_reporter = MetricsReporter(args, keep_history=False)
        # process-wide registry + flight recorder (core/telemetry.py):
        # profiler spans land on the trace.json timeline alongside the
        # round pipeline's dispatch/flush/drain events
        self.telemetry = Telemetry.get_instance(args)
        self.telemetry.attach_profiler(self.profiler)

    # -- algorithm hooks ----------------------------------------------
    def _init_server_state(self):
        return ()

    def _aggregate(
        self,
        global_params: Params,
        server_state,
        new_stacked: Params,
        weights: jax.Array,
        cohort: Batches,
        rng: jax.Array,
    ) -> Tuple[Params, Any]:
        """FedAvg: weighted average (fedavg_api.py:206-221)."""
        if self.server_aggregator is not None:
            # L3 operator seam: custom pure reduction, runs inside the
            # jitted round (robust/defense wrapping is then the custom
            # aggregator's own responsibility).
            return (
                self.server_aggregator.aggregate(
                    global_params, new_stacked, weights, rng
                ),
                server_state,
            )
        if self.robust is not None:
            return (
                self.robust.aggregate(new_stacked, weights, global_params, rng),
                server_state,
            )
        if getattr(self, "_fed_mesh", False):
            # the (data, fsdp) mesh path: a plain weighted_average over
            # a sharded client axis becomes partial sums + psum, whose
            # bits depend on the mesh shape. The exact expansion fold
            # is placement-independent, so every mesh shape — including
            # {data: 1} — finalizes to identical float32 params
            return exact_weighted_mean(new_stacked, weights), server_state
        return weighted_average(new_stacked, weights), server_state

    def _preprocess(self, cohort: Batches, server_state):
        """In-jit hook applied to the gathered cohort before local
        training (HS-FedAvg's FFT input normalization plugs in here)."""
        return cohort, server_state

    # -- engine -------------------------------------------------------
    def _build_jitted(self) -> None:
        # incremented at TRACE time (the python body runs only when jit
        # retraces) — the compile-count regression tests read this
        self._round_trace_count = 0

        def on_trace(idx) -> None:
            # trace-time only (the python body runs when jit traces):
            # counts EVERY trace, including the expected first compile
            # of each shape bucket — healthy runs show one per bucket;
            # more than that is a retrace storm, visible as a counter
            # and timeline instants instead of silent compile stalls
            self._round_trace_count += 1
            tel = getattr(self, "telemetry", None)
            if tel is not None and tel.enabled:
                tel.inc("pipeline_retraces_total")
                tel.recorder.instant(
                    "jit.retrace", cat="compile", bucket=int(idx.shape[0])
                )

        # read off the dataset, the trainer and the model where
        # sample_shape is read off the dataset: no flag, no model's name
        self._ragged = (
            self.mesh is None
            and self.client_trainer is None
            and self._has_empty_batches()
            and self._lane_step_floor() >= _HEAVY_LANE_STEP
        )
        round_fn = build_round_fn(
            self._local_train,
            self._aggregate,
            self._preprocess,
            mesh=self.mesh,
            use_round_lr=self._round_lr is not None,
            keep_stacked=self._keep_stacked,
            on_trace=on_trace,
            sample_shape=sample_store.sample_shape(self.dataset.packed_train),
            ragged=self._ragged,
        )
        self._round_fn = jax.jit(round_fn, donate_argnums=(0, 1))
        # donation deliberately NOT safe here: the sequential loop
        # calls this with the SAME self.global_params for every client
        # of the cohort — donating argnum 0 would invalidate the tree
        # the next client still trains from
        # lint: donation-ok — see comment above (sequential-mode reuse)
        self._local_train_j = jax.jit(self._local_train)
        self._eval_all = jax.jit(build_eval_all(self._eval))
        self._eval_global = jax.jit(self._eval)

    def _has_empty_batches(self) -> bool:
        """Does some client leave a whole batch of the packed
        ``num_batches`` empty? Read off the dataset's host counts; a
        federation packed to its clients' own length says no."""
        ns = self.dataset.packed_num_samples
        if ns is None or not len(ns):
            return False
        packed = self.dataset.packed_train
        fewest = int(np.ceil(np.min(ns) / packed.batch_size))  # lint: host-sync-ok — host counts
        return fewest < packed.num_batches

    def _lane_step_floor(self) -> int:
        """Multiply-adds one lane's step does at least: a parameter
        meets every sample of the batch once or more."""
        weights = sum(math.prod(a.shape) for a in jax.tree.leaves(self.global_params))
        return weights * self.dataset.packed_train.batch_size

    def _round_exec_name(self) -> str:
        """Registry name of the round executable this api dispatches —
        the ``executable`` tag on its ``exec_device_seconds`` series,
        matched against audit_report.json by ``fedml-tpu perf``."""
        if self.mesh is not None:
            return "simulation.round_fn_mesh"
        return "simulation.round_fn_ragged" if self._ragged else "simulation.round_fn"

    def _post_round_stacked(self, stacked: Params, idx: np.ndarray, rng) -> None:
        """Host-side hook fed the per-client cohort params when
        ``_keep_stacked`` is set (overridden by S-FedAvg / TurboAggregate)."""

    def _sample_store(self) -> Batches:
        """What ``_round_fn`` gathers the cohort from: the dataset's
        packed training split with every sample flattened
        (``core/sample_store.py``), made once per ``packed_train.x`` and
        before a loop's first round; the dataset's own ``Batches`` where
        its samples are one-dimensional already."""
        packed = self.dataset.packed_train
        if self._store is None or self._store[0] is not packed.x:
            self._store = None  # the old copy goes before the new one comes
            store, facts = sample_store.stage(packed)
            self._store = (packed.x, store)
            self._store_stagings += 1
            if self.telemetry.enabled:
                self.telemetry.recorder.instant("store.staged", cat="data", **facts)
        return self._store[1]

    def _eval_splits(self) -> Tuple[Batches, Batches]:
        """What an evaluation reads, training split and held-out split:
        the dataset's packed splits without the clients' padding
        (:func:`dense_eval_split`; the packed split itself where it has
        none to lose), made once per ``(packed_train.x, packed_test.x)``
        from the masks alone and before a loop's first round. Every
        loop's evaluation takes them from here."""
        train, test = self.dataset.packed_train, self.dataset.packed_test
        held = self._eval_held
        if held is None or held[0] is not train.x or held[1] is not test.x:
            self._eval_held = None  # the old copies go before the new ones come
            counts = np.asarray(real_slots(train.mask, test.mask)).tolist()  # lint: host-sync-ok — one fetch at set-up, outside the round loop
            (tr, tr_facts), (te, te_facts) = (
                dense_eval_split(split, n) for split, n in zip((train, test), counts)
            )
            share = self._eval_real_share = (tr_facts["real"] + te_facts["real"]) / (
                tr_facts["slots"] + te_facts["slots"])
            self._eval_held = (train.x, test.x, (tr, te))
            if self.telemetry.enabled:
                self.telemetry.set_gauge("pipeline_eval_real_share", share)
                self.telemetry.recorder.instant(
                    "eval.staged", cat="data",
                    bytes=tr_facts["bytes"] + te_facts["bytes"], real_share=share,
                    **{f"{name}_{k}": facts[k]
                       for name, facts in (("train", tr_facts), ("test", te_facts))
                       for k in ("nb", "nb_dense", "real")},
                )
        return self._eval_held[2]

    # -- reference-parity sampling ------------------------------------
    def _client_sampling(
        self, round_idx: int, client_num_in_total: int, client_num_per_round: int
    ) -> np.ndarray:
        return deterministic_client_sampling(
            round_idx, client_num_in_total, client_num_per_round
        )

    # -- round loop ----------------------------------------------------
    def train(self) -> Dict[str, float]:
        args = self.args
        from ..scale.engine import planet_knobs_active

        if planet_knobs_active(args):
            # registry-backed population plane (fedml_tpu/scale/): no
            # eager federation exists to pack — the planet loop samples
            # and materializes each round's cohort on demand
            packed = nsamples = None
        else:
            # jit inputs under multi-controller must be global arrays or
            # process-consistent host values — never locally-committed
            # device arrays (every process holds the same host copy)
            # (the sequential loop indexes the dataset's arrays by hand
            # and would hold a store for nothing)
            packed = (
                self.dataset.packed_train
                if self.mode == "sequential"
                else self._sample_store()
            )
            nsamples = (
                # one pre-loop conversion to a process-consistent host
                # value (multi-controller jit-input rule, comment above)
                np.asarray(self.dataset.packed_num_samples)  # lint: host-sync-ok
                if self._multi_controller
                else jnp.asarray(self.dataset.packed_num_samples)
            )
            self._eval_splits()
        comm_rounds = int(args.comm_round)
        freq = max(1, int(getattr(args, "frequency_of_the_test", 5)))
        ckpt, start_round = self._maybe_restore()
        if getattr(self, "_preempt_signal", None) is None:
            # the elastic seam (parallel/elastic.py): tests inject a
            # signal object directly; everyone else gets
            # it from the preempt_signal knob (validated to require
            # checkpoint_dir, so a notice always has somewhere durable
            # to land)
            from ..parallel.elastic import make_signal

            self._preempt_signal = make_signal(
                getattr(args, "preempt_signal", None)
            )
        # stall watchdog (core/telemetry.py): armed only when
        # args.stall_timeout_s > 0; observes the pipeline/comm
        # heartbeats and dumps a debug bundle to args.telemetry_dir
        watchdog = self.telemetry.maybe_start_watchdog(args)
        # pull-based /metrics endpoint (off unless args.metrics_port)
        # and on-demand per-round device profiling (args.profile_rounds)
        self.telemetry.maybe_start_metrics_server(args)
        from ..core.tracing import RoundProfiler

        self._round_profiler = RoundProfiler(args)
        try:
            # gc spans and the steal count, beside the loops' own phase
            # spans: what stalls a round from outside it
            with self.profiler.watch_stalls():
                stats = self._train_rounds(
                    packed, nsamples, comm_rounds, freq, ckpt, start_round
                )
            self.pipeline_stats["store_stagings"] = self._store_stagings
            if self._eval_real_share is not None:
                # samples over slots of one evaluation, both splits
                self.pipeline_stats["eval_real_share"] = self._eval_real_share
            return stats
        finally:
            if ckpt is not None:
                ckpt.close()
            self._round_profiler.close()
            if watchdog is not None:
                self.telemetry.stop_watchdog()
            self.telemetry.stop_metrics_server()
            # one perfetto-loadable trace.json + registry exposition per
            # run when args.telemetry_dir is set
            self.telemetry.export_run_artifacts(
                getattr(args, "telemetry_dir", None)
            )

    def _lr_mult(self, round_idx: int):
        """Round-indexed LR multiplier (schedule(r) / peak), or None.
        A numpy scalar: the jit treats it as a traced 0-d argument
        (compile once, vary per round), and it is a process-consistent
        host value under multi-controller."""
        if self._round_lr is None:
            return None
        return np.float32(
            # lint: host-sync-ok — the schedule and the knob are host scalars
            float(self._round_lr(round_idx)) / float(self.args.learning_rate)  # lint: host-sync-ok
        )

    def _train_rounds(
        self, packed, nsamples, comm_rounds, freq, ckpt, start_round
    ) -> Dict[str, float]:
        from ..scale.engine import PlanetRoundLoop, planet_knobs_active

        if planet_knobs_active(self.args):
            # registry-backed cohorts (ROADMAP item 2): O(cohort) host
            # memory per round from a million-client registry, two-tier
            # edge aggregation behind edge_num. The loop (registry +
            # per-shape jit cache) persists across train() calls so a
            # warm re-run replays with zero new compiles
            loop = getattr(self, "_planet_loop", None)
            if loop is None:
                loop = self._planet_loop = PlanetRoundLoop(self)
            return loop.run(
                packed, nsamples, comm_rounds, freq, ckpt, start_round
            )
        if self.mode != "sequential" and not self._keep_stacked:
            # the async executor (K rounds in flight, deferred metrics,
            # shape-bucketed compile cache); pipeline_depth=1 (default)
            # reproduces the synchronous loop's behavior and metrics
            from ..core.round_pipeline import RoundPipeline

            return RoundPipeline(self).run(
                packed, nsamples, comm_rounds, freq, ckpt, start_round
            )
        return self._train_rounds_sync(
            packed, nsamples, comm_rounds, freq, ckpt, start_round
        )

    def _train_rounds_sync(
        self, packed, nsamples, comm_rounds, freq, ckpt, start_round
    ) -> Dict[str, float]:
        """Synchronous loop: the sequential (per-client python loop)
        mode and the ``_keep_stacked`` algorithms, whose per-round host
        hooks (Shapley scoring, secure-agg staging) need the stacked
        cohort params on host every round."""
        args = self.args
        final_stats: Dict[str, float] = {}
        # the round pipeline's span names (docs/observability.md), as
        # far as this loop has the phase: it fetches inside `eval`, so
        # there is no `round.wait` and no `flush.fetch` here
        span = self.profiler.span
        for round_idx in range(start_round, comm_rounds):
            if getattr(self, "_round_profiler", None) is not None:
                self._round_profiler.tick(round_idx)
            with self.profiler.iteration_span("round", round=round_idx):
                with span("round.prep"):
                    t0 = time.perf_counter()
                    idx = self._client_sampling(
                        round_idx, self.dataset.client_num, int(args.client_num_per_round)
                    )
                    self.rng, round_rng = jax.random.split(self.rng)
                    if self._multi_controller:
                        round_rng = np.asarray(round_rng)  # lint: host-sync-ok — process-consistent host value (multi-controller rule)
                    lr_mult = self._lr_mult(round_idx)
                with span("round.dispatch"):
                    if self.mode == "sequential":
                        new_global, summed = self._sequential_round(
                            idx, round_rng, lr_mult, nsamples=nsamples
                        )
                        self.global_params = new_global
                    else:
                        extra = () if lr_mult is None else (lr_mult,)
                        with _devtime(
                            self._round_exec_name(), bucket=f"b{len(idx)}"
                        ):
                            out = self._round_fn(
                                self.global_params,
                                self.server_state,
                                packed,
                                nsamples,
                                np.asarray(idx) if self._multi_controller else jnp.asarray(idx),  # lint: host-sync-ok — idx is host numpy (sampling)
                                round_rng,
                                *extra,
                            )
                        self.global_params, self.server_state, summed = out[:3]
                        if self._keep_stacked:
                            self._post_round_stacked(out[3], idx, round_rng)
                if round_idx % freq == 0 or round_idx == comm_rounds - 1:
                    with span("eval"):
                        stats = self._local_test_on_all_clients(round_idx)
                    with span("flush.report"):
                        stats["round"] = round_idx
                        stats["round_time_s"] = time.perf_counter() - t0
                        # eval-round metric fetch: the sync loop fetches at its
                        # eval cadence by design (the pipelined loop defers)
                        stats["train_loss_cohort"] = float(summed["loss_sum"]) / max(  # lint: host-sync-ok
                            float(summed["count"]), 1.0  # lint: host-sync-ok — same eval-round fetch
                        )
                        stats.update(model_counters(summed))
                        stats.update(lane_steps(summed))
                        self.history.append(stats)
                        final_stats = stats
                        self.metrics_reporter.report_server_training_metric(stats)
                saved = False
                if ckpt is not None and (
                    (round_idx + 1) % self._ckpt_freq == 0
                    or round_idx == comm_rounds - 1
                ):
                    with span("round.ckpt"):
                        self._save_checkpoint(ckpt, round_idx)
                    saved = True
                self._maybe_preempt(ckpt, round_idx, saved=saved)
        return final_stats

    # -- elastic preemption seam (parallel/elastic.py) ----------------
    def _maybe_preempt(self, ckpt, round_idx: int, saved: bool = False) -> None:
        """Poll the preemption signal at the round boundary; on notice,
        make the drained round durable (WAL ``kind="preempt"``
        write-ahead of a forced checkpoint) and raise ``Preempted`` —
        the clean controlled exit a restart on the surviving devices
        resumes from bitwise-identically. ``saved=True`` means the
        cadence block already published this round's step."""
        signal = getattr(self, "_preempt_signal", None)
        if signal is None:
            return
        notice = signal.poll(int(round_idx))  # lint: host-sync-ok — round_idx is the host loop counter, never a device array
        if notice is None:
            return
        from ..parallel.elastic import preempt_now

        preempt_now(self, ckpt, int(round_idx), notice, saved=saved)  # lint: host-sync-ok — host loop counter (see poll above)

    # -- checkpoint / resume (new vs reference — SURVEY.md §5) --------
    def _maybe_restore(self):
        ckpt_dir = getattr(self.args, "checkpoint_dir", None)
        if not ckpt_dir:
            return None, 0
        from flax.serialization import from_state_dict, to_state_dict

        from ..core.checkpoint import RoundCheckpointer

        # None = this scenario's historical cadence (every 10 rounds)
        self._ckpt_freq = max(
            1, int(getattr(self.args, "checkpoint_freq", None) or 10)
        )
        ckpt = RoundCheckpointer(ckpt_dir)
        restored = self._restore_state(ckpt, to_state_dict)
        start_round = 0
        if restored is not None:
            from ..parallel.layout import is_fed_mesh, shard_tree

            self.global_params = jax.tree.map(
                jnp.asarray, from_state_dict(self.global_params, restored["params"])
            )
            mesh = getattr(self, "mesh", None)
            if mesh is not None and is_fed_mesh(mesh):
                # elastic resume: land the restored params at-rest on
                # the CURRENT (possibly reshaped) mesh — a raw-fallback
                # restore leaves them committed to one device, which
                # would pin every downstream jit there
                self.global_params = shard_tree(self.global_params, mesh)
            self.server_state = from_state_dict(
                self.server_state, restored["server_state"]
            )
            self.rng = jnp.asarray(
                np.asarray(restored["rng"]),  # lint: host-sync-ok — restore-time scalar pair, once per run; breaks the restore's single-device commitment
                dtype=jnp.uint32,
            )
            start_round = int(restored["round_idx"]) + 1  # lint: host-sync-ok — restore-time scalar, once per run
            self._restore_extra_state(restored.get("extra"))
            self._note_elastic_resume(ckpt, start_round)
            logging.info("resuming from round %d", start_round)
        self._to_state_dict = to_state_dict
        return ckpt, start_round

    def _restore_state(self, ckpt, to_state_dict):
        """Restore the latest step — device-direct onto the CURRENT
        mesh layout when one exists (the elastic resume path: a run
        preempted on 8 devices restores straight onto the surviving
        4-device mesh's NamedShardings, no host staging of the full
        model), raw host restore otherwise. A shaped target that the
        saved tree refuses (structure drift across versions, an
        ``extra`` block appearing/vanishing) falls back to the raw
        restore rather than failing the resume."""
        from ..parallel.layout import is_fed_mesh, shard_tree

        mesh = getattr(self, "mesh", None)
        if mesh is not None and is_fed_mesh(mesh):
            # the target's leaves carry the CURRENT mesh's at-rest
            # NamedShardings, so orbax restores each param straight
            # onto the surviving layout — no host staging of the model
            target = {
                "params": shard_tree(self.global_params, mesh),
                "server_state": to_state_dict(self.server_state),
                "rng": self.rng,
                "round_idx": 0,
            }
            extra = self._extra_checkpoint_state()
            if extra is not None:
                target["extra"] = extra
            try:
                return ckpt.restore(target=target)
            except Exception:  # noqa: BLE001 — shaped-restore drift
                logging.warning(
                    "mesh-targeted restore failed; retrying as raw "
                    "host restore", exc_info=True,
                )
        return ckpt.restore()

    def _note_elastic_resume(self, ckpt, start_round: int) -> None:
        """If the WAL's last word was ``kind="preempt"``, this restore
        IS the elastic resume: append the paired ``kind="resume"``
        record (the invariant checker's restorability evidence —
        ``preempt_paired_with_checkpoint``) and count it. A checkpoint
        dir with no WAL (or a WAL ending in an ordinary round record)
        is a plain restart — no record, no counter."""
        from ..core.checkpoint import RoundWAL

        wal = RoundWAL(ckpt.dir)
        last = wal.last()
        if last is None or last.get("kind") != "preempt":
            return
        from ..parallel.elastic import _mesh_devices, _mesh_shape

        mesh = getattr(self, "mesh", None)
        wal.append(
            int(start_round),  # lint: host-sync-ok — restore-time python scalar, once per run
            int(last.get("ckpt_step") or 0),  # lint: host-sync-ok — JSON field from the WAL, host-only
            [],
            kind="resume",
            extra={
                "devices": _mesh_devices(mesh),
                "mesh_shape": _mesh_shape(mesh),
            },
        )
        tel = getattr(self, "telemetry", None)
        if tel is not None and tel.enabled:
            tel.inc("elastic_resumes_total")
        logging.warning(
            "elastic resume: preempt record at round %s consumed; "
            "continuing from round %d on %d device(s)",
            last.get("round_idx"), int(start_round),  # lint: host-sync-ok — restore-time python scalar, once per run
            len(_mesh_devices(mesh)) or 1,
        )

    def _extra_checkpoint_state(self):
        """Algorithm-side host state to persist (S-FedAvg reputation)."""
        return None

    def _restore_extra_state(self, extra) -> None:
        pass

    def _save_checkpoint(self, ckpt, round_idx: int) -> None:
        state = {
            "params": self.global_params,
            "server_state": self._to_state_dict(self.server_state),
            "rng": self.rng,
            "round_idx": round_idx,
        }
        extra = self._extra_checkpoint_state()
        if extra is not None:
            state["extra"] = extra
        ckpt.save(round_idx, state)

    def _sequential_round(
        self, idx: np.ndarray, rng: jax.Array, lr_mult=None, nsamples=None
    ):
        """Reference §3.1 shape: python loop over sampled clients.

        Per-client work stays a device dispatch; sample counts are
        gathered in ONE device op at round end from the ``nsamples``
        array the caller already placed (the old per-client
        ``float(...)`` forced a host round-trip inside the loop)."""
        stacked_leaves: List[Params] = []
        sums = None
        extra = () if lr_mult is None else (lr_mult,)
        for j, i in enumerate(idx):
            client = Batches(
                x=self.dataset.packed_train.x[i],
                y=self.dataset.packed_train.y[i],
                mask=self.dataset.packed_train.mask[i],
            )
            p, m = self._local_train_j(
                self.global_params, client, jax.random.fold_in(rng, j), *extra
            )
            stacked_leaves.append(p)
            sums = m if sums is None else jax.tree.map(jnp.add, sums, m)
        from ..core.aggregation import stack_pytrees

        stacked = stack_pytrees(stacked_leaves)
        if nsamples is None:
            nsamples = jnp.asarray(self.dataset.packed_num_samples)
        ns = jnp.take(jnp.asarray(nsamples), jnp.asarray(idx))
        weights = normalize_weights(ns)
        new_global, self.server_state = self._aggregate(
            self.global_params, self.server_state, stacked, weights, None, rng
        )
        return new_global, sums

    # -- evaluation (fedavg_api.py:238 _local_test_on_all_clients) ----
    def _local_test_on_all_clients(self, round_idx: int) -> Dict[str, float]:
        """Loss and accuracy over every client's training samples and
        over every client's held-out ones: sums over samples, so read
        from :meth:`_eval_splits` (the packed splits less the clients'
        padding), not from the per-client packing."""
        train, test = self._eval_splits()
        train_sums = self._eval_all(self.global_params, train)
        test_sums = self._eval_all(self.global_params, test)
        tr = self.model.metrics_from_sums(train_sums)
        te = self.model.metrics_from_sums(test_sums)
        return {
            "train_acc": tr["acc"],
            "train_loss": tr["loss"],
            "test_acc": te["acc"],
            "test_loss": te["loss"],
        }

    def evaluate_global(self) -> Dict[str, float]:
        sums = self._eval_global(self.global_params, self.dataset.test_data_global)
        return self.model.metrics_from_sums(sums)


class FedProxAPI(FedAvgAPI):
    """FedProx = FedAvg + proximal term in the client loss
    (``mpi_p2p_mp/fedprox`` trainer semantics; ``args.fedprox_mu``)."""

    algorithm = "FedProx"


class FedOptAPI(FedAvgAPI):
    """Server-side adaptive optimization
    (``fedopt/fedopt_api.py`` + ``FedOptAggregator.py:81-130``): the
    averaged client delta is a pseudo-gradient fed to an optax server
    optimizer (sgd/momentum/adam/adagrad/yogi replaces OptRepo)."""

    algorithm = "FedOpt"
    _accepts_custom_aggregator = False

    def _init_server_state(self):
        self._server_opt = create_server_optimizer(self.args)
        return self._server_opt.init(self.global_params)

    def _aggregate(self, global_params, server_state, new_stacked, weights, cohort, rng):
        avg = weighted_average(new_stacked, weights)
        pseudo_grad = jax.tree.map(lambda g, a: g - a, global_params, avg)
        updates, new_state = self._server_opt.update(
            pseudo_grad, server_state, global_params
        )
        import optax

        new_global = optax.apply_updates(global_params, updates)
        return new_global, new_state


class FedNovaAPI(FedAvgAPI):
    """Normalized averaging (``fednova/fednova.py:12-169``,
    ``fednova_trainer.py:136-165``): clients' deltas are normalized by
    their local step counts a_i, then recombined with
    tau_eff = sum(p_i a_i):  w+ = w - tau_eff * sum(p_i (w - w_i)/a_i).
    a_i = epochs * (# non-empty batches) — exact for the plain-SGD
    client optimizer (momentum-corrected a_i is a later extension)."""

    algorithm = "FedNova"
    _accepts_custom_aggregator = False

    def _aggregate(self, global_params, server_state, new_stacked, weights, cohort, rng):
        if cohort is None:
            raise NotImplementedError("FedNova requires vectorized mode")
        epochs = float(self.args.epochs)
        nonempty = (cohort.mask.sum(axis=-1) > 0).astype(jnp.float32).sum(axis=-1)
        a_i = jnp.maximum(epochs * nonempty, 1.0)  # [C]
        tau_eff = (weights * a_i).sum()

        def combine(g, s):
            w = weights.reshape((-1,) + (1,) * (g.ndim)).astype(g.dtype)
            ai = a_i.reshape((-1,) + (1,) * (g.ndim)).astype(g.dtype)
            norm_delta = (g[None] - s) / ai  # [C, ...]
            return g - tau_eff * (w * norm_delta).sum(axis=0)

        return jax.tree.map(combine, global_params, new_stacked), server_state


def _algorithms():
    from .decentralized import DecentralizedDSGDAPI, DecentralizedPushSumAPI
    from .defenses import HSFedAvgAPI, SFedAvgAPI
    from .fedgan import FedGANAPI
    from .fednas import FedNASAPI
    from .hierarchical_fl import HierarchicalFLAPI
    from .split_learning import FedGKTAPI, SplitNNAPI, VFLAPI
    from .turboaggregate import TurboAggregateAPI

    return {
        "FedAvg": FedAvgAPI,
        "FedProx": FedProxAPI,
        "FedOpt": FedOptAPI,
        "FedNova": FedNovaAPI,
        "HierFedAvg": HierarchicalFLAPI,
        "DSGD": DecentralizedDSGDAPI,
        "PushSum": DecentralizedPushSumAPI,
        "SFedAvg": SFedAvgAPI,
        "HSFedAvg": HSFedAvgAPI,
        "FedGAN": FedGANAPI,
        "TurboAggregate": TurboAggregateAPI,
        "SplitNN": SplitNNAPI,
        "FedGKT": FedGKTAPI,
        "VFL": VFLAPI,
        "FedNAS": FedNASAPI,
    }


_ALGORITHMS = None


def get_algorithms():
    """Name -> API class registry (lazy to avoid circular imports)."""
    global _ALGORITHMS
    if _ALGORITHMS is None:
        _ALGORITHMS = _algorithms()
    return _ALGORITHMS
