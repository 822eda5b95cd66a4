"""Registry-backed round loop: 10k-client cohorts from a 1M registry.

The stock simulator round fn gathers the sampled cohort out of an
eagerly packed federation tensor — O(total-clients) host memory before
the first round. This loop inverts that: the population lives as the
columnar ``ClientRegistry`` (bytes per client), and each round
materializes ONLY its cohort:

    sample (Floyd, O(cohort))
      -> pack (pow2 nb x pow2 client buckets, LPT-balanced groups)
      -> materialize per group (labels host-side, features synthesized
         on device)
      -> vmap local training per group (one jit per (bucket, nb) shape
         — the compile census is the pow2 product, not the cohort)
      -> per-(group, edge) weighted partial sums, folded through the
         two-tier ``EdgeAggregationTree`` (``edge_num >= 2``) or a flat
         ``StreamingAccumulator`` — bit-identical either way
      -> O(model) finalize.

Peak host memory per round is O(cohort x client-data), independent of
registry size — bounded as an RSS delta on a 1M-client registry by
tests/test_planet_scale.py. Eval runs on the dataset's global holdout packs (the
per-client eval dicts the eager loader builds do not exist here).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from ..analysis.compiled import auditable, pow2_budget
from ..core.aggregation import StreamingAccumulator
from ..core.devtime import measure as _devtime
from .cohort import pack_cohort
from .registry import ClientRegistry
from .tree import EdgeAggregationTree

Params = Any

__all__ = ["PlanetRoundLoop", "build_group_fn", "planet_knobs_active"]


def build_group_fn(
    local_train,
    *,
    edge_num: int = 0,
    use_round_lr: bool = False,
    mesh=None,
    on_trace=None,
):
    """The per-(bucket, nb) group computation, as a pure function of
    its collaborators — vmap local training over the group's client
    axis, then each edge's weighted partial sum in one fused reduction
    (the term-rounding step of the streaming fold, computed groupwise).

    Module-level for the same reasons as ``fedavg_api.build_round_fn``:
    the jitted body must not close over a mutable loop object (retrace
    hazard), and the compiled-artifact auditor AOT-lowers this exact
    computation across the (bucket, nb) census without a registry or
    data. ``on_trace`` fires at trace time only. Returns the UNjitted
    function; callers own the ``jax.jit``.

    Donation contract (audited): ``global_params`` is returned as the
    FIRST output, unchanged — callers jit with ``donate_argnums=(0,)``
    and rebind their carry to that output per group
    (``gp, terms, ... = group_fn(gp, ...)``), so XLA aliases the
    buffer instead of copying the whole model into every group call
    (the old zero-aliasing TODO in audit_baseline.json).

    ``mesh`` (a fed ``(data, fsdp)`` mesh, ``parallel/layout.py``)
    shards the group's client axis along ``data`` and gathers the
    fsdp-sharded-at-rest params replicated for per-client compute —
    every chip trains a slice of every (bucket, nb) group.
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.layout import is_fed_mesh

    fed = mesh is not None and is_fed_mesh(mesh)
    E = max(1, edge_num)

    def group_fn(global_params, batches, ns, valid, edge_onehot, rng,
                 lr_mult=1.0):
        if on_trace is not None:
            on_trace()
        C = batches.mask.shape[0]
        vm = valid.reshape((-1,) + (1,) * (batches.mask.ndim - 1))
        masked = batches.replace(
            mask=batches.mask * vm.astype(batches.mask.dtype)
        )
        train_params = global_params
        if fed:
            from ..parallel.layout import fed_compute_constraints

            # the shared fed entry discipline (cohort along 'data',
            # params + routing scalars gathered replicated)
            train_params, masked, ns, valid, edge_onehot = (
                fed_compute_constraints(
                    mesh, global_params, masked, ns, valid, edge_onehot
                )
            )
        rngs = jax.random.split(rng, C)
        if use_round_lr:
            stacked, metrics = jax.vmap(
                local_train, in_axes=(None, 0, 0, None)
            )(train_params, masked, rngs, lr_mult)
        else:
            stacked, metrics = jax.vmap(
                local_train, in_axes=(None, 0, 0)
            )(train_params, masked, rngs)
        if fed:
            from ..parallel.layout import pin_cohort_outputs

            # per-client compute stays whole (see pin_cohort_outputs)
            stacked = pin_cohort_outputs(mesh, stacked)
        w = ns * valid  # [C]; padded slots weigh zero

        def edge_sums(leaf):
            # [C, ...] x [C, E] -> [E, ...]: each edge's weighted
            # partial sum in one fused reduction — the term-rounding
            # step of the streaming fold, computed groupwise
            flat = leaf.astype(jnp.float32).reshape(C, -1)
            out = jnp.einsum("cf,ce->ef", w[:, None] * flat, edge_onehot)
            return out.reshape((E,) + leaf.shape[1:])

        terms = jax.tree.map(edge_sums, stacked)
        edge_w = jnp.einsum("c,ce->e", w, edge_onehot)
        summed = {k: v.sum() for k, v in metrics.items()}
        return global_params, terms, edge_w, summed

    return group_fn


@auditable(
    "planet.group_fn",
    # global_params rides through as output 0 and every call site
    # rebinds its carry to it (gp, ... = group_fn(gp, ...)), so the
    # donation aliases the whole model tree — the audit_baseline.json
    # zero-aliasing TODO this executable used to carry is burned down
    donate=(0,),
    round_shaped=True,
    census_budget=lambda ctx: (
        pow2_budget(ctx.cohort_buckets) * pow2_budget(ctx.nb_census)
    ),
)
def _audit_group_fn_cases(ctx):
    """`fedml-tpu audit` provider: the EXACT per-(bucket, nb) group
    computation the planet loop jits, lowered across the two-axis pow2
    census with no registry and no data — donation of the per-group
    ``global_params`` rebind included."""
    import jax

    from ..analysis.compiled import LoweringCase

    fn = jax.jit(build_group_fn(
        ctx.local_train_fn(), edge_num=ctx.edge_num,
    ), donate_argnums=(0,))
    params = ctx.abstract_params()
    E = max(1, ctx.edge_num)
    return [
        LoweringCase(
            key=f"b{b}xnb{nb}",
            fn=fn,
            args=(
                params,
                ctx.abstract_group_batches(b, nb),
                ctx.sds((b,), "float32"),
                ctx.sds((b,), "float32"),
                ctx.sds((b, E), "float32"),
                ctx.abstract_key(),
            ),
        )
        for b in ctx.cohort_buckets
        for nb in ctx.nb_census
    ]


def planet_knobs_active(args) -> bool:
    """True when the registry-backed population plane is requested."""
    return int(getattr(args, "client_registry_size", 0) or 0) > 0


class PlanetRoundLoop:
    """Drives a FedAvg API's training over a ``ClientRegistry``.

    Constructed once and CACHED on the API across ``train()`` calls
    (``fedavg_api._planet_loop``) — the persistence is load-bearing:
    the trace-count/shape-key census requires the jit cache to
    survive repeat ``train()`` calls (a warm replay compiles nothing
    new). Owns the registry, the per-round
    pack/materialize/train/fold sequence, and the group-shaped jit
    cache. ``stats`` after ``run``: cohort size, edge count, trace
    count, shape-key census, waste fraction.
    """

    def __init__(self, api) -> None:
        self.api = api
        args = api.args
        self._validate(api)
        self.cohort_size = int(
            getattr(args, "cohort_size", 0) or 0
        ) or int(args.client_num_per_round)
        self.edge_num = int(getattr(args, "edge_num", 0) or 0)
        self.registry = ClientRegistry(
            int(args.client_registry_size),
            seed=int(getattr(args, "random_seed", 0)),
            memmap_dir=getattr(args, "registry_dir", None),
        )
        if self.cohort_size > self.registry.size:
            raise ValueError(
                f"cohort_size={self.cohort_size} exceeds "
                f"client_registry_size={self.registry.size}"
            )
        ds = api.dataset
        self.class_num = int(ds.class_num)
        # feature geometry comes from the global eval pack: [nb, bs, *F]
        self.feature_shape = tuple(
            int(d) for d in ds.test_data_global.x.shape[2:]
        )
        self.sigma = float(getattr(args, "synthetic_sigma", 1.0) or 1.0)
        self.waste_cap = float(getattr(args, "packing_waste_cap", 4.0) or 4.0)
        self.stats: Dict[str, Any] = {}
        # one jitted group fn per (bucket, nb) shape — counted at trace
        # time like the round fn's _round_trace_count
        self._group_fn = None
        self._trace_count = 0
        self._shape_keys_seen: set = set()
        self._trunc_warned = False

    @staticmethod
    def _validate(api) -> None:
        from ..parallel.layout import is_fed_mesh

        args = api.args
        unsupported = []
        if getattr(api, "mesh", None) is not None and not is_fed_mesh(api.mesh):
            # the fed (data, fsdp) mesh shards the (bucket, nb) group
            # fns across the chips (ROADMAP item 1); the legacy
            # 'clients' mesh pre-shards an eager federation tensor this
            # loop never builds
            unsupported.append("the legacy (clients) mesh")
        if getattr(api, "server_aggregator", None) is not None:
            unsupported.append("a custom server_aggregator")
        if getattr(api, "robust", None) is not None:
            unsupported.append(f"defense_type={args.defense_type!r}")
        if getattr(api, "_keep_stacked", False):
            unsupported.append(f"algorithm {api.algorithm} (stacked hooks)")
        if getattr(args, "sim_mode", "vectorized") != "vectorized":
            unsupported.append(f"sim_mode={args.sim_mode!r}")
        if api.algorithm not in ("FedAvg", "FedProx"):
            unsupported.append(
                f"federated_optimizer={api.algorithm} (custom server step)"
            )
        if getattr(api.dataset, "task", "classification") != "classification":
            unsupported.append(f"task={api.dataset.task!r}")
        if unsupported:
            raise ValueError(
                "client_registry_size: the registry-backed round loop "
                "aggregates via the streaming fold and synthesizes "
                "cohort data on demand; unsupported with "
                + ", ".join(unsupported)
            )

    # -- jitted group computation -------------------------------------
    def _build_group_fn(self):
        import jax

        api = self.api

        def on_trace() -> None:
            # trace-time only (the python body runs when jit retraces):
            # one trace per (bucket, nb) shape is the healthy census
            self._trace_count += 1

        return jax.jit(build_group_fn(
            api._local_train,
            edge_num=self.edge_num,
            use_round_lr=api._round_lr is not None,
            mesh=getattr(api, "mesh", None),
            on_trace=on_trace,
        ), donate_argnums=(0,))

    # -- round loop ---------------------------------------------------
    def run(
        self, packed, nsamples, comm_rounds: int, freq: int, ckpt, start_round: int
    ) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        api = self.api
        args = api.args
        del packed, nsamples  # registry mode has no eager federation
        if self._group_fn is None:
            self._group_fn = self._build_group_fn()
        tel = getattr(api, "telemetry", None)
        tel = tel if tel is not None and tel.enabled else None
        E = max(1, self.edge_num)
        # edge_flat_fold is the tests' A/B harness: terms still
        # partition per edge (identical term set, identical rounding)
        # but fold into ONE flat accumulator — the baseline the tree's
        # bit-identity is asserted against
        flat_fold = bool(getattr(args, "edge_flat_fold", False))
        tree = (
            EdgeAggregationTree(api.global_params, self.edge_num)
            if self.edge_num >= 2 and not flat_fold
            else None
        )
        ckpt_freq = getattr(api, "_ckpt_freq", 1)
        final_stats: Dict[str, float] = {}
        waste_fracs: List[float] = []
        x_dtype = api.dataset.test_data_global.x.dtype

        mesh = getattr(api, "mesh", None)
        profiler = getattr(api, "_round_profiler", None)
        for round_idx in range(start_round, comm_rounds):
            if profiler is not None:
                profiler.tick(round_idx)
            t0 = time.perf_counter()
            # the per-round donated carry: every group call rebinds it
            # (gp, terms, ... = group_fn(gp, ...)) so the model buffer
            # is aliased through the whole round instead of copied per
            # group. On a fed mesh the carry is placed fsdp-sharded at
            # rest first (finalize hands back an unplaced host tree).
            gp = api.global_params
            if mesh is not None:
                from ..parallel.layout import shard_tree

                gp = shard_tree(gp, mesh)
            idx = self.registry.sample_cohort(round_idx, self.cohort_size)
            plan = pack_cohort(
                self.registry.num_samples[idx],
                idx,
                int(args.batch_size),
                speed_tier=self.registry.speed_tier[idx],
                waste_cap=self.waste_cap,
                telemetry=tel,
            )
            waste_fracs.append(plan.waste_frac)
            if not self._trunc_warned:
                # no silent caps — but once per loop, not per group per
                # round (the eager loader's warn-once-at-load
                # semantics). The flag burns only on OBSERVED
                # truncation: an all-light round 0 must not silence a
                # long-tail round 1.
                total = int(self.registry.num_samples[idx].sum())  # lint: host-sync-ok — registry columns are host NumPy
                packed = int(
                    sum(g.num_samples.sum() for g in plan.groups)
                )
                if packed < total:
                    self._trunc_warned = True
                    logging.warning(
                        "planet cohort packing: long-tail truncation — "
                        "dropping %d/%d samples (%.2f%%) this round "
                        "under packing_waste_cap=%.1f (similar every "
                        "round; raise args.packing_waste_cap to keep "
                        "them)",
                        total - packed, total,
                        100.0 * (total - packed) / max(total, 1),
                        self.waste_cap,
                    )
            api.rng, round_rng = jax.random.split(api.rng)
            lr_mult = api._lr_mult(round_idx)
            extra = () if lr_mult is None else (lr_mult,)
            acc = tree if tree is not None else StreamingAccumulator(
                api.global_params
            )
            summed = None
            for g_i, group in enumerate(plan.groups):
                if group.shape_key not in self._shape_keys_seen:
                    self._shape_keys_seen.add(group.shape_key)
                    if tel is not None:
                        tel.recorder.instant(
                            "planet.trace", cat="compile",
                            bucket=group.bucket, nb=group.nb,
                        )
                batches, _ = self.registry.materialize_group(
                    group.client_idx, group.nb, int(args.batch_size),
                    self.feature_shape, self.class_num,
                    sigma=self.sigma, dtype=x_dtype,
                )
                # edge routing is a property of the CLIENT (registry id
                # mod E), not of its slot — stable across cohorts
                onehot = np.zeros((group.bucket, E), dtype=np.float32)
                onehot[np.arange(group.bucket), group.client_idx % E] = 1.0
                with _devtime(
                    "planet.group_fn", bucket=f"b{group.bucket}xnb{group.nb}"
                ):
                    gp, terms, edge_w, m = self._group_fn(
                        gp,
                        batches,
                        jnp.asarray(group.num_samples),
                        jnp.asarray(group.valid),
                        jnp.asarray(onehot),
                        jax.random.fold_in(round_rng, g_i),
                        *extra,
                    )
                # deliberate O(E)-scalar fetch: the per-edge fold
                # weights drive host-side python fold bookkeeping
                # (StreamingAccumulator.total_w is an exact python-
                # float sum by design); the model-sized terms stay on
                # device
                edge_w = np.asarray(edge_w, dtype=np.float64)  # lint: host-sync-ok — O(E) scalars (comment above)
                for e in range(E):
                    if edge_w[e] <= 0.0:
                        continue
                    term_e = jax.tree.map(lambda x: x[e], terms)
                    target = acc.acc(e) if tree is not None else acc
                    target.fold_weighted_term(term_e, float(edge_w[e]))  # lint: host-sync-ok — host numpy scalar
                summed = (
                    m if summed is None
                    else jax.tree.map(jnp.add, summed, m)
                )
            api.global_params = self._finalize_into(acc)
            if tree is not None:
                tree.reset()
            if tel is not None:
                tel.inc("pipeline_rounds_dispatched_total")
                tel.heartbeat("pipeline.round", round_idx)

            if round_idx % freq == 0 or round_idx == comm_rounds - 1:
                stats = self._eval_round(round_idx, summed, t0)
                api.history.append(stats)
                final_stats = stats
                api.metrics_reporter.report_server_training_metric(stats)
            saved = False
            if ckpt is not None and (
                (round_idx + 1) % ckpt_freq == 0
                or round_idx == comm_rounds - 1
            ):
                api._save_checkpoint(ckpt, round_idx)
                saved = True
            # elastic seam: the registry round is fully drained here
            # (finalize() collapsed the fold on host), so a notice
            # forces a durable exit the reshaped-mesh restart resumes
            # from — registry sampling is host-deterministic per round,
            # so the resumed cohorts replay identically
            api._maybe_preempt(ckpt, round_idx, saved=saved)

        self.stats = {
            "registry_clients": self.registry.size,
            "registry_bytes": self.registry.nbytes(),
            "cohort_size": self.cohort_size,
            "edge_num": self.edge_num,
            "rounds": comm_rounds - start_round,
            "trace_count": self._trace_count,
            "shape_keys": sorted(self._shape_keys_seen),
            # lint: host-sync-ok — waste_fracs is a host list of python floats
            "waste_frac_mean": float(np.mean(waste_fracs))
            if waste_fracs else 0.0,
        }
        api.pipeline_stats = self.stats
        if tel is not None:
            tel.set_gauge("registry_clients", self.registry.size)
        logging.debug("planet round loop: %s", self.stats)
        return final_stats

    def _finalize_into(self, acc) -> Params:
        """Finalize whichever fold topology served the round; cast back
        to the template dtypes happens inside finalize()."""
        return acc.finalize()

    def _eval_round(self, round_idx, summed, t0) -> Dict[str, float]:
        api = self.api
        with api.profiler.span("eval"):
            tr = api.model.metrics_from_sums(
                api._eval_global(
                    api.global_params, api.dataset.train_data_global
                )
            )
            te = api.model.metrics_from_sums(
                api._eval_global(
                    api.global_params, api.dataset.test_data_global
                )
            )
        stats = {
            "train_acc": tr["acc"],
            "train_loss": tr["loss"],
            "test_acc": te["acc"],
            "test_loss": te["loss"],
            "round": round_idx,
            "round_time_s": time.perf_counter() - t0,
        }
        if summed is not None:
            # eval-round metric fetch: metrics leave the device here by
            # design (the eval cadence IS the sync cadence)
            stats["train_loss_cohort"] = float(summed["loss_sum"]) / max(  # lint: host-sync-ok
                float(summed["count"]), 1.0  # lint: host-sync-ok — same eval-round fetch
            )
        return stats
