"""Mesh (multi-chip) simulation tests on the 8-device virtual CPU mesh.

Key property: sharding the cohort's client axis over the mesh is a
*layout* choice — results must match the unsharded single-chip run
exactly. This is the TPU analog of the reference running the same
algorithm under its SP and MPI simulators (SURVEY.md §4).
"""

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.data import load
from fedml_tpu.parallel.mesh import build_mesh, shard_federation
from fedml_tpu.simulation import FedAvgAPI, SimulatorMesh, SimulatorSingleProcess


def _args(make, **kw):
    base = dict(
        dataset="mnist",
        synthetic_train_size=600,
        synthetic_test_size=120,
        model="lr",
        partition_method="hetero",
        client_num_in_total=16,
        client_num_per_round=8,
        comm_round=2,
        epochs=1,
        batch_size=16,
        learning_rate=0.05,
        frequency_of_the_test=1,
        shuffle=False,
    )
    base.update(kw)
    return make(**base)


@pytest.mark.smoke
class TestMesh:
    def test_build_mesh_shapes(self, eight_devices):
        m = build_mesh()
        assert m.shape == {"clients": 8}
        m2 = build_mesh(mesh_shape={"clients": 4, "data": 2})
        assert m2.shape == {"clients": 4, "data": 2}

    def test_shard_federation_places_client_axis(self, eight_devices, args_factory):
        args = _args(args_factory)
        args = fedml_tpu.init(args)
        dataset = load(args)
        mesh = build_mesh()
        packed, ns = shard_federation(
            dataset.packed_train, dataset.packed_num_samples, mesh
        )
        shard_shapes = {s.data.shape for s in packed.x.addressable_shards}
        assert len(shard_shapes) == 1
        assert next(iter(shard_shapes))[0] == dataset.client_num // 8

    def test_mesh_equals_single_chip(self, eight_devices, args_factory):
        params = {}
        for mode in ("single", "mesh"):
            args = _args(args_factory)
            args = fedml_tpu.init(args)
            dataset = load(args)
            model = models.create(args, dataset.class_num)
            if mode == "mesh":
                sim = SimulatorMesh(args, None, dataset, model)
            else:
                sim = SimulatorSingleProcess(args, None, dataset, model)
            sim.run()
            params[mode] = jax.tree.map(np.asarray, sim.fl_trainer.global_params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            params["single"],
            params["mesh"],
        )

    def test_mesh_2d_clients_x_data(self, eight_devices, args_factory):
        """clients x data hybrid sharding compiles and runs."""
        args = _args(args_factory, comm_round=1)
        args.mesh_shape = {"clients": 4, "data": 2}
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        sim = SimulatorMesh(args, None, dataset, model)
        stats = sim.run()
        assert "train_acc" in stats

    def test_total_clients_not_divisible_is_padded(self, eight_devices, args_factory):
        """client_num_in_total that doesn't tile the mesh gets padded
        with zero-sample dummy clients — run must succeed and match the
        single-chip result."""
        params = {}
        for mode in ("single", "mesh"):
            args = _args(args_factory, client_num_in_total=13, client_num_per_round=8)
            args = fedml_tpu.init(args)
            dataset = load(args)
            model = models.create(args, dataset.class_num)
            sim = (
                SimulatorMesh(args, None, dataset, model)
                if mode == "mesh"
                else SimulatorSingleProcess(args, None, dataset, model)
            )
            sim.run()
            params[mode] = jax.tree.map(np.asarray, sim.fl_trainer.global_params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            params["single"],
            params["mesh"],
        )

    def test_cohort_not_divisible_raises(self, eight_devices, args_factory):
        args = _args(args_factory, client_num_per_round=3)
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        with pytest.raises(ValueError, match="multiple of the mesh"):
            SimulatorMesh(args, None, dataset, model)


@pytest.mark.smoke
class TestFedMesh:
    """The (data, fsdp) production mesh (parallel/layout.py + the
    build_round_fn fed branch): cohort sharded along ``data``, params
    fsdp-sharded at rest, aggregation through the exact expansion fold
    — bitwise identical across EVERY mesh shape."""

    def _world(self, make, shape, **kw):
        args = _args(make, model="lr", comm_round=2, **kw)
        args.mesh_shape = shape
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        sim = SimulatorMesh(args, None, dataset, model)
        sim.run()
        return sim

    def test_mesh_shapes_bitwise_identical(self, eight_devices, args_factory):
        """{data: 4, fsdp: 2}, {data: 8} and {data: 2, fsdp: 4} all
        finalize to EXACTLY the single-chip {data: 1, fsdp: 1} world's
        float32 bits — the per-client compute is never tensor-split
        (FSDP gathers at use) and the exact expansion fold is
        placement-independent."""
        base = self._world(args_factory, {"data": 1, "fsdp": 1})
        base_params = jax.tree.map(np.asarray, base.fl_trainer.global_params)
        for shape in (
            {"data": 4, "fsdp": 2}, {"data": 8}, {"data": 2, "fsdp": 4}
        ):
            sim = self._world(args_factory, shape)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
                base_params,
                sim.fl_trainer.global_params,
            )
            # compile census: one trace per world
            assert sim.fl_trainer._round_trace_count == 1
            # the round gathered from the flat sample store, placed as
            # the federation is: client axis along 'data'
            api = sim.fl_trainer
            store = api._sample_store()
            assert api.pipeline_stats["store_stagings"] == 1
            assert store.x.ndim == 4 and store.x.sharding.is_equivalent_to(
                api.dataset.packed_train.x.sharding, 4
            )

    def test_params_fsdp_sharded_at_rest(self, eight_devices, args_factory):
        """The carried global params live fsdp-sharded on the mesh —
        each chip holds 1/fsdp of every sharded leaf, the 'models
        larger than one chip's HBM' contract."""
        from fedml_tpu.parallel.layout import SpecLayout

        sim = self._world(args_factory, {"data": 2, "fsdp": 4})
        kernel = sim.fl_trainer.global_params["Dense_0"]["kernel"]
        # XLA-normalized specs drop trailing Nones: compare the
        # sharded axis, not the exact tuple
        assert kernel.sharding.spec[0] == SpecLayout().fsdp_axis
        n_rows = kernel.shape[0]
        assert {s.data.shape for s in kernel.addressable_shards} == {
            (n_rows // 4, kernel.shape[1])
        }

    def test_fed_mesh_close_to_vmap_engine(self, eight_devices, args_factory):
        """The exact fold is a better-rounded weighted mean, not a
        different algorithm: the fed world tracks the stock
        single-process vmap engine to float tolerance."""
        sim = self._world(args_factory, {"data": 4, "fsdp": 2})
        args = _args(args_factory, model="lr", comm_round=2)
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        ref = SimulatorSingleProcess(args, None, dataset, model)
        ref.run()
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            ),
            ref.fl_trainer.global_params,
            sim.fl_trainer.global_params,
        )

    def test_cohort_not_divisible_by_data_raises(
        self, eight_devices, args_factory
    ):
        args = _args(args_factory, model="lr", client_num_per_round=3)
        args.mesh_shape = {"data": 8}
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        with pytest.raises(ValueError, match="multiple of the mesh 'data'"):
            SimulatorMesh(args, None, dataset, model)


@pytest.mark.smoke
class TestOnMeshAggregation:
    """stream ≡ buffered stays BITWISE on the mesh: the streaming
    fold's order-independence argument holds when the limbs and terms
    are (data, fsdp)-sharded device trees — raw and int8 uplinks."""

    def _mesh_trees(self, n=4, seed=11):
        from fedml_tpu.parallel.layout import build_fed_mesh, shard_tree

        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        rng = np.random.RandomState(seed)
        trees = [
            shard_tree(
                {
                    "Dense_0": {
                        "kernel": np.asarray(rng.randn(8, 6), np.float32),
                        "bias": np.asarray(rng.randn(6), np.float32),
                    }
                },
                mesh,
            )
            for _ in range(n)
        ]
        ws = [float(w) for w in rng.randint(1, 9, size=n)]
        return mesh, trees, ws

    def _assert_bitwise(self, a, b):
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)
            ),
            a, b,
        )

    def test_stream_fold_order_independent_on_mesh_raw(self, eight_devices):
        from fedml_tpu.core.aggregation import StreamingAccumulator

        _, trees, ws = self._mesh_trees()
        a1 = StreamingAccumulator(trees[0])
        a2 = StreamingAccumulator(trees[0])
        for i in (0, 1, 2, 3):
            a1.fold(trees[i], ws[i])
        for i in (3, 1, 0, 2):  # a different arrival order
            a2.fold(trees[i], ws[i])
        self._assert_bitwise(a1.finalize(), a2.finalize())

    def test_stream_fold_order_independent_on_mesh_int8(self, eight_devices):
        from fedml_tpu.core.aggregation import StreamingAccumulator
        from fedml_tpu.core.compression import Int8Codec

        codec = Int8Codec()
        _, trees, ws = self._mesh_trees(seed=13)
        g = trees[0]
        encs = [
            codec.encode(jax.tree.map(lambda x: x * 0.01, t)) for t in trees
        ]
        a1 = StreamingAccumulator(g)
        a2 = StreamingAccumulator(g)
        for i in (0, 1, 2, 3):
            a1.fold_encoded(codec, encs[i], g, ws[i])
        for i in (2, 3, 1, 0):
            a2.fold_encoded(codec, encs[i], g, ws[i])
        self._assert_bitwise(a1.finalize(), a2.finalize())

    def test_fold_limbs_matches_direct_folds(self, eight_devices):
        """Feeding an on-mesh partial fold's 3-limb expansion into a
        root accumulator (fold_limbs) is bitwise identical to folding
        the underlying terms there — the device-resident limb handoff
        the mesh aggregation plane rides."""
        from fedml_tpu.core.aggregation import StreamingAccumulator

        _, trees, ws = self._mesh_trees(seed=17)
        direct = StreamingAccumulator(trees[0])
        for t, w in zip(trees, ws):
            direct.fold(t, w)
        partial = StreamingAccumulator(trees[0])
        for t, w in zip(trees[2:], ws[2:]):
            partial.fold(t, w)
        root = StreamingAccumulator(trees[0])
        for t, w in zip(trees[:2], ws[:2]):
            root.fold(t, w)
        root.fold_limbs(partial._limbs, sum(ws[2:]), count=partial.count)
        # fold accounting must see the underlying uploads, not the
        # limb-set handoff (quorum denominators read count)
        assert root.count == direct.count
        self._assert_bitwise(direct.finalize(), root.finalize())

    def test_fold_limbs_validates_shape(self, eight_devices):
        from fedml_tpu.core.aggregation import StreamingAccumulator

        _, trees, _ = self._mesh_trees()
        acc = StreamingAccumulator(trees[0])
        with pytest.raises(ValueError, match="3-limb"):
            acc.fold_limbs((trees[0], trees[0]), 1.0)
        with pytest.raises(ValueError, match="count"):
            acc.fold_limbs((trees[0], trees[1], trees[2]), 1.0, count=-1)

    def test_non_exact_aggregation_warns_on_fed_mesh(
        self, eight_devices, args_factory, caplog
    ):
        """The bitwise guarantee covers the plain FedAvg reduction;
        a defense on a fed mesh degrades to float tolerance and must
        say so LOUDLY at construction."""
        import logging

        args = _args(
            args_factory, model="lr",
            defense_type="norm_diff_clipping", norm_bound=1.0,
        )
        args.mesh_shape = {"data": 4, "fsdp": 2}
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        with caplog.at_level(logging.WARNING):
            SimulatorMesh(args, None, dataset, model)
        assert any(
            "NOT bitwise identical" in r.message for r in caplog.records
        )


@pytest.mark.smoke
class TestPlanetOnFedMesh:
    """The registry-backed planet loop's (bucket, nb) group fns shard
    over the fed mesh — mesh and no-mesh worlds train to float
    tolerance (the groupwise einsum reduction is psum-reordered, so
    the claim is allclose, not bitwise)."""

    def _planet_api(self, mesh_shape=None):
        from fedml_tpu.parallel.layout import build_fed_mesh
        from fedml_tpu.simulation import FedAvgAPI

        a = _make_planet_args(
            client_registry_size=512, cohort_size=32, comm_round=2
        )
        if mesh_shape:
            a.mesh_shape = mesh_shape  # init() flips the threefry flag
        args = fedml_tpu.init(a)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        mesh = (
            build_fed_mesh(mesh_shape=mesh_shape) if mesh_shape else None
        )
        return FedAvgAPI(args, None, dataset, model, mesh=mesh)

    def test_planet_group_fns_on_mesh(self, eight_devices):
        # mesh world FIRST: its init() flips jax_threefry_partitionable
        # before either world initializes params or materializes
        # registry data, so both draw from the same stream
        apis = {
            "mesh": self._planet_api({"data": 4, "fsdp": 2}),
            "flat": self._planet_api(None),
        }
        for api in apis.values():
            api.train()
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            ),
            apis["flat"].global_params,
            apis["mesh"].global_params,
        )
        # one jit trace per (bucket, nb) shape key, mesh or not
        for api in apis.values():
            stats = api.pipeline_stats
            assert stats["trace_count"] == len(stats["shape_keys"])

    def test_planet_rejects_legacy_mesh(self, eight_devices):
        from fedml_tpu.parallel.mesh import build_mesh
        from fedml_tpu.simulation import FedAvgAPI

        args = fedml_tpu.init(
            _make_planet_args(
                client_registry_size=128, cohort_size=16, comm_round=1
            )
        )
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        api = FedAvgAPI(
            args, None, dataset, model,
            mesh=build_mesh(mesh_shape={"clients": 8}),
        )
        with pytest.raises(ValueError, match="legacy"):
            api.train()


def _make_planet_args(**kw):
    from fedml_tpu.arguments import Arguments

    a = Arguments()
    base = dict(
        dataset="synthetic",
        model="lr",
        client_num_in_total=kw.get("client_registry_size", 128),
        client_num_per_round=kw.get("cohort_size", 16),
        epochs=1,
        batch_size=16,
        learning_rate=0.1,
        frequency_of_the_test=10**9,
        synthetic_train_size=256,
        synthetic_test_size=64,
        comm_round=2,
        # the mesh-vs-flat allclose below isolates the group-fn mesh
        # plumbing; shuffle draws differ between the partitionable
        # (mesh) and legacy threefry streams, so pin them off
        shuffle=False,
    )
    base.update(kw)
    for k, v in base.items():
        setattr(a, k, v)
    a._validate()
    return a


class TestAlgorithms:
    """Smoke + semantics for FedProx / FedOpt / FedNova / robust agg."""

    def _run(self, make, optimizer, **kw):
        args = _args(
            make,
            client_num_in_total=8,
            client_num_per_round=8,
            comm_round=3,
            **kw,
        )
        args.federated_optimizer = optimizer
        args = fedml_tpu.init(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        sim = SimulatorSingleProcess(args, None, dataset, model)
        stats = sim.run()
        return stats, sim.fl_trainer

    def test_fedprox_runs(self, args_factory):
        stats, _ = self._run(args_factory, "FedProx", fedprox_mu=0.1)
        assert stats["train_acc"] > 0.5

    def test_fedprox_mu_zero_equals_fedavg(self, args_factory):
        s1, t1 = self._run(args_factory, "FedProx", fedprox_mu=0.0)
        s2, t2 = self._run(args_factory, "FedAvg")
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            t1.global_params,
            t2.global_params,
        )

    def test_fedopt_adam_runs(self, args_factory):
        stats, _ = self._run(
            args_factory, "FedOpt", server_optimizer="adam", server_lr=0.01
        )
        assert stats["train_acc"] > 0.5

    def test_fedopt_sgd_lr1_equals_fedavg(self, args_factory):
        """Server SGD with lr=1 on the pseudo-gradient reproduces plain
        FedAvg (the FedOpt paper's sanity identity)."""
        s1, t1 = self._run(
            args_factory, "FedOpt", server_optimizer="sgd", server_lr=1.0
        )
        s2, t2 = self._run(args_factory, "FedAvg")
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
            t1.global_params,
            t2.global_params,
        )

    def test_fednova_runs(self, args_factory):
        stats, _ = self._run(args_factory, "FedNova", epochs=2)
        assert stats["train_acc"] > 0.5

    def test_robust_aggregation_runs(self, args_factory):
        stats, _ = self._run(
            args_factory, "FedAvg", defense_type="norm_diff_clipping", norm_bound=1.0
        )
        assert stats["train_acc"] > 0.3

    def test_median_aggregation_runs(self, args_factory):
        stats, _ = self._run(args_factory, "FedAvg", defense_type="median")
        assert "train_acc" in stats
