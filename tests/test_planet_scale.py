"""Planet-scale population plane (fedml_tpu/scale/).

Covers the ISSUE-9 acceptance contract:
- registry determinism: same seed => same columns, same cohort draws,
  same per-client data across materializations;
- O(cohort) sampling/round memory: Floyd sampling never touches
  registry-sized arrays (tracemalloc-bounded on a 1M registry), and a
  full registry-backed round's RSS delta is bounded by the cohort;
- tree == flat bitwise aggregation identity, plain and int8-quantized
  uploads, any edge count, any fold order;
- cohort packing respects the pow2 bucket census (<= 7 shape keys for
  a uniform 8 -> 512 cohort sweep, the PR-2 bound) and consumes
  core/scheduler (LPT makespan splits, boustrophedon shard deal);
- the registry-backed simulator trains end-to-end, deterministically,
  bit-identically between the two-tier tree and the flat fold;
- the loader never builds per-client state proportional to the
  registry, and the knobs validate loudly.
"""

import os
import tracemalloc

import numpy as np
import pytest

pytestmark = pytest.mark.smoke

import jax
import jax.numpy as jnp

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core.aggregation import StreamingAccumulator, pytree_sub
from fedml_tpu.core.compression import Int8Codec
from fedml_tpu.core.topology import EdgeTreeTopology
from fedml_tpu.data import load
from fedml_tpu.scale import ClientRegistry, EdgeAggregationTree, pack_cohort
from fedml_tpu.simulation import FedAvgAPI

from tests.conftest import make_args


def _tree_template():
    return {
        "w": jnp.zeros((13, 5)),
        "nested": (jnp.zeros((7,)), jnp.zeros((3, 2))),
    }


def _random_tree(i, template):
    r = np.random.RandomState(1000 + i)
    return jax.tree.map(
        lambda x: jnp.asarray(r.normal(0, 1, x.shape), jnp.float32), template
    )


def _max_diff(a, b):
    return max(
        float(abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


class TestClientRegistry:
    def test_columns_deterministic_and_columnar(self):
        r1 = ClientRegistry(5000, seed=3)
        r2 = ClientRegistry(5000, seed=3)
        for col in ("num_samples", "speed_tier", "shard_offset", "client_seed"):
            assert np.array_equal(getattr(r1, col), getattr(r2, col)), col
        r3 = ClientRegistry(5000, seed=4)
        assert not np.array_equal(r1.num_samples, r3.num_samples)
        # ~22 bytes per client (incl. the cross-device availability
        # phase + last_checkin columns), no hidden python-object
        # population
        assert r1.nbytes() == 22 * 5000
        assert (r1.num_samples >= 20).all() and (r1.num_samples <= 400).all()

    def test_shard_offsets_are_prefix_sums(self):
        r = ClientRegistry(100, seed=0)
        off, n = r.shard_slice(0)
        assert off == 0 and n == int(r.num_samples[0])
        for i in range(1, 100):
            o_prev, n_prev = r.shard_slice(i - 1)
            o, _ = r.shard_slice(i)
            assert o == o_prev + n_prev
        assert r.total_samples == int(r.num_samples.sum())

    def test_cohort_sampling_deterministic_without_replacement(self):
        r = ClientRegistry(10_000, seed=1)
        a = r.sample_cohort(7, 256)
        b = r.sample_cohort(7, 256)
        assert np.array_equal(a, b)
        assert len(np.unique(a)) == 256
        assert (a >= 0).all() and (a < 10_000).all()
        c = r.sample_cohort(8, 256)
        assert not np.array_equal(a, c)
        # same registry seed => same draws on a fresh instance
        assert np.array_equal(ClientRegistry(10_000, seed=1).sample_cohort(7, 256), a)

    def test_sampling_memory_is_o_cohort_on_1m_registry(self):
        """Floyd's algorithm: drawing 1k from 1M must never build an
        arange/permutation of the registry (that is ~8 MB; the bound
        here is two decades under it)."""
        reg = ClientRegistry(1_000_000, seed=0)
        reg.sample_cohort(0, 1000)  # warm any lazy allocations
        tracemalloc.start()
        reg.sample_cohort(1, 1000)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert peak < 512 * 1024, f"sampling peak {peak} bytes"

    def test_client_data_stable_across_materializations(self):
        reg = ClientRegistry(2_000, seed=5)
        idx = reg.sample_cohort(0, 16)
        ys1 = [reg.client_labels(int(i), 10) for i in idx]
        ys2 = [reg.client_labels(int(i), 10) for i in idx]
        for a, b in zip(ys1, ys2):
            assert np.array_equal(a, b)
        # labels are a function of the client alone, not the cohort
        solo = reg.client_labels(int(idx[3]), 10)
        assert np.array_equal(solo, ys1[3])
        b1, ns1 = reg.materialize_group(idx, 4, 32, (12,), 10)
        b2, ns2 = reg.materialize_group(idx, 4, 32, (12,), 10)
        assert np.array_equal(ns1, ns2)
        assert _max_diff(b1, b2) == 0.0

    def test_memmap_registry_matches_in_ram(self, tmp_path):
        rram = ClientRegistry(1_000, seed=9)
        rmm = ClientRegistry(1_000, seed=9, memmap_dir=str(tmp_path))
        for col in (
            "num_samples", "speed_tier", "shard_offset", "client_seed",
            "availability", "last_checkin",
        ):
            assert np.array_equal(getattr(rram, col), getattr(rmm, col)), col
        assert os.path.exists(tmp_path / "num_samples.npy")
        assert os.path.exists(tmp_path / "availability.npy")
        assert np.array_equal(
            rram.sample_cohort(3, 64), rmm.sample_cohort(3, 64)
        )
        # last_checkin is the one run-time-mutable column: stamps made
        # through the memmap registry round-trip to disk and back
        avail = rmm.sample_available_cohort(0, 8)
        rmm.record_checkin(int(avail[0]), 4)
        reopened = np.load(tmp_path / "last_checkin.npy", mmap_mode="r")
        assert int(reopened[int(avail[0])]) == 4
        assert int(rram.last_checkin[int(avail[0])]) == -1  # RAM twin untouched

    def test_validation(self):
        with pytest.raises(ValueError):
            ClientRegistry(0)
        with pytest.raises(ValueError):
            ClientRegistry(10, min_samples=50, max_samples=20)
        reg = ClientRegistry(100)
        with pytest.raises(ValueError):
            reg.sample_cohort(0, 101)
        with pytest.raises(ValueError):
            reg.sample_cohort(0, 0)

    def test_registry_gauge_exported(self):
        from fedml_tpu.core.telemetry import Telemetry

        Telemetry.reset()
        ClientRegistry(12_345, seed=0)
        snap = Telemetry.get_instance().snapshot()
        assert snap["gauges"]["registry_clients"] == 12_345


class TestCohortPacking:
    def test_pow2_census_8_to_512(self):
        """Uniform client sizes, cohorts 8 -> 512: the packer must
        produce at most ceil(log2(512/8)) + 1 = 7 distinct jit shape
        keys — the same census bound the round pipeline pinned."""
        keys = set()
        for cohort in (8, 12, 32, 48, 100, 256, 400, 512):
            sizes = np.full(cohort, 100)
            plan = pack_cohort(sizes, np.arange(cohort), 32)
            keys |= set(plan.shape_keys)
        assert len(keys) <= 7, sorted(keys)

    def test_groups_are_pow2_shaped_and_cover_cohort(self):
        rng = np.random.RandomState(0)
        sizes = rng.randint(20, 400, 100)
        idx = rng.permutation(100_000)[:100].astype(np.int64)
        plan = pack_cohort(sizes, idx, 32)
        seen = []
        for g in plan.groups:
            assert g.bucket == 1 << (g.bucket - 1).bit_length()  # pow2
            assert g.nb == 1 << (g.nb - 1).bit_length()
            assert g.valid[: g.real_clients].all()
            assert not g.valid[g.real_clients:].any()
            seen.extend(g.client_idx[: g.real_clients].tolist())
        assert sorted(seen) == sorted(idx.tolist())
        assert 0.0 <= plan.waste_frac < 1.0

    def test_lpt_split_balances_heterogeneous_work(self):
        """An oversized nb-group splits via greedy_makespan on
        tier-weighted workloads: sub-group loads must be closer to
        balanced than a worst-case contiguous split."""
        n = 64
        sizes = np.full(n, 100)
        tiers = np.zeros(n, dtype=np.int64)
        tiers[:8] = 2  # 8 slow clients: 4x work each
        plan = pack_cohort(
            sizes, np.arange(n), 32, speed_tier=tiers, max_group_clients=16
        )
        assert plan.makespan_splits >= 1
        loads = []
        for g in plan.groups:
            real = g.client_idx[: g.real_clients]
            w = sizes[real] * (2.0 ** tiers[real])
            loads.append(w.sum())
        # LPT bound: max load within 4/3 of the mean (classic bound is
        # 4/3 - 1/3m of optimum; mean <= optimum)
        assert max(loads) <= 4.0 / 3.0 * (sum(loads) / len(loads)) + 400

    def test_lpt_split_never_exceeds_max_group_clients(self):
        """LPT balances load, not count: many light clients balancing a
        few heavy ones could overfill one lane past max_group_clients
        and pad to a 2x-wider pow2 bucket. The repair pass must keep
        every sub-group at or under the cap."""
        n = 96
        sizes = np.full(n, 100)
        tiers = np.zeros(n, dtype=np.int64)
        tiers[:4] = 4  # 4 clients carry 16x work each — LPT isolates
        # them and would pile the 92 light clients onto the other lanes
        plan = pack_cohort(
            sizes, np.arange(n), 32, speed_tier=tiers, max_group_clients=16
        )
        assert plan.makespan_splits >= 1
        for g in plan.groups:
            assert g.real_clients <= 16
        # every client still packed exactly once
        packed = sorted(
            int(c) for g in plan.groups
            for c in g.client_idx[: g.real_clients]
        )
        assert packed == list(range(n))

    def test_shard_deal_is_equal_count_near_equal_load(self):
        rng = np.random.RandomState(1)
        sizes = rng.randint(20, 400, 32)
        plan = pack_cohort(sizes, np.arange(32), 32, shard_num=4)
        for g in plan.groups:
            lanes = g.shards
            counts = [len(l) for l in lanes]
            assert max(counts) - min(counts) <= 1
        # shard positions must tile the group's real clients exactly:
        # lane slots index the arrays AS LAID OUT (consecutive chunks
        # covering 0..real_clients-1 within each group)
        for g in plan.groups:
            flat = sorted(p for l in g.shards for p in l)
            assert flat == list(range(g.real_clients))
            # and per-lane loads read through those slots stay
            # near-equal — the deal's balance survives the reorder
            loads = [
                float(g.num_samples[np.asarray(l, dtype=np.int64)].sum())
                for l in g.shards if l
            ]
            if len(loads) > 1:
                assert max(loads) - min(loads) <= max(
                    g.num_samples[: g.real_clients].max(), 1.0
                )

    def test_waste_frac_histogram_observed(self):
        from fedml_tpu.core.telemetry import Telemetry

        Telemetry.reset()
        tel = Telemetry.get_instance()
        pack_cohort(np.full(10, 50), np.arange(10), 32, telemetry=tel)
        snap = tel.snapshot()
        assert "cohort_bucket_waste_frac" in snap["histograms"]


class TestEdgeTree:
    def test_tree_identical_to_flat_plain(self):
        template = _tree_template()
        rng = np.random.RandomState(2)
        uploads = [
            (_random_tree(i, template), float(w))
            for i, w in enumerate(rng.randint(1, 300, 20))
        ]
        flat = StreamingAccumulator(template)
        for th, w in uploads:
            flat.fold(th, w)
        want = flat.finalize()
        for edges in (2, 3, 8):
            tree = EdgeAggregationTree(template, edges)
            for i in rng.permutation(len(uploads)):
                th, w = uploads[i]
                tree.acc_for(int(i)).fold(th, w)
            assert _max_diff(want, tree.finalize()) == 0.0, edges

    def test_tree_identical_to_flat_int8(self):
        template = _tree_template()
        codec = Int8Codec()
        glob = _random_tree(999, template)
        rng = np.random.RandomState(3)
        encs = [
            (codec.encode(pytree_sub(_random_tree(i, template), glob)), float(w))
            for i, w in enumerate(rng.randint(1, 300, 12))
        ]
        flat = StreamingAccumulator(template)
        for e, w in encs:
            flat.fold_encoded(codec, e, glob, w)
        want = flat.finalize()
        tree = EdgeAggregationTree(template, 4)
        for i in rng.permutation(len(encs)):
            e, w = encs[i]
            tree.acc_for(int(i)).fold_encoded(codec, e, glob, w)
        assert _max_diff(want, tree.finalize()) == 0.0

    def test_merge_preserves_totals_and_empty_edges_skip(self):
        template = _tree_template()
        tree = EdgeAggregationTree(template, 5)
        tree.acc_for(0).fold(_random_tree(0, template), 10.0)
        tree.acc_for(1).fold(_random_tree(1, template), 20.0)
        assert tree.count == 2 and tree.total_w == 30.0
        out = tree.finalize()  # 3 empty edges must not poison the root
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(out))
        tree.reset()
        assert tree.count == 0
        with pytest.raises(RuntimeError):
            tree.finalize()

    def test_assignment_modes(self):
        template = _tree_template()
        tree = EdgeAggregationTree(template, 4)
        assert tree.edge_of(6) == 2  # stable round-robin
        asn = EdgeAggregationTree.assign_by_load([100, 90, 5, 5, 5, 5], 2)
        loads = [0, 0]
        for i, e in asn.items():
            loads[e] += [100, 90, 5, 5, 5, 5][i]
        assert abs(loads[0] - loads[1]) <= 15
        t2 = EdgeAggregationTree(template, 2, assignment=asn)
        assert t2.edge_of(0) == asn[0]

    def test_topology_star_shape(self):
        topo = EdgeTreeTopology(4)
        topo.generate_topology()
        assert topo.get_in_neighbor_idx_list(0) == [1, 2, 3, 4]
        assert topo.get_out_neighbor_idx_list(2) == [0]
        assert topo.get_in_neighbor_idx_list(3) == []
        row = topo.topology[0]
        assert row[0] == 0 and np.allclose(row[1:], 0.25)
        with pytest.raises(ValueError):
            EdgeTreeTopology(0)

    def test_cross_silo_aggregator_edge_tier_bit_identical(self):
        """The LOCAL-world edge tier: FedMLAggregator with edge_num
        folds rank uploads through the tree and finalizes bitwise
        identically to the flat server."""
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )

        def world(edge_num):
            args = make_args(
                training_type="cross_silo", backend="LOCAL",
                dataset="synthetic", model="lr", client_num_in_total=6,
                client_num_per_round=6, batch_size=16, edge_num=edge_num,
            )
            model = models.create(args, 10)
            agg = FedMLAggregator(args, model)
            for i in range(6):
                r = np.random.RandomState(i)
                theta = jax.tree.map(
                    lambda x: x + r.normal(0, 0.1, x.shape).astype(np.float32),
                    agg.global_params,
                )
                assert agg.receive_upload(i, 10.0 * (i + 1), model_params=theta) == "folded"
            assert (agg._tree is not None) == (edge_num >= 2)
            return agg.aggregate()

        assert _max_diff(world(0), world(4)) == 0.0


def _build_planet(**kw):
    base = dict(
        dataset="synthetic",
        model="lr",
        client_registry_size=600,
        cohort_size=12,
        edge_num=3,
        client_num_in_total=600,
        client_num_per_round=12,
        comm_round=2,
        epochs=1,
        batch_size=32,
        learning_rate=0.1,
        frequency_of_the_test=1,
        synthetic_train_size=128,
        synthetic_test_size=64,
    )
    base.update(kw)
    args = make_args(**base)
    args = fedml_tpu.init(args)
    ds = load(args)
    model = models.create(args, ds.class_num)
    return args, ds, FedAvgAPI(args, None, ds, model)


class TestRegistrySimulation:
    def test_trains_deterministically_and_tree_equals_flat(self):
        _, _, api = _build_planet()
        stats = api.train()
        assert stats["round"] == 1
        assert len(api.history) == 2
        assert api.pipeline_stats["registry_clients"] == 600
        assert api.pipeline_stats["edge_num"] == 3
        assert api.pipeline_stats["trace_count"] == len(
            api.pipeline_stats["shape_keys"]
        )
        # same seed => bit-identical params
        _, _, api2 = _build_planet()
        api2.train()
        assert _max_diff(api.global_params, api2.global_params) == 0.0
        # two-tier tree == flat fold of the same per-edge terms
        _, _, api3 = _build_planet(edge_flat_fold=True)
        api3.train()
        assert _max_diff(api.global_params, api3.global_params) == 0.0

    def test_1m_registry_round_memory_is_o_cohort(self):
        """A 1M-client registry round: columns cost ~22 MB and the
        sample->pack->materialize path for a 1k cohort stays under a
        cohort-scale RSS bound (nothing O(registry) materializes)."""
        from fedml_tpu.core.sys_stats import current_rss_bytes

        reg = ClientRegistry(1_000_000, seed=0)
        assert reg.nbytes() == 22_000_000
        idx = reg.sample_cohort(0, 1000)
        plan = pack_cohort(
            reg.num_samples[idx], idx, 32, speed_tier=reg.speed_tier[idx]
        )
        # the first materialization pays for the backend's start and
        # the first compile (~110 MB of RSS when this test runs
        # alone): the process's cost, not the round's
        g = plan.groups[0]
        b, _ = reg.materialize_group(g.client_idx, g.nb, 32, (12,), 10)
        jax.block_until_ready(b.x)
        del b
        rss0 = current_rss_bytes()
        for g in plan.groups:
            b, _ = reg.materialize_group(g.client_idx, g.nb, 32, (12,), 10)
            jax.block_until_ready(b.x)
        delta = current_rss_bytes() - rss0
        # 1k cohort x <=16 nb x 32 bs x 12 feats x 4 B ~= 25 MB of
        # device-side cohort tensors; 256 MB is cohort-scale slack,
        # far below any O(registry x data) materialization (~1.4 GB)
        assert delta < 256 * 1024 * 1024, delta

    def test_loader_builds_no_per_client_state(self):
        args = make_args(
            dataset="synthetic", model="lr", client_registry_size=50_000,
            cohort_size=100, client_num_in_total=50_000,
            client_num_per_round=100, batch_size=32,
        )
        tracemalloc.start()
        ds = load(args)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert ds.client_num == 50_000
        assert ds.packed_train is None
        assert ds.train_data_local_dict == {}
        assert ds.train_data_local_num_dict == {}
        # eval holdouts only: peak is megabytes, not a 50k federation
        assert peak < 64 * 1024 * 1024, peak

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="cohort_size"):
            make_args(client_registry_size=100, cohort_size=200)
        with pytest.raises(ValueError, match="edge_num"):
            make_args(client_registry_size=100, cohort_size=10, edge_num=11)
        with pytest.raises(ValueError, match="training_type"):
            make_args(
                training_type="cross_silo", backend="LOCAL",
                client_registry_size=100,
            )
        with pytest.raises(ValueError, match="client_registry_size"):
            make_args(client_registry_size="nope")
        with pytest.raises(ValueError, match="must be >= 0"):
            make_args(edge_num=-1)
        # edge_num alone (cross-silo edge tier) needs no registry
        args = make_args(
            training_type="cross_silo", backend="LOCAL", edge_num=4
        )
        assert args.edge_num == 4

    def test_unsupported_configs_raise_loudly(self):
        from fedml_tpu.scale.engine import PlanetRoundLoop

        _, _, api = _build_planet(defense_type="median")
        with pytest.raises(ValueError, match="defense_type"):
            PlanetRoundLoop(api)
        # build through the optimizer's real API class (the simulator
        # factory path) so ``api.algorithm`` reflects FedOpt
        from fedml_tpu.simulation import FedOptAPI

        args, ds, _ = _build_planet(
            federated_optimizer="FedOpt", server_lr=0.1
        )
        api = FedOptAPI(args, None, ds, models.create(args, ds.class_num))
        with pytest.raises(ValueError, match="FedOpt"):
            PlanetRoundLoop(api)

    def test_registry_dataset_rejects_non_classification(self):
        with pytest.raises(ValueError, match="classification"):
            load(
                make_args(
                    dataset="shakespeare", model="rnn",
                    client_registry_size=1000, cohort_size=10,
                    client_num_per_round=10, batch_size=8,
                )
            )

    def test_registry_dataset_rejects_poisoning(self):
        with pytest.raises(ValueError, match="poison_type"):
            load(
                make_args(
                    dataset="synthetic", client_registry_size=1000,
                    cohort_size=10, client_num_per_round=10,
                    poison_type="label_flip", poisoned_client_idxs=[0],
                )
            )


class TestElasticResume:
    """Planet-engine preemption tolerance (parallel/elastic.py): a
    registry-cohort world preempted mid-run on an 8-device fed mesh
    resumes on the 4 surviving devices — registry sampling replays
    host-deterministically, the WAL pairs preempt/resume, and the
    final params are bitwise identical to the uninterrupted run."""

    def _mesh_world(self, mesh_shape, devices=None, **kw):
        from fedml_tpu.parallel.layout import build_fed_mesh

        base = dict(
            dataset="synthetic",
            model="lr",
            client_registry_size=512,
            cohort_size=32,
            edge_num=2,
            client_num_in_total=512,
            client_num_per_round=32,
            comm_round=3,
            epochs=1,
            batch_size=16,
            learning_rate=0.1,
            frequency_of_the_test=10**9,
            synthetic_train_size=256,
            synthetic_test_size=64,
            mesh_shape=mesh_shape,
        )
        base.update(kw)
        args = fedml_tpu.init(make_args(**base))
        ds = load(args)
        model = models.create(args, ds.class_num)
        mesh = build_fed_mesh(devices=devices, mesh_shape=mesh_shape)
        return FedAvgAPI(args, None, ds, model, mesh=mesh)

    @pytest.mark.slow  # three full registry trains (jit per mesh shape)
    def test_preempted_run_resumes_bitwise_on_reshaped_mesh(
        self, tmp_path, eight_devices
    ):
        from fedml_tpu.core.checkpoint import RoundWAL
        from fedml_tpu.core.invariants import InvariantChecker
        from fedml_tpu.parallel.elastic import (
            Preempted,
            SimulatedPreemption,
        )

        # the uninterrupted 8-device reference
        ref = self._mesh_world({"data": 4, "fsdp": 2})
        ref.train()

        # preempted at round 1 on the full mesh
        api1 = self._mesh_world(
            {"data": 4, "fsdp": 2}, checkpoint_dir=str(tmp_path)
        )
        api1._preempt_signal = SimulatedPreemption(at_round=1)
        with pytest.raises(Preempted) as ei:
            api1.train()
        assert ei.value.round_idx == 1 and ei.value.ckpt_step == 1
        recs = RoundWAL(str(tmp_path)).records()
        assert [r.get("kind") for r in recs] == ["preempt"]
        assert recs[0]["mesh_shape"] == {"data": 4, "fsdp": 2}

        # restart on the surviving half: both axes reshaped, the
        # registry cohorts replay from the same host-deterministic
        # sampler, and round 2 runs on the (2, 2) mesh
        api2 = self._mesh_world(
            {"data": 2, "fsdp": 2},
            devices=eight_devices[:4],
            checkpoint_dir=str(tmp_path),
        )
        api2.train()
        assert _max_diff(ref.global_params, api2.global_params) == 0.0
        kinds = [r.get("kind") for r in RoundWAL(str(tmp_path)).records()]
        assert kinds == ["preempt", "resume"]
        rep = InvariantChecker(None, str(tmp_path)).check()
        assert rep.ok, rep.to_dict()
        assert "preempt_paired_with_checkpoint" in rep.checked
        assert "preempt_resume_continuity" in rep.checked


class TestAvailability:
    """The diurnal availability plane the Beehive sampler draws from
    (docs/cross_device.md)."""

    def test_availability_is_deterministic_diurnal_trace(self):
        r1 = ClientRegistry(5_000, seed=3)
        r2 = ClientRegistry(5_000, seed=3)
        assert np.array_equal(r1.availability, r2.availability)
        idx = np.arange(5_000)
        for hour in (0, 7, 23):
            a = r1.is_available(idx, hour)
            assert np.array_equal(a, r2.is_available(idx, hour))
            # duty_hours=14 of 24: roughly that fraction is on at any hour
            frac = float(a.mean())
            assert 0.5 < frac < 0.68, frac
        # a device is on for exactly duty_hours of the day
        on_hours = sum(
            int(r1.is_available(17, h)) for h in range(24)
        )
        assert on_hours == r1.duty_hours

    def test_available_cohort_deterministic_and_actually_available(self):
        reg = ClientRegistry(10_000, seed=1)
        a = reg.sample_available_cohort(5, 256)
        assert np.array_equal(a, reg.sample_available_cohort(5, 256))
        assert len(np.unique(a)) == 256
        assert bool(reg.is_available(a, 5 % 24).all())
        # a different round is a different hour AND a different stream
        b = reg.sample_available_cohort(6, 256)
        assert not np.array_equal(a, b)
        # the availability-aware stream must not mirror the plain one
        assert not np.array_equal(a, reg.sample_cohort(5, 256))

    def test_available_sampling_memory_is_o_cohort_on_1m_registry(self):
        reg = ClientRegistry(1_000_000, seed=0)
        reg.sample_available_cohort(0, 1000)  # warm lazy allocations
        tracemalloc.start()
        reg.sample_available_cohort(1, 1000)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # no availability mask over all N is ever built (~1 MB);
        # the bound is the same two-decades-under as sample_cohort's
        assert peak < 512 * 1024, f"available sampling peak {peak} bytes"

    def test_low_duty_cycle_raises_named_error(self):
        reg = ClientRegistry(64, seed=0, duty_hours=1)
        with pytest.raises(ValueError, match="sample_available_cohort"):
            reg.sample_available_cohort(0, 60, max_draw_factor=2)

    def test_checkin_stamps_only_named_devices(self):
        reg = ClientRegistry(100, seed=0)
        assert (reg.last_checkin == -1).all()
        reg.record_checkin(np.asarray([3, 7]), 12)
        assert int(reg.last_checkin[3]) == 12
        assert int(reg.last_checkin[7]) == 12
        assert (np.delete(reg.last_checkin, [3, 7]) == -1).all()
