"""Elastic-mesh preemption tolerance (fedml_tpu/parallel/elastic.py):
the pluggable preemption signal, the drain -> WAL preempt record ->
forced checkpoint -> clean exit choreography, the reshaped resume on
the surviving device set (bitwise identical to an uninterrupted run),
limb travel across the reshape, the invariant checker's preempt/resume
ledger, the watcher's stale-target relearn, and the serving fleet's
remesh onto a degraded device set."""

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.data import load
from fedml_tpu.parallel.elastic import (
    ChaosPreemption,
    FilePreemption,
    MetadataPreemption,
    Preempted,
    PreemptionSignal,
    SimulatedPreemption,
    make_signal,
    reshape_limb_state,
    surviving_mesh,
)
from fedml_tpu.parallel.layout import build_fed_mesh, shard_tree
from fedml_tpu.simulation import SimulatorMesh

from tests.conftest import make_args

pytestmark = pytest.mark.smoke


class TestMakeSignal:
    def test_none_and_none_string_disable(self):
        assert make_signal(None) is None
        assert make_signal("") is None
        assert make_signal("none") is None
        assert make_signal("  NONE ") is None

    def test_passthrough_of_signal_objects(self):
        sig = SimulatedPreemption(3)
        assert make_signal(sig) is sig

    def test_round_spec(self):
        sig = make_signal("round:2")
        assert isinstance(sig, SimulatedPreemption)
        assert sig.at_round == 2 and sig.describe() == "round:2"

    def test_file_spec(self):
        sig = make_signal("file:/tmp/drain-me")
        assert isinstance(sig, FilePreemption)
        assert sig.path == "/tmp/drain-me"

    def test_metadata_and_chaos_specs(self):
        assert isinstance(make_signal("metadata"), MetadataPreemption)
        assert isinstance(make_signal("chaos"), ChaosPreemption)

    @pytest.mark.parametrize(
        "bad", ["round:", "round:x", "round:-1", "file:", "frobnicate"]
    )
    def test_bad_specs_are_loud(self, bad):
        with pytest.raises(ValueError, match="preempt_signal"):
            make_signal(bad)


class TestSignals:
    def test_simulated_fires_at_and_after_round(self):
        sig = SimulatedPreemption(2, reason="drill")
        assert sig.poll(0) is None and sig.poll(1) is None
        notice = sig.poll(2)
        assert notice is not None and notice.reason == "drill"
        assert notice.detail["at_round"] == 2
        assert sig.poll(3) is not None

    def test_file_signal_fires_when_path_exists(self, tmp_path):
        flag = tmp_path / "drain"
        sig = FilePreemption(str(flag))
        assert sig.poll(0) is None
        flag.write_text("")
        notice = sig.poll(1)
        assert notice is not None and notice.reason == "preempt-file"
        assert notice.detail["path"] == str(flag)

    def test_metadata_signal_off_gce_reads_as_no_event(self):
        # no metadata server here: unreachable must read as "no
        # event", never an error — the signal adds no failure mode
        assert MetadataPreemption(timeout_s=0.2).poll(0) is None

    def test_chaos_signal_bridges_the_schedule(self):
        from fedml_tpu.core.chaos import (
            ChaosSchedule,
            install_chaos,
            reset_chaos,
        )

        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "elastic.check", "round": 1},
             "fault": "device.loss"},
        ]))
        try:
            sig = ChaosPreemption()
            assert sig.poll(0) is None
            notice = sig.poll(1)
            assert notice is not None and notice.reason == "device.loss"
            assert notice.detail["chaos_fault"]["kind"] == "device.loss"
        finally:
            reset_chaos()

    def test_chaos_signal_noop_without_schedule(self):
        from fedml_tpu.core.chaos import reset_chaos

        reset_chaos()
        assert ChaosPreemption().poll(0) is None


class TestSurvivingMesh:
    def test_builds_over_the_surviving_subset(self, eight_devices):
        mesh = surviving_mesh(
            devices=eight_devices[:4], mesh_shape={"data": 4, "fsdp": 1}
        )
        assert dict(mesh.shape) == {"data": 4, "fsdp": 1}
        assert set(mesh.devices.flatten()) == set(eight_devices[:4])

    def test_refuses_below_the_floor(self, eight_devices):
        with pytest.raises(RuntimeError, match="elastic_min_devices"):
            surviving_mesh(
                devices=eight_devices[:2],
                mesh_shape={"data": 2, "fsdp": 1},
                min_devices=4,
            )


class TestLimbTravel:
    def _tree(self, seed, shape=(16, 4)):
        rng = np.random.RandomState(seed)
        return {
            "kernel": rng.standard_normal(shape).astype(np.float32),
            "bias": rng.standard_normal(shape[1]).astype(np.float32),
        }

    def test_reshape_limb_state_passthrough_without_fed_mesh(self):
        state = {"limbs": [self._tree(0)] * 3, "total_w": 1.0, "count": 1}
        assert reshape_limb_state(state, None) is state

    @pytest.mark.parametrize("uplink", ["raw", "int8"])
    def test_limbs_reshard_and_fold_bitwise_across_the_reshape(
        self, eight_devices, uplink
    ):
        """The travel contract: fold half the uploads on the 8-device
        mesh, export/reshard/fold_limbs onto the 4-device survivor
        mesh, fold the rest there — finalize must equal the
        single-mesh fold of all four EXACTLY, for raw uploads and for
        int8-encoded ones (decode + weight fused in the term)."""
        from fedml_tpu.core.aggregation import StreamingAccumulator
        from fedml_tpu.core.compression import Int8Codec

        mesh8 = build_fed_mesh(
            devices=eight_devices, mesh_shape={"data": 8, "fsdp": 1}
        )
        mesh4 = build_fed_mesh(
            devices=eight_devices[:4], mesh_shape={"data": 4, "fsdp": 1}
        )
        ups = [self._tree(i) for i in range(4)]
        ws = [3.0, 1.0, 5.0, 2.0]
        if uplink == "raw":
            def fold(acc, i, mesh):
                acc.fold(shard_tree(ups[i], mesh), ws[i])
        else:
            codec = Int8Codec()
            encs = [codec.encode(u) for u in ups]

            def fold(acc, i, mesh):
                acc.fold_encoded(
                    codec, encs[i], shard_tree(ups[0], mesh), ws[i]
                )
        ref = StreamingAccumulator(shard_tree(ups[0], mesh8))
        for i in range(4):
            fold(ref, i, mesh8)
        acc8 = StreamingAccumulator(shard_tree(ups[0], mesh8))
        for i in (0, 1):
            fold(acc8, i, mesh8)
        state = reshape_limb_state(acc8.export_state(), mesh4)
        for limb in state["limbs"]:
            for leaf in jax.tree.leaves(limb):
                assert leaf.sharding.mesh.devices.size == 4
        acc4 = StreamingAccumulator(shard_tree(ups[0], mesh4))
        acc4.fold_limbs(
            state["limbs"], state["total_w"], count=state["count"]
        )
        for i in (2, 3):
            fold(acc4, i, mesh4)
        assert acc4.count == ref.count and acc4.total_w == ref.total_w
        for a, b in zip(
            jax.tree.leaves(ref.finalize()), jax.tree.leaves(acc4.finalize())
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestElasticKnobs:
    def test_preempt_signal_requires_checkpoint_dir(self):
        with pytest.raises(ValueError, match="needs\n?.*checkpoint_dir"):
            make_args(preempt_signal="round:2")

    def test_preempt_signal_with_checkpoint_dir_accepted(self, tmp_path):
        a = make_args(
            preempt_signal="round:2", checkpoint_dir=str(tmp_path)
        )
        assert a.preempt_signal == "round:2"

    def test_bad_preempt_signal_fails_validation(self, tmp_path):
        with pytest.raises(ValueError, match="preempt_signal"):
            make_args(
                preempt_signal="frobnicate", checkpoint_dir=str(tmp_path)
            )

    def test_elastic_min_devices_coerced_and_floored(self):
        assert make_args(elastic_min_devices="4").elastic_min_devices == 4
        assert make_args(elastic_min_devices=None).elastic_min_devices == 1
        with pytest.raises(ValueError, match="elastic_min_devices"):
            make_args(elastic_min_devices=0)
        with pytest.raises(ValueError, match="elastic_min_devices"):
            make_args(elastic_min_devices="four")


def _world(mesh_shape, devices=None, **kw):
    """A mini fed-mesh world (LR over the synthetic MNIST stand-in)."""
    args = make_args(
        dataset="mnist",
        synthetic_train_size=320,
        synthetic_test_size=80,
        model="lr",
        partition_method="hetero",
        client_num_in_total=16,
        client_num_per_round=8,
        comm_round=3,
        epochs=1,
        batch_size=16,
        learning_rate=0.05,
        frequency_of_the_test=10**9,
        shuffle=False,
        mesh_shape=mesh_shape,
        **kw,
    )
    args = fedml_tpu.init(args)
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    mesh = (
        build_fed_mesh(devices=devices, mesh_shape=mesh_shape)
        if devices is not None
        else None
    )
    return SimulatorMesh(args, None, dataset, model, mesh=mesh)


class TestPreemptResume:
    def test_preempt_drains_records_and_resumes_bitwise(
        self, tmp_path, eight_devices
    ):
        """The tentpole end to end, in miniature: a notice at round 1
        on the 8-device mesh -> Preempted after the WAL preempt record
        and the forced checkpoint; a restart on 4 surviving devices
        restores device-direct, pairs the resume record, and finishes
        bitwise identical to the uninterrupted 8-device run."""
        from fedml_tpu.core.checkpoint import RoundWAL
        from fedml_tpu.core.invariants import InvariantChecker

        # the uninterrupted reference
        sim0 = _world({"data": 8, "fsdp": 1})
        sim0.run()
        base = jax.tree.map(np.asarray, sim0.fl_trainer.global_params)

        # the preempted run
        sim1 = _world({"data": 8, "fsdp": 1}, checkpoint_dir=str(tmp_path))
        sim1.fl_trainer._preempt_signal = SimulatedPreemption(at_round=1)
        with pytest.raises(Preempted) as ei:
            sim1.run()
        assert ei.value.round_idx == 1 and ei.value.ckpt_step == 1
        recs = RoundWAL(str(tmp_path)).records()
        assert [r.get("kind") for r in recs] == ["preempt"]
        assert recs[0]["round_idx"] == 1 and recs[0]["ckpt_step"] == 1
        assert recs[0]["reason"] == "maintenance-simulated"
        assert recs[0]["mesh_shape"] == {"data": 8, "fsdp": 1}
        assert len(recs[0]["devices"]) == 8

        # the restart on the surviving half
        sim2 = _world(
            {"data": 4, "fsdp": 1},
            devices=eight_devices[:4],
            checkpoint_dir=str(tmp_path),
        )
        sim2.run()
        resumed = jax.tree.map(np.asarray, sim2.fl_trainer.global_params)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(resumed)):
            assert np.array_equal(a, b)  # bitwise, not allclose
        kinds = [r.get("kind") for r in RoundWAL(str(tmp_path)).records()]
        assert kinds == ["preempt", "resume"]
        rep = InvariantChecker(None, str(tmp_path)).check()
        assert rep.ok, rep.to_dict()
        assert "preempt_paired_with_checkpoint" in rep.checked
        assert "preempt_resume_continuity" in rep.checked

    def test_preempt_without_checkpointer_is_loud(self, eight_devices):
        from fedml_tpu.parallel.elastic import PreemptionNotice, preempt_now

        sim = _world({"data": 2, "fsdp": 1})
        with pytest.raises(RuntimeError, match="checkpoint_dir"):
            preempt_now(
                sim.fl_trainer, None, 0, PreemptionNotice("maintenance")
            )

    def test_cadence_saved_round_skips_the_double_save(self, tmp_path):
        """When the cadence block already published the round's step,
        preempt_now must not save again — one step directory, one WAL
        preempt record naming it."""
        import os

        sim = _world(
            {"data": 2, "fsdp": 1},
            checkpoint_dir=str(tmp_path),
            checkpoint_freq=1,  # cadence saves EVERY round
        )
        sim.fl_trainer._preempt_signal = SimulatedPreemption(at_round=0)
        with pytest.raises(Preempted):
            sim.run()
        from fedml_tpu.core.checkpoint import RoundWAL

        recs = RoundWAL(str(tmp_path)).records()
        assert [r.get("kind") for r in recs] == ["preempt"]
        assert recs[0]["ckpt_step"] == 0
        steps = [d for d in os.listdir(tmp_path) if d.isdigit()]
        assert steps == ["0"]


class TestPreemptInvariants:
    """The checker-side contract, from hand-written ledgers."""

    def _check(self, build):
        from fedml_tpu.core.checkpoint import RoundWAL
        from fedml_tpu.core.invariants import InvariantChecker

        import tempfile

        with tempfile.TemporaryDirectory() as d:
            build(RoundWAL(d))
            return InvariantChecker(None, d).check()

    def test_paired_ledger_is_green(self):
        rep = self._check(lambda wal: (
            wal.append(1, 1, [], kind="preempt", extra={"reason": "x"}),
            wal.append(2, 1, [], kind="resume"),
        ))
        assert rep.ok, rep.to_dict()

    def test_trailing_preempt_is_legal(self):
        rep = self._check(
            lambda wal: wal.append(1, 1, [], kind="preempt")
        )
        assert rep.ok, rep.to_dict()

    def test_ordinary_ledger_skips_both_invariants(self):
        rep = self._check(lambda wal: wal.append(0, None, [1], folded=[1]))
        assert "preempt_paired_with_checkpoint" in rep.skipped
        assert "preempt_resume_continuity" in rep.skipped

    def test_preempt_answered_by_non_resume_fails(self):
        rep = self._check(lambda wal: (
            wal.append(1, 1, [], kind="preempt"),
            wal.append(2, 2, [7], folded=[7]),
        ))
        assert not rep.ok
        assert any(
            v["invariant"] == "preempt_paired_with_checkpoint"
            for v in rep.violations
        )

    def test_resume_at_wrong_round_fails_continuity(self):
        rep = self._check(lambda wal: (
            wal.append(1, 1, [], kind="preempt"),
            wal.append(3, 1, [], kind="resume"),  # round 2 skipped
        ))
        assert not rep.ok
        assert any(
            v["invariant"] == "preempt_resume_continuity"
            for v in rep.violations
        )

    def test_resume_restoring_wrong_step_fails_pairing(self):
        rep = self._check(lambda wal: (
            wal.append(1, 1, [], kind="preempt"),
            wal.append(2, 0, [], kind="resume"),  # older step restored
        ))
        assert not rep.ok
        assert any(
            v["invariant"] == "preempt_paired_with_checkpoint"
            for v in rep.violations
        )

    def test_orphan_resume_fails(self):
        rep = self._check(
            lambda wal: wal.append(2, 1, [], kind="resume")
        )
        assert not rep.ok
        assert any(
            v["invariant"] == "preempt_resume_continuity"
            for v in rep.violations
        )


class TestWatcherRelearn:
    def test_stale_shaped_target_relearns_raw_and_counts(self, tmp_path):
        """Satellite: a CheckpointWatcher whose restore_target was
        learned on the pre-loss mesh must fall back to a raw restore
        when the shaped restore fails (the elastic relearn), deliver
        the state, and count serving_restore_target_relearned_total."""
        from fedml_tpu.core.checkpoint import (
            CheckpointWatcher,
            RoundCheckpointer,
        )
        from fedml_tpu.core.telemetry import Telemetry

        model = models.create(
            make_args(dataset="synthetic", input_dim=8, model="lr"), 4
        )
        params = model.init(jax.random.PRNGKey(0))
        ckpt = RoundCheckpointer(str(tmp_path))
        ckpt.save(3, {"params": params, "round_idx": 3})

        def stale_target():
            # a target tree the saved checkpoint refuses (pre-loss
            # structure drift): shaped restore raises, relearn kicks in
            return {"params": {"nope": np.zeros((2, 2), np.float32)},
                    "round_idx": 0}

        tel = Telemetry.get_instance()
        tel.enabled = True
        before = tel.get_counter("serving_restore_target_relearned_total")
        watcher = CheckpointWatcher(str(tmp_path), restore_target=stale_target)
        try:
            step, state = watcher.poll()
            assert step == 3
            assert "params" in state  # delivered via the raw retry
            assert (
                tel.get_counter("serving_restore_target_relearned_total")
                == before + 1
            )
            assert 3 not in watcher._bad  # relearned, not condemned
        finally:
            watcher.close()
            ckpt.close()


def _endpoint_world(data, fsdp):
    from fedml_tpu.serving import MeshModelEndpoint

    args = make_args(
        dataset="synthetic", input_dim=8, model="lr", serve_deadline_ms=0.0
    )
    model = models.create(args, 4)
    params = model.init(jax.random.PRNGKey(0))
    mesh = build_fed_mesh(
        mesh_shape={"data": data, "fsdp": fsdp}, warn_nonpartitionable=False
    )
    return args, model, params, MeshModelEndpoint(model, params, mesh)


class TestServingRemesh:
    def test_endpoint_remesh_answers_bitwise_identically(
        self, eight_devices
    ):
        _args, _model, _params, ep = _endpoint_world(4, 2)
        x = np.random.RandomState(3).randn(8, 8).astype(np.float32)
        before = np.asarray(ep.infer(x))
        ep.remesh(
            devices=eight_devices[:4], mesh_shape={"data": 2, "fsdp": 2}
        )
        assert dict(ep.mesh.shape) == {"data": 2, "fsdp": 2}
        assert ep.shard_multiple == 2
        assert all(
            d in set(eight_devices[:4])
            for d in ep.mesh.devices.flatten()
        )
        after = np.asarray(ep.infer(x))
        assert np.array_equal(before, after)  # the response identity

    def test_fleet_remesh_quiesces_reroutes_and_relearns(
        self, eight_devices
    ):
        """The fleet half: remesh stops each engine (shedding counted),
        rebuilds the endpoint over the survivors, restarts, and drops
        the learned restore target so the next publish relearns it on
        the new layout."""
        from fedml_tpu.serving import ServingFleet

        args = make_args(
            dataset="synthetic", input_dim=8, model="lr",
            serve_deadline_ms=0.0, serve_fleet_size=2,
        )
        model = models.create(args, 4)
        params = model.init(jax.random.PRNGKey(0))
        mesh = build_fed_mesh(
            mesh_shape={"data": 4, "fsdp": 1}, warn_nonpartitionable=False
        )
        fleet = ServingFleet.build(model, params, args, mesh=mesh).start()
        try:
            x = np.random.RandomState(5).randn(8).astype(np.float32)
            before = fleet.submit(x).result(timeout=30)
            state = {
                "params": model.init(jax.random.PRNGKey(9)),
                "round_idx": 1,
            }
            fleet.publish_state(state, 1)
            assert fleet.restore_target() is not None
            n = fleet.remesh(
                devices=eight_devices[:2],
                mesh_shape={"data": 2, "fsdp": 1},
            )
            assert n == 2
            assert fleet._restore_target is None  # relearn on publish
            for eng in fleet.engines:
                assert eng.alive()
                assert dict(eng.endpoint.mesh.shape) == {
                    "data": 2, "fsdp": 1,
                }
                assert eng.batcher.shard_multiple == 2
            after = fleet.submit(x).result(timeout=30)
            # same published params, reshaped mesh: bitwise identical
            assert np.array_equal(np.asarray(before), np.asarray(after)) \
                is False  # params were swapped by the publish...
            pub_ref = fleet.submit(x).result(timeout=30)
            assert np.array_equal(np.asarray(after), np.asarray(pub_ref))
        finally:
            fleet.stop()


class TestRoundPipelinePreempt:
    def test_pipeline_drains_inflight_before_the_exit(self, tmp_path):
        """Depth-K rounds drain deterministically before the snapshot:
        a notice under pipeline_depth=2 must still produce a preempt
        record whose checkpoint matches the drained round exactly
        (resume replays nothing, skips nothing)."""
        from fedml_tpu.core.checkpoint import RoundWAL

        sim = _world(
            {"data": 2, "fsdp": 1},
            checkpoint_dir=str(tmp_path),
            pipeline_depth=2,
        )
        sim.fl_trainer._preempt_signal = SimulatedPreemption(at_round=1)
        with pytest.raises(Preempted) as ei:
            sim.run()
        assert ei.value.round_idx == 1
        recs = RoundWAL(str(tmp_path)).records()
        assert [r.get("kind") for r in recs] == ["preempt"]
        assert recs[0]["ckpt_step"] == 1
