"""Mesh-sharded serving fleet (fedml_tpu/serving/fleet.py +
mesh_endpoint.py): pjit'd forwards bitwise identical across mesh
shapes, device-direct sharded hot swap (version-gated, sharding
identity asserted), the CheckpointWatcher sharded restore target
(corrupt-latest fallback preserved), and load-aware fleet routing
(drain to live endpoints under delay/kill, counted sheds, SLO door)."""

import threading

import jax
import numpy as np
import pytest

from tests.conftest import make_args

pytestmark = pytest.mark.smoke


def _build(model_kw=None, **kw):
    from fedml_tpu import models

    args = make_args(
        dataset="synthetic", input_dim=8, model="lr",
        serve_deadline_ms=0.0, **kw,
    )
    model = models.create(args, 4)
    params = model.init(jax.random.PRNGKey(0))
    return args, model, params


def _fed_mesh(data, fsdp):
    from fedml_tpu.parallel.layout import build_fed_mesh

    return build_fed_mesh(
        mesh_shape={"data": data, "fsdp": fsdp}, warn_nonpartitionable=False
    )


def _burst(engine, xs, timeout=30):
    engine.pause()
    futs = [engine.submit(x) for x in xs]
    engine.resume()
    return [f.result(timeout=timeout) for f in futs]


class TestMeshEndpoint:
    def test_bitwise_identical_across_mesh_shapes(self, eight_devices):
        """The tentpole identity: the SAME requests served through
        (1,1) and (2,2) submeshes return bitwise-identical responses,
        across 2 mid-run hot swaps, with one jit trace per bucket."""
        from fedml_tpu.serving import MeshModelEndpoint, ServingEngine

        args, model, params = _build()
        xs = [
            np.random.RandomState(i).randn(8).astype(np.float32)
            for i in range(6)
        ]
        pubs = [model.init(jax.random.PRNGKey(k)) for k in (11, 12)]
        got = {}
        for shape in ((1, 1), (2, 2)):
            ep = MeshModelEndpoint(model, params, _fed_mesh(*shape))
            rows = []
            with ServingEngine(ep, args) as eng:
                rows.append(np.stack(_burst(eng, xs)))
                for v, pub in enumerate(pubs):
                    ep.swap(pub, version=v + 1)
                    rows.append(np.stack(_burst(eng, xs)))
            assert ep.swaps == 2
            assert ep.trace_counts == {8: 1}  # one bucket, one trace
            got[shape] = np.concatenate(rows)
        assert np.array_equal(got[(1, 1)], got[(2, 2)])  # bitwise

    def test_mesh_params_live_sharded_at_rest(self, eight_devices):
        from fedml_tpu.parallel.layout import AXIS_PARAM
        from fedml_tpu.serving import MeshModelEndpoint

        _args, model, params = _build()
        ep = MeshModelEndpoint(model, params, _fed_mesh(2, 2))
        specs = {
            tuple(getattr(l.sharding, "spec", ()))
            for l in jax.tree.leaves(ep.params())
        }
        # at least one leaf actually fsdp-sharded (the weight matrix)
        assert any(AXIS_PARAM in s for s in specs)
        assert ep.shard_multiple == 2  # data axis lanes

    def test_batch_must_tile_the_data_axis(self, eight_devices):
        from fedml_tpu.serving import MeshModelEndpoint

        _args, model, params = _build()
        ep = MeshModelEndpoint(model, params, _fed_mesh(2, 2))
        with pytest.raises(ValueError, match="tile the data axis"):
            ep.infer(np.zeros((3, 8), np.float32))
        # the batcher lifts buckets to the lane multiple
        from fedml_tpu.serving.batcher import MicroBatcher
        import queue as queue_mod

        mb = MicroBatcher(
            queue_mod.Queue(), 64, 0.0, "exact", shard_multiple=2
        )

        class _R:
            def __init__(self, x):
                self.x = x

        _padded, valid, bucket, n = mb.pad([_R(np.zeros(8, np.float32))] * 3)
        assert bucket == 4 and n == 3
        assert valid.tolist() == [1, 1, 1, 0]

    def test_mesh_swap_version_gated_stale_dropped(self, eight_devices):
        from fedml_tpu.core.telemetry import Telemetry
        from fedml_tpu.serving import MeshModelEndpoint

        _args, model, params = _build()
        ep = MeshModelEndpoint(model, params, _fed_mesh(2, 2))
        p2 = model.init(jax.random.PRNGKey(5))
        assert ep.swap(p2, version=7) == 7
        # stale and duplicate publishes: dropped, counted, version holds
        assert ep.swap(params, version=3) == 7
        assert ep.swap(params, version=7) == 7
        assert ep.swaps == 1
        assert Telemetry.get_instance().get_counter(
            "serving_swaps_rejected_total", reason="stale_version"
        ) == 2
        assert ep.swap(model.init(jax.random.PRNGKey(6)), version=9) == 9


class TestSwapShardingIdentity:
    def test_plain_swap_rejects_differently_placed_tree(self):
        """Satellite regression: a pytree of identical shapes/dtypes on
        a DIFFERENT device must fail the swap — it would silently
        retrace every bucket on the next batch."""
        from fedml_tpu.serving import ModelEndpoint

        devs = jax.devices()
        assert len(devs) >= 2
        _args, model, params = _build()
        ep = ModelEndpoint(model, params)
        elsewhere = jax.device_put(ep.params(), devs[1])
        with pytest.raises(ValueError, match="sharding"):
            ep.swap(elsewhere)
        assert ep.swaps == 0

    def test_mesh_swap_normalizes_any_placement(self, eight_devices):
        """The mesh endpoint's at-rest placement re-shards EVERY
        incoming tree onto its own mesh, so a publish sharded for a
        different mesh shape — or living on the host — swaps cleanly
        and can never trip the identity check (no retrace possible)."""
        from fedml_tpu.parallel.layout import shard_tree
        from fedml_tpu.serving import MeshModelEndpoint

        _args, model, params = _build()
        mesh = _fed_mesh(2, 2)
        ep = MeshModelEndpoint(model, params, mesh)
        want = {l.sharding for l in jax.tree.leaves(ep.params())}
        other = shard_tree(
            jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(2))),
            _fed_mesh(1, 4),
        )
        assert ep.swap(other) == 1
        # host-side (numpy) publishes — the watcher's raw path — too
        v = ep.swap(jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(3))))
        assert v == 2 and ep.swaps == 2
        assert {l.sharding for l in jax.tree.leaves(ep.params())} == want


class TestWatcherShardedTarget:
    def _publish(self, ckpt, model, key, step):
        state = {
            "params": jax.tree.map(
                np.asarray, model.init(jax.random.PRNGKey(key))
            ),
            "round_idx": step,
        }
        ckpt.save(step, state)
        return state

    def test_restore_lands_device_direct_on_the_mesh(
        self, tmp_path, eight_devices
    ):
        """First publish restores raw (teaches the fleet the state
        tree); every later publish restores straight onto the mesh
        NamedShardings — no host gather — and swaps version-gated."""
        from fedml_tpu.core.checkpoint import CheckpointWatcher, RoundCheckpointer
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build(serve_fleet_size=2)
        mesh = _fed_mesh(2, 2)
        ckpt = RoundCheckpointer(str(tmp_path))
        self._publish(ckpt, model, key=1, step=3)
        fleet = ServingFleet.build(model, params, args, mesh=mesh)
        watcher = CheckpointWatcher(
            str(tmp_path), restore_target=fleet.restore_target
        )
        try:
            step, state = watcher.poll()
            fleet.publish_state(state, step)
            assert fleet.restore_target() is not None
            want = self._publish(ckpt, model, key=2, step=7)
            step, state = watcher.poll()
            leaves = jax.tree.leaves(state["params"])
            from jax.sharding import NamedSharding

            assert all(
                isinstance(l.sharding, NamedSharding) for l in leaves
            )
            fleet.publish_state(state, step)
            for eng in fleet.engines:
                ep = eng.endpoint
                assert ep.version == 7 and ep.swaps == 2
                got = jax.tree.map(np.asarray, ep.params())
                assert all(
                    np.array_equal(a, b)
                    for a, b in zip(
                        jax.tree.leaves(got),
                        jax.tree.leaves(want["params"]),
                    )
                )
        finally:
            watcher.close()
            ckpt.close()

    def test_corrupt_latest_falls_back_with_target_set(
        self, tmp_path, eight_devices
    ):
        """The fault contract survives the sharded target: a garbled
        newest step degrades to the previous version, is remembered as
        bad, and the NEXT good step restores device-direct."""
        from fedml_tpu.core.checkpoint import CheckpointWatcher, RoundCheckpointer
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build()
        ckpt = RoundCheckpointer(str(tmp_path))
        self._publish(ckpt, model, key=1, step=1)
        fleet = ServingFleet.build(model, params, args, mesh=_fed_mesh(2, 2))
        watcher = CheckpointWatcher(
            str(tmp_path), restore_target=fleet.restore_target
        )
        try:
            step, state = watcher.poll()
            fleet.publish_state(state, step)
            self._publish(ckpt, model, key=2, step=4)
            for f in (tmp_path / "4").rglob("*"):
                if f.is_file():
                    f.write_bytes(b"GARBAGE")
            assert watcher.poll() is None  # fell back, no crash
            assert 4 in watcher._bad
            self._publish(ckpt, model, key=3, step=5)
            step, state = watcher.poll()
            assert step == 5
            fleet.publish_state(state, step)
            assert fleet.engines[0].endpoint.version == 5
        finally:
            watcher.close()
            ckpt.close()

    def test_no_target_keeps_raw_restore(self, tmp_path):
        from fedml_tpu.core.checkpoint import CheckpointWatcher, RoundCheckpointer

        _args, model, _params = _build()
        ckpt = RoundCheckpointer(str(tmp_path))
        self._publish(ckpt, model, key=1, step=2)
        ckpt.close()
        watcher = CheckpointWatcher(str(tmp_path))
        try:
            step, state = watcher.poll()
            assert step == 2
            assert all(
                isinstance(l, np.ndarray)
                for l in jax.tree.leaves(state["params"])
            )
        finally:
            watcher.close()


class TestFleetRouting:
    def test_least_loaded_spreads_evenly(self):
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build(serve_fleet_size=2)
        with ServingFleet.build(model, params, args) as fleet:
            futs = [
                fleet.submit(np.zeros(8, np.float32)) for _ in range(12)
            ]
            for f in futs:
                f.result(timeout=30)
            assert sum(fleet.routed) == 12
            assert fleet.load_skew() <= 2.0

    def test_fleet_wide_swap_reaches_every_endpoint_without_a_retrace(self):
        """``ServingFleet.hot_swap`` publishes one tree to every
        endpoint: both answer from the new weights, at the same
        version, through the executables they already traced."""
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build(serve_fleet_size=2)
        xs = [
            np.random.RandomState(i).randn(8).astype(np.float32)
            for i in range(8)
        ]
        with ServingFleet.build(model, params, args) as fleet:
            before = [f.result(timeout=30) for f in fleet.submit_burst(xs)]
            traces = [dict(e.endpoint.trace_counts) for e in fleet.engines]
            assert all(traces), "an endpoint served nothing before the swap"
            new = model.init(jax.random.PRNGKey(7))
            version = fleet.hot_swap(new)
            after = [f.result(timeout=30) for f in fleet.submit_burst(xs)]
            assert [e.endpoint.swaps for e in fleet.engines] == [1, 1]
            assert {e.endpoint.version for e in fleet.engines} == {version}
            assert traces == [
                dict(e.endpoint.trace_counts) for e in fleet.engines
            ]
        want = np.asarray(model.apply(new, np.stack(xs)))
        np.testing.assert_allclose(np.stack(after), want, atol=1e-6)
        assert not np.allclose(np.stack(before), np.stack(after))

    def test_static_deal_uses_assign_by_load(self):
        from fedml_tpu.core.scheduler import assign_by_load
        from fedml_tpu.serving import ServingFleet

        # the scheduler face the fleet routes through
        plan = assign_by_load([5, 1, 4, 2], 2)
        loads = [0, 0]
        for i, t in plan.items():
            loads[t] += [5, 1, 4, 2][i]
        assert abs(loads[0] - loads[1]) <= 2  # near-equal total load
        args, model, params = _build(
            serve_fleet_size=2, serve_route_policy="static"
        )
        with ServingFleet.build(model, params, args) as fleet:
            futs = fleet.submit_burst(
                [np.zeros(8, np.float32)] * 8, loads=[3, 1, 2, 2, 1, 3, 2, 2]
            )
            for f in futs:
                f.result(timeout=30)
            assert fleet.load_skew() <= 2.0

    def test_delayed_endpoint_sheds_load_to_its_peer(self):
        """Scheduled delay: a paused endpoint accumulates depth, so
        least-loaded routing drains new requests to the live peer;
        everything completes once the slow one resumes."""
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build(serve_fleet_size=2)
        with ServingFleet.build(model, params, args) as fleet:
            fleet.engines[0].pause()
            stuck = [
                fleet.engines[0].submit(np.zeros(8, np.float32))
                for _ in range(4)
            ]
            for _ in range(8):
                f = fleet.submit(np.zeros(8, np.float32))
                assert fleet.routed[0] == 0  # went to the live peer
                f.result(timeout=30)  # which is back at depth 0
            assert fleet.routed[1] == 8
            fleet.engines[0].resume()
            for f in stuck:
                f.result(timeout=30)

    def test_killed_endpoint_drains_to_live_and_sheds_counted(self):
        """Kill: a stopped engine is excluded from routing; with the
        whole fleet down the request sheds typed and counted."""
        from fedml_tpu.core.telemetry import Telemetry
        from fedml_tpu.serving import ServingFleet
        from fedml_tpu.serving.admission import ServingShedError

        args, model, params = _build(serve_fleet_size=2, run_id="fleet_kill")
        fleet = ServingFleet.build(model, params, args).start()
        try:
            fleet.engines[0].stop()
            futs = [
                fleet.submit(np.zeros(8, np.float32)) for _ in range(6)
            ]
            for f in futs:
                f.result(timeout=30)
            assert fleet.routed[0] == 0 and fleet.routed[1] == 6
            fleet.engines[1].stop()
            dead = fleet.submit(np.zeros(8, np.float32))
            with pytest.raises(ServingShedError):
                dead.result(timeout=5)
            tel = Telemetry.get_instance()
            assert tel.get_counter(
                "serving_fleet_shed_total", reason="no_endpoint"
            ) == 1
        finally:
            fleet.stop()

    def test_queue_full_fails_over_and_counts(self):
        """Both queues tiny and paused: the third submit sees a typed
        queue-full shed and fails over (counted) to the next
        candidate."""
        from fedml_tpu.core.telemetry import Telemetry
        from fedml_tpu.serving import ServingFleet

        args, model, params = _build(
            serve_fleet_size=2, serve_queue_size=1, serve_route_failover=1
        )
        fleet = ServingFleet.build(model, params, args).start()
        try:
            for e in fleet.engines:
                e.pause()
            futs = [
                fleet.submit(np.zeros(8, np.float32)) for _ in range(3)
            ]
            tel = Telemetry.get_instance()
            assert tel.get_counter("serving_fleet_failover_total") >= 1
            for e in fleet.engines:
                e.resume()
            done = sum(
                1 for f in futs
                if f.exception(timeout=30) is None
            )
            assert done == 2  # the two queued ones served; one shed
        finally:
            fleet.stop()

    def test_slo_controller_sheds_at_the_door(self):
        from fedml_tpu.core.telemetry import Telemetry
        from fedml_tpu.serving import FleetSloError, ServingFleet
        from fedml_tpu.serving.engine import LATENCY_BUCKETS_S

        args, model, params = _build(
            serve_fleet_size=2, serve_route_slo_ms=50.0
        )
        tel = Telemetry.get_instance(args)
        fleet = ServingFleet.build(model, params, args).start()
        try:
            # below min_count the controller abstains
            assert fleet.slo.p99_ms() is None
            for _ in range(30):
                tel.observe(
                    "serving_request_latency_s", 0.4,
                    buckets=LATENCY_BUCKETS_S, bucket=4,
                )
            assert fleet.slo.p99_ms() > 50.0
            fut = fleet.submit(np.zeros(8, np.float32))
            with pytest.raises(FleetSloError):
                fut.result(timeout=5)
            assert tel.get_counter(
                "serving_fleet_shed_total", reason="slo"
            ) == 1
        finally:
            fleet.stop()


class TestFleetFrontend:
    @pytest.mark.parametrize("faults_outermost", [True, False])
    def test_roundtrip_with_faults_in_both_wrap_orders(
        self, faults_outermost
    ):
        """The fleet frontend composes with FaultInjector /
        instrumentation in either wrap order, exactly like the
        single-endpoint frontend: a dropped request is counted and the
        client's retry lands on the fleet."""
        from fedml_tpu import constants
        from fedml_tpu.core.comm.faults import FaultInjector
        from fedml_tpu.core.comm.instrument import wrap_instrumented
        from fedml_tpu.core.managers import _build_com_manager
        from fedml_tpu.core.telemetry import Telemetry
        from fedml_tpu.serving import FleetFrontend, ServingClient, ServingFleet
        from fedml_tpu.serving.frontends import build_serving_com

        rid = f"fleet_fe_{int(faults_outermost)}"
        args, model, params = _build(serve_fleet_size=2, run_id=rid)
        fleet = ServingFleet.build(model, params, args).start()
        fe = FleetFrontend(fleet, build_serving_com(args, 0, 2), args)
        threading.Thread(target=fe.serve_forever, daemon=True).start()
        raw = _build_com_manager(args, 1, 2, "LOCAL")
        fault_kw = dict(
            drop_prob=1.0, max_faults=1,
            msg_types=[constants.MSG_TYPE_C2S_INFER_REQUEST],
        )
        if faults_outermost:
            com_c = FaultInjector(wrap_instrumented(raw, args), **fault_kw)
        else:
            com_c = wrap_instrumented(FaultInjector(raw, **fault_kw), args)
        cl = ServingClient(com_c, rank=1, args=args)
        try:
            x = np.random.RandomState(2).randn(8).astype(np.float32)
            y = cl.request(x, timeout_s=0.5, retries=2)
            ref = np.asarray(model.apply(params, x[None]))[0]
            assert np.allclose(y, ref, atol=1e-5)
            tel = Telemetry.get_instance()
            assert tel.get_counter("serving_client_retries_total") >= 1
            assert sum(fleet.routed) >= 1
        finally:
            cl.close()
            fe.stop()
            fleet.stop()

    def test_cli_serve_dry_run_fleet_mesh(self, capsys, eight_devices):
        import json as json_mod

        from fedml_tpu import cli

        rc = cli.main(
            ["serve", "--dry-run", "--fleet-size", "2", "--mesh", "2x2"]
        )
        assert rc == 0
        status = json_mod.loads(
            capsys.readouterr().out.strip().splitlines()[-1]
        )
        assert status["fleet_size"] == 2
        assert status["mesh"] == {"data": 2, "fsdp": 2}
        assert status["route_policy"] == "least_loaded"
