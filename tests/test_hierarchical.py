"""Hierarchical server plane (docs/hierarchical.md): edge aggregators
as REAL ranks over the comm seam.

What these tests pin, end to end and at the unit level:

- **tree-over-ranks ≡ in-process tree ≡ flat** — the three topologies
  produce BITWISE identical final params for raw and int8-encoded
  uplinks (the ``StreamingAccumulator.merge`` contract, now across
  processes and a msgpack wire);
- **two-hop exactly-once** — drop+dup faults on both hops with the
  reliable channel stacked outermost heal to exactly one fold per
  (client, round) and one merge per (edge, round), in either wrap
  order (the root's app-level dedup backstops the channel's);
- **root decides, edges enforce** — anomaly evidence propagates up,
  the quarantine list propagates down, probation releases;
- **edge death** — the root detects a dead EDGE and closes the round
  over the survivors (or finishes loudly with none) instead of
  stalling the grace window;
- **edge crash/restart** — a mid-round edge kill at a chaos barrier
  recovers through RESYNC + its WAL sub-ledger, bit-identical to the
  clean world, with the multi-tier invariant checker green;
- **multi-tier invariants** — clean artifacts pass; planted
  double-merge / missing-sub-ledger violations are flagged.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import constants, models
from fedml_tpu.core.aggregation import StreamingAccumulator
from fedml_tpu.core.comm.local import _Fabric
from fedml_tpu.core.invariants import InvariantChecker
from fedml_tpu.core.message import Message
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.cross_silo import Client, Server
from fedml_tpu.cross_silo.hierarchical import (
    HierEdge,
    RootServerManager,
    edge_clients,
    hier_partition,
    plan_edge_partition,
    prepare_client_args,
    run_local_hier_world,
)
from fedml_tpu.cross_silo.horizontal.fedml_aggregator import FedMLAggregator
from fedml_tpu.data import load

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_args(make, rank, run_id, n_clients=4, rounds=2, **kw):
    base = dict(
        training_type="cross_silo",
        backend="LOCAL",
        dataset="mnist",
        synthetic_train_size=200,
        synthetic_test_size=40,
        model="lr",
        partition_method="hetero",
        client_num_in_total=n_clients,
        client_num_per_round=n_clients,
        comm_round=rounds,
        epochs=1,
        batch_size=16,
        learning_rate=0.1,
        frequency_of_the_test=rounds,
        shuffle=False,
        run_id=run_id,
        rank=rank,
    )
    base.update(kw)
    a = make(**base)
    a = fedml_tpu.init(a)
    ds = load(a)
    m = models.create(a, ds.class_num)
    return a, ds, m


def _run_flat(make, run_id, n_clients=4, rounds=2, **kw):
    a0, ds0, m0 = _mk_args(make, 0, run_id, n_clients, rounds, **kw)
    server = Server(a0, None, ds0, m0)
    clients = []
    for r in range(1, n_clients + 1):
        a, ds, m = _mk_args(make, r, run_id, n_clients, rounds, **kw)
        clients.append(Client(a, None, ds, m))
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return jax.tree.map(
        np.asarray, server.aggregator.get_global_model_params()
    )


def _run_hier(make, run_id, n_clients=4, edge_num=2, rounds=2, **kw):
    def mk(role, rank):
        return _mk_args(
            make, rank, run_id, n_clients, rounds,
            edge_plane="ranks", edge_num=edge_num, **kw,
        )

    world = run_local_hier_world(mk, n_clients, edge_num)
    return world


def _params_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.mark.smoke
class TestPlanning:
    def test_partition_balanced_and_deterministic(self):
        p1 = plan_edge_partition(8, 4)
        p2 = plan_edge_partition(8, 4)
        assert p1 == p2
        inv = edge_clients(p1)
        assert sorted(inv) == [1, 2, 3, 4]
        assert all(len(v) == 2 for v in inv.values())
        assert sorted(r for v in inv.values() for r in v) == list(range(1, 9))

    def test_partition_by_load(self):
        # one heavy client: the deal balances total load, not counts
        p = plan_edge_partition(4, 2, sizes=[100, 1, 1, 1])
        inv = edge_clients(p)
        heavy_edge = p[1]
        assert len(inv[heavy_edge]) <= len(inv[3 - heavy_edge])

    def test_partition_validation(self):
        with pytest.raises(ValueError, match="edge_num"):
            plan_edge_partition(4, 0)
        with pytest.raises(ValueError, match="sizes"):
            plan_edge_partition(4, 2, sizes=[1, 2])

    def test_prepare_client_args_points_at_edge_fabric(self, args_factory):
        a = args_factory(
            training_type="cross_silo",
            client_num_per_round=4,
            client_num_in_total=4,
            edge_plane="ranks",
            edge_num=2,
            rank=3,
            run_id="hp",
        )
        part = plan_edge_partition(4, 2)
        prepare_client_args(a, part)
        assert a.run_id == f"hp_edge{part[3]}"
        a.rank = 99
        with pytest.raises(ValueError, match="not in the edge partition"):
            prepare_client_args(a, part)

    def test_knob_validation(self, args_factory):
        ok = dict(
            training_type="cross_silo",
            client_num_per_round=4,
            client_num_in_total=4,
            edge_plane="ranks",
            edge_num=2,
        )
        args_factory(**ok)  # valid baseline
        with pytest.raises(ValueError, match="agg_mode=stream"):
            args_factory(**dict(ok, agg_mode="async"))
        with pytest.raises(ValueError, match="agg_mode=stream"):
            args_factory(**dict(ok, agg_mode="buffered"))
        with pytest.raises(ValueError, match="median"):
            args_factory(**dict(ok, defense_type="median", norm_bound=1.0))
        with pytest.raises(ValueError, match="elastic"):
            args_factory(**dict(ok, elastic_membership=True))
        with pytest.raises(ValueError, match="aggregation_deadline_s"):
            args_factory(**dict(ok, aggregation_deadline_s=5.0))
        with pytest.raises(ValueError, match="edge_num"):
            args_factory(**dict(ok, edge_num=9))
        with pytest.raises(ValueError, match="edge_plane"):
            args_factory(**dict(ok, edge_plane="bogus"))
        with pytest.raises(ValueError, match="hier_port_stride"):
            args_factory(**dict(ok, hier_port_stride=0))
        with pytest.raises(ValueError, match="training_type"):
            args_factory(
                **dict(ok, training_type="simulation", backend="sp")
            )

    def test_inproc_tree_suppressed_under_ranks_plane(self, args_factory):
        a = args_factory(
            training_type="cross_silo",
            client_num_per_round=4,
            client_num_in_total=4,
            edge_plane="ranks",
            edge_num=2,
            dataset="mnist",
            synthetic_train_size=80,
            synthetic_test_size=20,
            model="lr",
        )
        ds = load(a)
        agg = FedMLAggregator(a, models.create(a, ds.class_num))
        assert agg._tree is None  # the ROOT does the tree merge


@pytest.mark.smoke
class TestBitIdentity:
    def test_tree_over_ranks_matches_inproc_tree_and_flat(self, args_factory):
        flat = _run_flat(args_factory, "hier_flat")
        Telemetry.reset()
        # in-process tree (PR 9): same world, edge tier inside the server
        inproc = _run_flat(
            args_factory, "hier_inproc", edge_num=2, edge_plane="inproc"
        )
        Telemetry.reset()
        world = _run_hier(args_factory, "hier_ranks")
        hier = jax.tree.map(
            np.asarray, world["root"].aggregator.get_global_model_params()
        )
        assert _params_equal(flat, inproc)
        assert _params_equal(flat, hier)

    def test_bit_identity_int8_uplinks(self, args_factory):
        flat = _run_flat(args_factory, "hier_flat8", compression="int8")
        Telemetry.reset()
        world = _run_hier(args_factory, "hier_ranks8", compression="int8")
        hier = jax.tree.map(
            np.asarray, world["root"].aggregator.get_global_model_params()
        )
        assert _params_equal(flat, hier)


@pytest.mark.smoke
class TestTwoHopExactlyOnce:
    def test_drop_dup_faults_heal_to_exactly_once(self, args_factory):
        clean = _run_hier(args_factory, "hier_clean_x1")
        clean_params = jax.tree.map(
            np.asarray, clean["root"].aggregator.get_global_model_params()
        )
        Telemetry.reset()
        n, rounds = 4, 2
        world = _run_hier(
            args_factory, "hier_fault_x1",
            reliable_comm=True,
            comm_retry_max=8,
            comm_retry_base_s=0.05,
            fault_injection={"drop_prob": 0.25, "duplicate_prob": 0.25},
        )
        tel = Telemetry.get_instance()
        # every (client, round) folded exactly once at its edge, every
        # (edge, round) merged exactly once at the root — duplicates
        # were dropped (by the channel or the app-level dedup), drops
        # were healed by retransmission
        folded = sum(
            tel.counters_matching("hier_uploads_folded_total").values()
        )
        merges = sum(tel.counters_matching("hier_edge_merges_total").values())
        assert folded == n * rounds
        assert merges == 2 * rounds
        faulty_params = jax.tree.map(
            np.asarray, world["root"].aggregator.get_global_model_params()
        )
        assert _params_equal(clean_params, faulty_params)

    def test_duplicate_edge_report_dropped_either_wrap_order(self, root_world):
        """A duplicate merged-limb report that SLIPS PAST the channel
        dedup (a restarted edge's fresh incarnation, or a channel
        stacked inside the injector) is dropped by the root's
        per-(edge, round) dedup — the app-level half of two-hop
        exactly-once, independent of wrap order."""
        root, template = root_world
        rep = _edge_report(1, 0, template, folded=[1, 2], cohort=[1, 2])
        root.handle_message_edge_report(rep)
        count_after_first = root._root_acc.count
        root.handle_message_edge_report(rep)  # exact duplicate
        assert root._root_acc.count == count_after_first
        tel = Telemetry.get_instance()
        assert tel.get_counter("hier_edge_merge_dups_total", reason="dup") == 1
        # stale (previous-round) report after the round advanced
        rep2 = _edge_report(2, 0, template, folded=[3, 4], cohort=[3, 4])
        root.handle_message_edge_report(rep2)  # closes round 0
        stale = _edge_report(1, 0, template, folded=[1, 2], cohort=[1, 2])
        root.handle_message_edge_report(stale)
        assert (
            tel.get_counter("hier_edge_merge_dups_total", reason="stale") == 1
        )


def _edge_report(edge, round_idx, template, folded, cohort):
    acc = StreamingAccumulator(template)
    for r in folded:
        acc.fold(
            jax.tree.map(lambda x: x + np.float32(0.01 * r), template), 50.0
        )
    msg = Message(constants.MSG_TYPE_E2R_EDGE_REPORT, edge, 0)
    msg.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, round_idx)
    msg.add_params(constants.MSG_ARG_KEY_EDGE_STATE, acc.export_state())
    msg.add_params(constants.MSG_ARG_KEY_FOLDED, list(folded))
    msg.add_params(constants.MSG_ARG_KEY_COHORT, list(cohort))
    return msg


@pytest.fixture
def root_world(args_factory, tmp_path):
    """A unit-level root: LOCAL fabric, both edges announced ONLINE,
    round 0 broadcast out. Returns (manager, params template)."""
    a = args_factory(
        training_type="cross_silo",
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=2,
        edge_plane="ranks",
        edge_num=2,
        dataset="mnist",
        synthetic_train_size=80,
        synthetic_test_size=20,
        model="lr",
        run_id=f"rootunit_{os.path.basename(str(tmp_path))}",
        rank=0,
        shuffle=False,
    )
    ds = load(a)
    model = models.create(a, ds.class_num)
    agg = FedMLAggregator(a, model, test_data=None)
    part = hier_partition(a, ds)
    mgr = RootServerManager(a, agg, part)
    mgr.register_message_receive_handlers()
    for e in (1, 2):
        online = Message(constants.MSG_TYPE_C2S_CLIENT_STATUS, e, 0)
        online.add_params(
            constants.MSG_ARG_KEY_CLIENT_STATUS, constants.CLIENT_STATUS_ONLINE
        )
        mgr.handle_message_edge_status(online)
    assert mgr.is_initialized
    yield mgr, agg.get_global_model_params()
    if mgr._failure_detector is not None:
        mgr._failure_detector.stop()


def _drain(run_id, rank):
    q = _Fabric.get(f"run_{run_id}").inbox(rank)
    out = []
    while not q.empty():
        out.append(q.get_nowait())
    return [m for m in out if isinstance(m, Message)]


@pytest.mark.smoke
class TestRootDecidesEdgesEnforce:
    def test_quarantine_evidence_propagates_and_releases(self, root_world):
        root, template = root_world
        run_id = root.args.run_id
        _drain(run_id, 1), _drain(run_id, 2)  # round 0 broadcasts
        # edge 2 reports anomaly evidence for global rank 3
        ev = Message(constants.MSG_TYPE_E2R_CLIENT_EVENT, 2, 0)
        ev.add_params(
            constants.MSG_ARG_KEY_EVENT_KIND, constants.HIER_EVENT_QUARANTINE
        )
        ev.add_params(constants.MSG_ARG_KEY_RANK, 3)
        root.handle_message_client_event(ev)
        assert 3 in root._quarantine
        # close round 0 -> the NEXT broadcast carries the decision
        part = root.partition
        e_of = {e: rs for e, rs in edge_clients(part).items()}
        for e in (1, 2):
            folded = [r for r in e_of[e] if r != 3]
            root.handle_message_edge_report(
                _edge_report(e, 0, template, folded, e_of[e])
            )
        rounds = {
            e: [
                m
                for m in _drain(run_id, e)
                if m.get_type()
                == constants.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT
            ]
            for e in (1, 2)
        }
        for e in (1, 2):
            (msg,) = rounds[e]
            assert msg.get(constants.MSG_ARG_KEY_QUARANTINED) == [3]
            assignment = {
                int(k): v
                for k, v in msg.get(
                    constants.MSG_ARG_KEY_HIER_ASSIGNMENT
                ).items()
            }
            assert 3 not in assignment  # excluded from selection too
        # probation ticked at the close; force the last period and
        # close round 1 — the release must reach the NEXT broadcast
        assert root._quarantine[3] == root.quarantine_rounds - 1
        root._quarantine[3] = 1
        for e in (1, 2):
            folded = [r for r in e_of[e] if r != 3]
            root.handle_message_edge_report(
                _edge_report(e, 1, template, folded, e_of[e])
            )
        assert 3 not in root._quarantine  # released


    def test_edge_enforces_quarantine_list(self, args_factory, tmp_path):
        a = args_factory(
            training_type="cross_silo",
            client_num_in_total=4,
            client_num_per_round=4,
            comm_round=2,
            edge_plane="ranks",
            edge_num=2,
            dataset="mnist",
            synthetic_train_size=80,
            synthetic_test_size=20,
            model="lr",
            run_id=f"edgeunit_{os.path.basename(str(tmp_path))}",
            rank=1,
            shuffle=False,
        )
        ds = load(a)
        model = models.create(a, ds.class_num)
        edge = HierEdge(a, None, ds, model)
        mgr = edge.manager
        mgr.register_message_receive_handlers()
        for r in mgr.client_ranks:
            mgr.client_online[r] = True
        ranks = mgr.client_ranks
        quarantined, ok_rank = ranks[0], ranks[1]
        rnd = Message(constants.MSG_TYPE_S2C_INIT_CONFIG, 0, 0)
        rnd.add_params(
            constants.MSG_ARG_KEY_MODEL_PARAMS,
            mgr.aggregator.get_global_model_params(),
        )
        rnd.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, 0)
        rnd.add_params(
            constants.MSG_ARG_KEY_HIER_ASSIGNMENT,
            {str(ok_rank): 0},  # the root already excluded the other
        )
        rnd.add_params(constants.MSG_ARG_KEY_QUARANTINED, [quarantined])
        mgr.handle_message_round(rnd)
        up = Message(
            constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, quarantined, 0
        )
        up.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, 0)
        up.add_params(
            constants.MSG_ARG_KEY_MODEL_PARAMS,
            mgr.aggregator.get_global_model_params(),
        )
        up.add_params(constants.MSG_ARG_KEY_NUM_SAMPLES, 10.0)
        before = mgr.aggregator.folds_total
        mgr.handle_message_upload(up)
        assert mgr.aggregator.folds_total == before  # rejected pre-fold
        assert (
            Telemetry.get_instance().get_counter(
                "defense_quarantined_rejected_total"
            )
            >= 1
        )

    def test_root_advancing_abandons_open_edge_round(
        self, args_factory, tmp_path
    ):
        """A quorum close at the ROOT can advance past a straggler
        edge: the edge's abandoned partial window must be discarded,
        never mixed into the next round's accumulator."""
        a = args_factory(
            training_type="cross_silo",
            client_num_in_total=4,
            client_num_per_round=4,
            comm_round=3,
            edge_plane="ranks",
            edge_num=2,
            dataset="mnist",
            synthetic_train_size=80,
            synthetic_test_size=20,
            model="lr",
            run_id=f"edgeab_{os.path.basename(str(tmp_path))}",
            rank=1,
            shuffle=False,
        )
        ds = load(a)
        model = models.create(a, ds.class_num)
        mgr = HierEdge(a, None, ds, model).manager
        mgr.register_message_receive_handlers()
        r1, r2 = mgr.client_ranks[:2]
        for r in mgr.client_ranks:
            mgr.client_online[r] = True
        params = mgr.aggregator.get_global_model_params()

        def round_msg(idx):
            m = Message(constants.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT, 0, 0)
            m.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, params)
            m.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, idx)
            m.add_params(
                constants.MSG_ARG_KEY_HIER_ASSIGNMENT,
                {str(r1): 0, str(r2): 1},
            )
            m.add_params(constants.MSG_ARG_KEY_QUARANTINED, [])
            return m

        mgr.handle_message_round(round_msg(0))
        up = Message(constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, r1, 0)
        up.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, 0)
        up.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, params)
        up.add_params(constants.MSG_ARG_KEY_NUM_SAMPLES, 10.0)
        mgr.handle_message_upload(up)
        assert mgr.aggregator.num_received() == 1  # partial, round open
        mgr.handle_message_round(round_msg(1))  # root quorum-advanced
        assert mgr.round_idx == 1
        assert mgr.aggregator.num_received() == 0  # window discarded
        assert (
            Telemetry.get_instance().get_counter(
                "hier_edge_rounds_abandoned_total"
            )
            == 1
        )


def _edge_unit(args_factory, tmp_path, run_tag, **kw):
    """A unit-level edge manager with all clients marked online."""
    a = args_factory(
        training_type="cross_silo",
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=3,
        edge_plane="ranks",
        edge_num=2,
        dataset="mnist",
        synthetic_train_size=80,
        synthetic_test_size=20,
        model="lr",
        run_id=f"{run_tag}_{os.path.basename(str(tmp_path))}",
        rank=1,
        shuffle=False,
        **kw,
    )
    ds = load(a)
    mgr = HierEdge(a, None, ds, models.create(a, ds.class_num)).manager
    mgr.register_message_receive_handlers()
    for r in mgr.client_ranks:
        mgr.client_online[r] = True
    return mgr


def _round_msg_for(mgr, idx, assignment):
    m = Message(constants.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT, 0, 0)
    m.add_params(
        constants.MSG_ARG_KEY_MODEL_PARAMS,
        mgr.aggregator.get_global_model_params(),
    )
    m.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, idx)
    m.add_params(
        constants.MSG_ARG_KEY_HIER_ASSIGNMENT,
        {str(r): s for r, s in assignment.items()},
    )
    m.add_params(constants.MSG_ARG_KEY_QUARANTINED, [])
    return m


@pytest.mark.smoke
class TestHeldRoundLiveness:
    """Regression: a HELD round (a client of its assignment offline at
    arrival) must start as soon as its blocker clears — never wedge."""

    def test_left_client_does_not_hold_a_round_forever(
        self, args_factory, tmp_path
    ):
        """A client that LEFT (OFFLINE) before the round broadcast
        reached the edge must not be awaited: the round starts over the
        survivors (the leaver is excluded like a detector death)."""
        mgr = _edge_unit(args_factory, tmp_path, "edgeleft")
        r1, r2 = mgr.client_ranks[:2]
        off = Message(constants.MSG_TYPE_C2S_CLIENT_STATUS, r2, 0)
        off.add_params(
            constants.MSG_ARG_KEY_CLIENT_STATUS,
            constants.CLIENT_STATUS_OFFLINE,
        )
        mgr.handle_message_client_status(off)
        # the root's broadcast still assigns the leaver (the LEAVE
        # event raced the selection snapshot)
        mgr.handle_message_round(_round_msg_for(mgr, 0, {r1: 0, r2: 1}))
        assert mgr._round_open, "round wedged waiting on a leaver"
        assert mgr.round_idx == 0
        # ...and it expects only the survivor
        assert mgr.aggregator.client_num == 1

    def test_pending_round_starts_when_blocker_comes_online_mid_round(
        self, args_factory, tmp_path
    ):
        """Round R open; the root quorum-advances and broadcasts R+1
        whose assignment includes a briefly-offline client. When that
        client comes ONLINE, the held R+1 must start (abandoning R's
        stale window) instead of only being resynced into dead R."""
        mgr = _edge_unit(args_factory, tmp_path, "edgehold")
        r1, r2 = mgr.client_ranks[:2]
        mgr.handle_message_round(_round_msg_for(mgr, 0, {r1: 0, r2: 1}))
        assert mgr._round_open and mgr.round_idx == 0
        mgr.client_online[r2] = False  # restarting client, not declared
        mgr.handle_message_round(_round_msg_for(mgr, 1, {r1: 0, r2: 1}))
        assert mgr._pending_round is not None  # held on r2
        assert mgr.round_idx == 0
        on = Message(constants.MSG_TYPE_C2S_CLIENT_STATUS, r2, 0)
        on.add_params(
            constants.MSG_ARG_KEY_CLIENT_STATUS,
            constants.CLIENT_STATUS_ONLINE,
        )
        mgr.handle_message_client_status(on)
        assert mgr._pending_round is None
        assert mgr.round_idx == 1 and mgr._round_open


@pytest.mark.smoke
class TestEdgeDeath:
    def test_dead_edge_drops_from_round_and_survivor_closes(self, root_world):
        root, template = root_world
        part = edge_clients(root.partition)
        # edge 1 reports; edge 2 dies silently -> round must close over
        # edge 1 alone instead of stalling the grace window
        root.handle_message_edge_report(
            _edge_report(1, 0, template, part[1], part[1])
        )
        assert root.round_idx == 0  # still waiting on edge 2
        dead = Message(constants.MSG_TYPE_S2S_CLIENT_DEAD, 0, 0)
        dead.add_params(constants.MSG_ARG_KEY_RANK, 2)
        root.handle_message_edge_dead(dead)
        assert root.round_idx == 1  # closed over the survivor
        assert root.edge_deaths == 1
        tel = Telemetry.get_instance()
        assert tel.get_counter("hier_edges_declared_dead_total") == 1
        # the next broadcast goes ONLY to the survivor
        assert _drain(root.args.run_id, 1)
        later = [
            m
            for m in _drain(root.args.run_id, 2)
            if m.get(constants.MSG_ARG_KEY_ROUND_INDEX) == 1
        ]
        assert later == []

    def test_all_edges_dead_finishes_loudly(self, root_world):
        root, _ = root_world
        for e in (1, 2):
            dead = Message(constants.MSG_TYPE_S2S_CLIENT_DEAD, 0, 0)
            dead.add_params(constants.MSG_ARG_KEY_RANK, e)
            root.handle_message_edge_dead(dead)
        tel = Telemetry.get_instance()
        assert tel.get_counter("cross_silo_finish_total") == 1
        finishes = [
            m
            for m in _drain(root.args.run_id, 1)
            if m.get_type() == constants.MSG_TYPE_S2C_FINISH
        ]
        assert finishes  # clients released, not stranded

    def test_detector_declares_silent_edge(self, args_factory, tmp_path):
        """The real detector path: edges beat root-ward; one that stops
        is declared dead via the loopback message (the satellite fix —
        heartbeats route client→edge, so the ROOT watches edges)."""
        a = args_factory(
            training_type="cross_silo",
            client_num_in_total=2,
            client_num_per_round=2,
            comm_round=2,
            edge_plane="ranks",
            edge_num=2,
            heartbeat_timeout_s=0.3,
            dataset="mnist",
            synthetic_train_size=80,
            synthetic_test_size=20,
            model="lr",
            run_id=f"edet_{os.path.basename(str(tmp_path))}",
            rank=0,
            shuffle=False,
        )
        ds = load(a)
        model = models.create(a, ds.class_num)
        agg = FedMLAggregator(a, model, test_data=None)
        mgr = RootServerManager(a, agg, {1: 1, 2: 2})
        try:
            mgr.register_message_receive_handlers()
            for e in (1, 2):
                online = Message(constants.MSG_TYPE_C2S_CLIENT_STATUS, e, 0)
                online.add_params(
                    constants.MSG_ARG_KEY_CLIENT_STATUS,
                    constants.CLIENT_STATUS_ONLINE,
                )
                mgr.handle_message_edge_status(online)
            deadline = time.monotonic() + 5.0
            declared = []
            while time.monotonic() < deadline and not declared:
                declared = [
                    m
                    for m in _drain(a.run_id, 0)
                    if m.get_type() == constants.MSG_TYPE_S2S_CLIENT_DEAD
                ]
                time.sleep(0.05)
            assert declared, "silent edge never declared dead"
        finally:
            if mgr._failure_detector is not None:
                mgr._failure_detector.stop()


@pytest.mark.smoke
class TestEdgeCrashRestart:
    def test_edge_kill_at_barrier_recovers_bit_identical(
        self, args_factory, tmp_path
    ):
        """kill_client at the edge.merge_upload chaos barrier: edge 1
        dies after folding round 0 but before shipping. A restarted
        edge resumes via the root's RESYNC (its WAL sub-ledger has no
        record for the in-flight round — it re-runs it), the world
        completes bit-identically to the clean run, and `fedml-tpu
        check` is green including the multi-tier invariants."""
        clean = _run_hier(args_factory, "hier_ck_clean")
        clean_params = jax.tree.map(
            np.asarray, clean["root"].aggregator.get_global_model_params()
        )
        Telemetry.reset()
        ck = str(tmp_path / "ck")
        td = str(tmp_path / "td")
        kw = dict(
            checkpoint_dir=ck,
            telemetry_dir=td,
            # client beats double as the reconnect probe: a restarted
            # edge learns its clients are (still) online from them —
            # the flat server-restart recovery path, one hop down
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=60.0,
            chaos_schedule=[
                {
                    "at": {
                        "event": "barrier",
                        "name": "edge.merge_upload",
                        "rank": 1,
                        "occurrence": 1,
                    },
                    "fault": {"kind": "kill_client"},
                }
            ],
        )
        n, e_num = 4, 2
        restarted = threading.Event()

        def mk(role, rank):
            return _mk_args(
                args_factory, rank, "hier_ck", n, 2,
                edge_plane="ranks", edge_num=e_num, **kw,
            )

        def edge_wrapper(rank, edge):
            if rank != 1:
                return edge.run

            def run_and_die():
                from fedml_tpu.core.chaos import ProcessKilled

                try:
                    edge.run()
                except ProcessKilled:
                    time.sleep(0.3)  # let the corpse's threads drain
                    a2, ds2, m2 = mk("edge", 1)
                    # fresh incarnation, same args: reads its WAL
                    # sub-ledger, re-announces, gets RESYNCed
                    edge2 = HierEdge(
                        a2, None, ds2, m2, partition=edge.partition
                    )
                    restarted.set()
                    edge2.run()

            return run_and_die

        world = run_local_hier_world(
            mk, n, e_num, edge_wrapper=edge_wrapper
        )
        assert restarted.is_set(), "the kill never fired"
        hier_params = jax.tree.map(
            np.asarray, world["root"].aggregator.get_global_model_params()
        )
        assert _params_equal(clean_params, hier_params)
        report = InvariantChecker(
            telemetry_dir=td, checkpoint_dir=ck
        ).check()
        assert report.ok, report.to_dict()
        assert "edge_partition" in report.checked
        assert "edge_subledger_consistent" in report.checked
        # the sub-ledger proved useful: the restarted edge logged the
        # re-run round exactly once (the killed incarnation never
        # appended — it died before the write-ahead)
        from fedml_tpu.core.checkpoint import RoundWAL

        sub = RoundWAL(os.path.join(ck, "edge_1")).records()
        assert [r["round_idx"] for r in sub] == [0, 1]


@pytest.mark.smoke
class TestMultiTierChecker:
    # measured ~2.3s: inside the fast-gate budget, so tier-1 keeps one
    # real three-tier world end-to-end
    def test_clean_world_passes_and_planted_violations_flag(
        self, args_factory, tmp_path
    ):
        ck, td = str(tmp_path / "ck"), str(tmp_path / "td")
        _run_hier(
            args_factory, "hier_chk", checkpoint_dir=ck, telemetry_dir=td
        )
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()
        for name in (
            "edge_partition",
            "edge_merge_exactly_once",
            "edge_subledger_consistent",
        ):
            assert name in report.checked, report.to_dict()

        # planted violation 1: a rank folded at BOTH edges (double merge)
        wal_path = os.path.join(ck, "round_wal.jsonl")
        with open(wal_path) as fh:
            lines = [json.loads(ln) for ln in fh if ln.strip()]
        doctored = [dict(r) for r in lines]
        ef = doctored[0]["edge_folds"]
        edges = sorted(ef)
        ef[edges[0]] = sorted(set(ef[edges[0]]) | {ef[edges[1]][0]})
        with open(wal_path, "w") as fh:
            for r in doctored:
                fh.write(json.dumps(r) + "\n")
        bad = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert not bad.ok
        assert any(
            v["invariant"] == "edge_partition" for v in bad.violations
        )

        # planted violation 2: a merged set with no sub-ledger twin
        with open(wal_path, "w") as fh:
            for r in lines:
                fh.write(json.dumps(r) + "\n")
        sub_path = os.path.join(ck, "edge_1", "round_wal.jsonl")
        with open(sub_path) as fh:
            sub_lines = [ln for ln in fh if ln.strip()]
        with open(sub_path, "w") as fh:
            fh.writelines(sub_lines[1:])  # drop round 0's write-ahead
        bad2 = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert any(
            v["invariant"] == "edge_subledger_consistent"
            for v in bad2.violations
        )


class TestCliEdge:
    @pytest.mark.slow  # subprocess + jax import
    def test_edge_dry_run_prints_status(self, tmp_path):
        cf = tmp_path / "hier.yaml"
        cf.write_text(
            "\n".join(
                [
                    "train_args:",
                    "  training_type: cross_silo",
                    "  client_num_in_total: 4",
                    "  client_num_per_round: 4",
                    "  comm_round: 1",
                    "hier_args:",
                    "  edge_plane: ranks",
                    "  edge_num: 2",
                    "data_args:",
                    "  dataset: mnist",
                    "  synthetic_train_size: 80",
                    "  synthetic_test_size: 20",
                    "model_args:",
                    "  model: lr",
                ]
            )
        )
        r = subprocess.run(
            [
                sys.executable,
                "-m",
                "fedml_tpu.cli",
                "edge",
                "--rank",
                "1",
                "--cf",
                str(cf),
                "--dry-run",
            ],
            capture_output=True,
            text=True,
            timeout=240,
            cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == 0, r.stderr[-800:]
        status = json.loads(r.stdout.strip().splitlines()[-1])
        assert status["edge_rank"] == 1
        assert status["edge_num"] == 2
        assert len(status["clients"]) == 2
        assert status["fabric"].endswith("_edge1")

    def test_edge_rank_zero_rejected(self, args_factory):
        from fedml_tpu.edge_agent import run_edge

        a = args_factory(
            training_type="cross_silo",
            client_num_in_total=4,
            client_num_per_round=4,
            edge_plane="ranks",
            edge_num=2,
            dataset="mnist",
            synthetic_train_size=80,
            synthetic_test_size=20,
            model="lr",
            rank=0,
        )
        with pytest.raises(ValueError, match="edge rank is 1"):
            run_edge(a, dry_run=True)
