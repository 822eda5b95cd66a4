"""Async staleness-weighted aggregation (agg_mode=async, FedBuff-style
— docs/robustness.md "round-barrier failure model").

The server never barriers on a cohort: each upload is an update DELTA
folded on arrival with weight ``n * staleness_decay^staleness`` (hard
cap ``staleness_max``), and every ``async_publish_every`` folds the
global model publishes — through the checkpoint dir, which is the
serving plane's hot-swap feed. These tests pin:

- the staleness-weight unit oracle (``core.aggregation.staleness_weight``)
  and the hard cap;
- a LOCAL async world completes with every accepted update folded
  exactly once (fold counters == distinct (rank, seq) ledger);
- exactly-once holds under duplication + delay faults with the
  reliable channel on;
- a server restart mid-run seeds the fold ledger from the WAL's
  publish records: the resumed run finishes and no (rank, seq) pair
  ever folds twice across both incarnations;
- publishes land in the checkpoint dir where a ``CheckpointWatcher``
  (the serving plane's consumer) can see them.
"""

import threading
import time

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import constants, models
from fedml_tpu.core.aggregation import StreamingAccumulator, staleness_weight
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.data import load

from test_cross_silo import _mk_args


@pytest.mark.smoke
class TestStalenessOracle:
    def test_weight_formula(self):
        assert staleness_weight(10, 0, 0.5) == 10.0
        assert staleness_weight(10, 3, 0.5) == 10.0 * 0.125
        assert staleness_weight(7, 2, 1.0) == 7.0  # decay 1 = no discount
        np.testing.assert_allclose(
            staleness_weight(100, 5, 0.9), 100 * 0.9**5
        )

    def test_negative_staleness_rejected(self):
        with pytest.raises(ValueError, match="staleness"):
            staleness_weight(10, -1, 0.5)

    def test_knob_validation(self, args_factory):
        with pytest.raises(ValueError, match="agg_mode"):
            args_factory(agg_mode="bogus")
        with pytest.raises(ValueError, match="round_quorum_frac"):
            args_factory(round_quorum_frac=1.5)
        with pytest.raises(ValueError, match="staleness_decay"):
            args_factory(staleness_decay=0.0)
        with pytest.raises(ValueError, match="async_publish_every"):
            args_factory(agg_mode="async", async_publish_every=0)
        with pytest.raises(ValueError, match="aggregation_deadline_s"):
            args_factory(agg_mode="async", aggregation_deadline_s=5.0)
        a = args_factory(
            agg_mode="async", staleness_decay=0.25, staleness_max=3,
            async_publish_every=2,
        )
        assert a.staleness_decay == 0.25 and a.async_publish_every == 2

    def test_async_rejects_full_cohort_aggregators(self, args_factory):
        """median/custom aggregators cannot stream; async has no
        buffered fallback to offer, so construction must fail loudly."""
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )

        a = _mk_args(args_factory, "async_med", "LOCAL", agg_mode="async",
                     defense_type="median")
        a.rank = 0
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        with pytest.raises(ValueError, match="agg_mode=async"):
            FedMLAggregator(a, m)


@pytest.mark.smoke
class TestAsyncFoldUnit:
    def test_delta_fold_publish_applies_weighted_mean(self, args_factory):
        """publish_async: global += weighted-mean of folded deltas,
        with staleness scales riding the weights."""
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )

        a = _mk_args(args_factory, "async_unit", "LOCAL", agg_mode="async")
        a.rank = 0
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        agg = FedMLAggregator(a, m)
        g0 = jax.tree.map(np.asarray, agg.get_global_model_params())
        d1 = jax.tree.map(lambda x: np.ones_like(x) * 0.5, g0)
        d2 = jax.tree.map(lambda x: -np.ones_like(x) * 0.25, g0)
        agg.fold_delta(10.0, delta=d1, weight_scale=1.0)  # w=10
        agg.fold_delta(20.0, delta=d2, weight_scale=0.5)  # w=10
        assert agg.pending_folds() == 2
        agg.publish_async()
        assert agg.pending_folds() == 0
        want = jax.tree.map(lambda g: g + (10 * 0.5 + 10 * -0.25) / 20, g0)
        jax.tree.map(
            lambda got, w: np.testing.assert_allclose(
                np.asarray(got), w, rtol=1e-6
            ),
            agg.get_global_model_params(),
            want,
        )

    def test_publish_with_nothing_folded_is_noop(self, args_factory):
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )

        a = _mk_args(args_factory, "async_unit2", "LOCAL", agg_mode="async")
        a.rank = 0
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        agg = FedMLAggregator(a, m)
        g0 = jax.tree.map(np.asarray, agg.get_global_model_params())
        agg.publish_async()
        jax.tree.map(
            lambda got, w: np.testing.assert_array_equal(np.asarray(got), w),
            agg.get_global_model_params(), g0,
        )


def _build_async_world(args_factory, run_id, n_clients=4, **kw):
    from fedml_tpu.cross_silo import Client, Server

    base = dict(agg_mode="async", **kw)

    def make(rank):
        a = _mk_args(args_factory, run_id, "LOCAL", **base)
        a.rank = rank
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        return a, ds, m

    a0, ds0, m0 = make(0)
    server = Server(a0, None, ds0, m0)
    clients = []
    for r in range(1, n_clients + 1):
        a, ds, m = make(r)
        clients.append(Client(a, None, ds, m))
    return server, clients


def _run_async_world(args_factory, run_id, n_clients=4, **kw):
    server, clients = _build_async_world(args_factory, run_id, n_clients, **kw)
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=90)
    assert not any(t.is_alive() for t in threads), "clients hung"
    return server


def _assert_exactly_once(mgr, expect_target=True):
    """Every accepted update folded exactly once: the weight log's
    (rank, seq) pairs are distinct, agree with the ledger, and match
    the staleness-weight oracle."""
    ids = [(e["rank"], e["seq"]) for e in mgr.async_weight_log]
    assert len(ids) == len(set(ids)), "a (rank, seq) pair folded twice"
    for e in mgr.async_weight_log:
        np.testing.assert_allclose(
            e["weight"],
            staleness_weight(
                e["sample_num"], e["staleness"], mgr.staleness_decay
            ),
        )
    if expect_target:
        assert mgr.async_folds >= mgr._async_target_folds()


@pytest.mark.smoke
class TestAsyncWorld:
    def test_async_world_completes_exactly_once(self, args_factory):
        Telemetry.reset()
        server = _run_async_world(
            args_factory, "async_w1", async_publish_every=3,
        )
        mgr = server.manager
        target = mgr._async_target_folds()
        assert target == 3 * 4  # comm_round x clients
        assert mgr.async_folds == target
        assert mgr.version >= target // mgr.async_publish_every
        _assert_exactly_once(mgr)
        # params stayed finite (convergence is no test's claim)
        for leaf in jax.tree.leaves(server.aggregator.get_global_model_params()):
            assert np.isfinite(np.asarray(leaf)).all()
        tel = Telemetry.get_instance()
        folds = sum(tel.counters_matching("agg_folds_total").values())
        assert folds == target
        publishes = sum(tel.counters_matching("agg_publish_total").values())
        assert publishes == mgr.version

    def test_async_exactly_once_under_dup_and_delay(self, args_factory):
        """Network duplication + delay with the reliable channel on:
        the dedup plus the (rank, seq) ledger keep every accepted
        update folded exactly once."""
        Telemetry.reset()
        server = _run_async_world(
            args_factory, "async_w2",
            async_publish_every=2,
            reliable_comm=True,
            comm_retry_max=8,
            comm_retry_base_s=0.05,
            fault_injection={
                "duplicate_prob": 0.5,
                "delay_s": 0.05,
                "delay_prob": 0.2,
            },
        )
        mgr = server.manager
        _assert_exactly_once(mgr)
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("comm_dup_dropped_total").values()
        ) > 0, "dedup never exercised"
        assert mgr.async_folds == mgr._async_target_folds()

    def test_straggler_update_is_staleness_discounted(self, args_factory):
        """One client holds its first model until the server has
        published past it, so that upload lands stale and folds with
        decay^staleness < 1 — and the run still completes."""
        # publish_every=1: every fold bumps the version. The initial
        # dispatch hands every rank version 0; the sleeper trains on it
        # only once a publish exists, and the others hold their second
        # model until the sleeper's upload has folded, so the fold
        # target cannot be reached without a stale fold
        server, clients = _build_async_world(
            args_factory, "async_w3", async_publish_every=1,
            staleness_decay=0.5, staleness_max=50,
        )
        mgr = server.manager
        sleeper = 3

        def wait_for(cond):
            deadline = time.monotonic() + 60.0
            while not cond() and time.monotonic() < deadline:
                time.sleep(0.01)

        def held(c, cond):
            orig = c.trainer.train

            def train(params, round_idx):
                wait_for(cond)
                return orig(params, round_idx)

            c.trainer.train = train

        for rank, c in enumerate(clients, start=1):
            if rank == sleeper:
                held(c, lambda: mgr.version >= 1)
            else:
                held(c, lambda: mgr.version < 1 or any(
                    e["rank"] == sleeper for e in mgr.async_weight_log
                ))
        threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
        for t in threads:
            t.start()
        server.run()
        for t in threads:
            t.join(timeout=90)
        _assert_exactly_once(mgr)
        first = next(e for e in mgr.async_weight_log if e["rank"] == sleeper)
        assert first["staleness"] >= 1, first
        stale_folds = [e for e in mgr.async_weight_log if e["staleness"] > 0]
        for e in stale_folds:
            assert e["weight"] < e["sample_num"]  # discount applied


class TestAsyncLiveness:
    @pytest.mark.slow  # detector-paced LOCAL world (>4s fast-gate budget)
    def test_all_clients_dead_finishes_instead_of_hanging(self, args_factory):
        """Async's only finish path is an upload; when every client is
        kill -9'd the failure detector must shut the federation down
        loudly — not hang forever waiting for folds."""

        class _Killed(Exception):
            pass

        server, clients = _build_async_world(
            args_factory, "async_dead", n_clients=2,
            heartbeat_interval_s=0.1, heartbeat_timeout_s=0.8,
            client_num_in_total=2, client_num_per_round=2,
        )

        def kill(mgr):
            def _k(msg):
                if mgr._heartbeat is not None:
                    mgr._heartbeat.stop()
                raise _Killed()

            return _k

        for c in clients:
            c.manager._train_and_send = kill(c.manager)

        def client_thread(c):
            try:
                c.run()
            except _Killed:
                pass

        threads = [
            threading.Thread(target=client_thread, args=(c,), daemon=True)
            for c in clients
        ]
        for t in threads:
            t.start()
        done = threading.Event()

        def server_thread():
            server.run()
            done.set()

        st = threading.Thread(target=server_thread, daemon=True)
        st.start()
        assert done.wait(timeout=60), "async server hung with no clients left"
        assert server.manager.async_folds == 0
        assert server.manager.deaths == 2


@pytest.mark.smoke
class TestAsyncRestartReplay:
    def test_wal_ledger_survives_server_restart(self, args_factory, tmp_path):
        """Server crashes right after a publish; the restarted server
        seeds its fold ledger from the WAL's publish records, resumes
        at the published version, completes the fold target, no
        (rank, seq) pair folds twice across both incarnations, and the
        InvariantChecker proves the same from the artifacts alone."""
        from fedml_tpu.core.invariants import InvariantChecker
        from fedml_tpu.cross_silo import Client, Server

        class _Crash(Exception):
            pass

        Telemetry.reset()
        kw = dict(
            agg_mode="async",
            async_publish_every=2,
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=60.0,
            checkpoint_dir=str(tmp_path / "async_ck"),
            checkpoint_freq=1,
            telemetry_dir=str(tmp_path / "async_td"),
            comm_round=4,
        )

        def make(rank):
            a = _mk_args(args_factory, "async_rs", "LOCAL", **kw)
            a.rank = rank
            a = fedml_tpu.init(a)
            ds = load(a)
            m = models.create(a, ds.class_num)
            return a, ds, m

        a0, ds0, m0 = make(0)
        server1 = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = make(r)
            clients.append(Client(a, None, ds, m))

        crashed = threading.Event()
        mgr1 = server1.manager
        orig_publish = mgr1._async_publish

        def publish_then_crash():
            orig_publish()
            if mgr1.version == 2 and not crashed.is_set():
                if mgr1._failure_detector is not None:
                    mgr1._failure_detector.stop()
                crashed.set()
                raise _Crash()

        mgr1._async_publish = publish_then_crash

        threads = [
            threading.Thread(target=c.run, daemon=True) for c in clients
        ]
        for t in threads:
            t.start()

        def server1_thread():
            try:
                server1.run()
            except _Crash:
                pass

        st = threading.Thread(target=server1_thread, daemon=True)
        st.start()
        assert crashed.wait(timeout=120)
        st.join(timeout=60)
        assert not st.is_alive()
        folded_before = set(
            (e["rank"], e["seq"]) for e in mgr1.async_weight_log
        )

        a0b, ds0b, m0b = make(0)
        server2 = Server(a0b, None, ds0b, m0b)
        mgr2 = server2.manager
        assert mgr2._resumed
        assert mgr2.version >= 2  # resumed at (or past) the crash publish
        # the WAL publish records seeded the dedup ledger
        assert folded_before <= mgr2._folded_ids
        assert mgr2.async_folds >= len(folded_before)
        server2.run()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "clients hung"
        assert mgr2.async_folds >= mgr2._async_target_folds()
        # exactly-once ACROSS incarnations: nothing folded before the
        # crash folded again after it
        folded_after = set((e["rank"], e["seq"]) for e in mgr2.async_weight_log)
        assert not (folded_before & folded_after)
        _assert_exactly_once(mgr2, expect_target=False)
        # and the WAL's full publish ledger is duplicate-free
        pairs = []
        for rec in mgr2._wal.records():
            if rec.get("kind") == "publish":
                pairs.extend(tuple(p) for p in rec.get("folded") or [])
        assert len(pairs) == len(set(pairs))
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("agg_folds_total").values()
        ) == len(pairs)
        report = InvariantChecker(
            telemetry_dir=str(tmp_path / "async_td"),
            checkpoint_dir=str(tmp_path / "async_ck"),
        ).check()
        assert report.ok, report.to_dict()
        for name in ("exactly_once_folds", "version_monotone",
                     "no_reissued_seqs", "no_lost_unreported_folds"):
            assert name in report.checked, report.to_dict()


class TestAsyncServingFeed:
    @pytest.mark.slow  # LOCAL world + watcher poll
    def test_publishes_feed_checkpoint_watcher(self, args_factory, tmp_path):
        """Every publish checkpoints; the serving plane's
        CheckpointWatcher (PR 4) sees the newest version — train-to-
        serve continuous rollout without a restart."""
        from fedml_tpu.core.checkpoint import CheckpointWatcher

        server = _run_async_world(
            args_factory, "async_serve",
            async_publish_every=2,
            checkpoint_dir=str(tmp_path / "pub_ck"),
            checkpoint_freq=1,
        )
        mgr = server.manager
        assert mgr.version > 0
        watcher = CheckpointWatcher(str(tmp_path / "pub_ck"))
        try:
            update = watcher.poll()
            assert update is not None
            step, state = update
            assert step == mgr.version
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                ),
                state["params"],
                server.aggregator.get_global_model_params(),
            )
        finally:
            watcher.close()
