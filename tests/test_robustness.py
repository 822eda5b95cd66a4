"""Fault-tolerance layer (docs/robustness.md): reliable delivery,
failure detection, crash recovery.

Beyond the reference (SURVEY.md §5 "no failure detection / elastic
recovery"): these tests pin the three guarantees of the layer, each
in a world of its own —

- **at-least-once + dedup = exactly-once**: a lossy/duplicating
  network with ``reliable_comm`` produces the same global model as a
  clean one, and the receive-side dedup (not just idempotent
  aggregation) eats the duplicates;
- **liveness**: a client killed WITHOUT sending OFFLINE (kill -9) is
  declared dead by the heartbeat failure detector and the round
  completes over the survivors — no deadline required;
- **crash recovery**: a server restarted mid-federation resumes from
  its checkpoint + round WAL and releases reconnecting clients with
  RESYNC (current round + params), landing on the same global model as
  an uninterrupted run.
"""

import threading
import time

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import constants, models
from fedml_tpu.core.comm.base import BaseCommunicationManager, CommSendError, Observer
from fedml_tpu.core.comm.heartbeat import FailureDetector
from fedml_tpu.core.comm.reliable import ReliableChannel, maybe_wrap_reliable
from fedml_tpu.core.message import Message
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.data import load

from test_cross_silo import _mk_args, _run_world


class _RecordingTransport(BaseCommunicationManager):
    def __init__(self):
        self.sent = []
        self.observer = None

    def send_message(self, msg):
        self.sent.append(msg)

    def add_observer(self, o):
        self.observer = o

    def remove_observer(self, o):
        pass

    def handle_receive_message(self):
        pass

    def stop_receive_message(self):
        pass


class _Sink(Observer):
    def __init__(self):
        self.got = []

    def receive_message(self, t, m):
        self.got.append((int(t), m))


def _tracked_msg(t=3, sender=1, receiver=0):
    return Message(t, sender, receiver)


@pytest.mark.smoke
class TestReliableChannelUnit:
    def test_tracked_send_attaches_seq_and_chan(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=0, retry_base_s=60.0)
        ch.send_message(_tracked_msg())
        m = rec.sent[0]
        assert m.get(constants.MSG_ARG_KEY_COMM_SEQ) == 1
        assert m.get(constants.MSG_ARG_KEY_COMM_CHAN) == ch.channel_id

    def test_retransmits_then_gives_up(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=2, retry_base_s=0.02)
        ch.send_message(_tracked_msg())
        deadline = time.monotonic() + 5.0
        while ch.stats["giveups"] == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(rec.sent) == 3  # original + 2 retransmits
        assert ch.stats["retries"] == 2
        assert ch.stats["giveups"] == 1
        assert ch.pending_unacked() == 0

    def test_ack_stops_retransmission(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=5, retry_base_s=0.05)
        ch.add_observer(_Sink())
        out = _tracked_msg()
        ch.send_message(out)
        ack = Message(constants.MSG_TYPE_COMM_ACK, 0, 1)
        ack.add_params(
            constants.MSG_ARG_KEY_COMM_ACK_SEQ,
            out.get(constants.MSG_ARG_KEY_COMM_SEQ),
        )
        ack.add_params(
            constants.MSG_ARG_KEY_COMM_ACK_CHAN,
            out.get(constants.MSG_ARG_KEY_COMM_CHAN),
        )
        rec.observer.receive_message(ack.get_type(), ack)
        assert ch.pending_unacked() == 0
        time.sleep(0.3)
        assert len(rec.sent) == 1  # no retransmits after the ack
        assert ch.stats["retries"] == 0

    def test_stale_incarnation_ack_ignored(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=5, retry_base_s=60.0)
        ch.add_observer(_Sink())
        out = _tracked_msg()
        ch.send_message(out)
        ack = Message(constants.MSG_TYPE_COMM_ACK, 0, 1)
        ack.add_params(constants.MSG_ARG_KEY_COMM_ACK_SEQ, 1)
        ack.add_params(
            constants.MSG_ARG_KEY_COMM_ACK_CHAN, ch.channel_id ^ 1
        )  # previous incarnation's channel
        rec.observer.receive_message(ack.get_type(), ack)
        assert ch.pending_unacked() == 1

    def test_receive_dedup_and_ack(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=0, retry_max=5, retry_base_s=60.0)
        sink = _Sink()
        ch.add_observer(sink)
        inbound = _tracked_msg(t=3, sender=1, receiver=0)
        inbound.add_params(constants.MSG_ARG_KEY_COMM_SEQ, 7)
        inbound.add_params(constants.MSG_ARG_KEY_COMM_CHAN, 1234)
        ch._observer_wrappers[sink].receive_message(3, inbound)
        ch._observer_wrappers[sink].receive_message(3, inbound)  # duplicate
        assert len(sink.got) == 1  # delivered once
        assert ch.stats["dup_dropped"] == 1
        # BOTH receipts get ACKed (the dup usually means our first ack
        # was lost); acks ship from a worker thread — never the
        # dispatch thread, which a blocking transport send could freeze
        def acks():
            return [
                m for m in rec.sent
                if m.get_type() == constants.MSG_TYPE_COMM_ACK
            ]

        deadline = time.monotonic() + 5.0
        while len(acks()) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(acks()) == 2
        assert acks()[0].get(constants.MSG_ARG_KEY_COMM_ACK_SEQ) == 7
        assert acks()[0].get(constants.MSG_ARG_KEY_COMM_ACK_CHAN) == 1234

    def test_dedup_memory_bounded_per_sender_incarnation(self):
        """Every peer restart mints a fresh channel id; a long-lived
        server must keep only the newest few incarnations' dedup state
        per sender, not grow forever with crash-looping clients."""
        from fedml_tpu.core.comm.reliable import _MAX_INCARNATIONS

        ch = ReliableChannel(_RecordingTransport(), rank=0)
        for chan in range(10):
            assert not ch._is_duplicate(1, chan, seq=1)
        assert len(ch._seen[1]) == _MAX_INCARNATIONS
        # the newest incarnations survive; evicted ones forget
        assert ch._is_duplicate(1, 9, seq=1)
        assert not ch._is_duplicate(1, 0, seq=1)  # evicted: re-learned

    def test_untracked_types_bypass_the_protocol(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=5, retry_base_s=60.0)
        sink = _Sink()
        ch.add_observer(sink)
        # heartbeats: periodic by construction, never tracked
        ch.send_message(
            Message(constants.MSG_TYPE_C2S_HEARTBEAT, 1, 0)
        )
        # self-addressed loopback (deadline timer): never tracked
        ch.send_message(Message(constants.MSG_TYPE_S2S_AGG_DEADLINE, 1, 1))
        assert ch.pending_unacked() == 0
        for m in rec.sent:
            assert m.get(constants.MSG_ARG_KEY_COMM_SEQ) is None
        # an untracked inbound message is delivered without an ack
        ch._observer_wrappers[sink].receive_message(
            constants.MSG_TYPE_C2S_HEARTBEAT,
            Message(constants.MSG_TYPE_C2S_HEARTBEAT, 2, 1),
        )
        assert len(sink.got) == 1
        time.sleep(0.1)  # acks are async; give a stray one time to appear
        assert all(
            m.get_type() != constants.MSG_TYPE_COMM_ACK for m in rec.sent
        )

    def test_composes_with_fault_injector(self):
        """reliable(faulty(transport)) — the managers' stack: an
        injected drop of the FIRST copy is healed by a retransmit that
        re-traverses the injector."""
        from fedml_tpu.core.comm.faults import FaultInjector

        rec = _RecordingTransport()
        fi = FaultInjector(rec, drop_prob=1.0, max_faults=1, msg_types=[3])
        ch = ReliableChannel(fi, rank=1, retry_max=4, retry_base_s=0.02)
        ch.send_message(_tracked_msg())
        deadline = time.monotonic() + 5.0
        while not rec.sent and time.monotonic() < deadline:
            time.sleep(0.02)
        assert rec.sent, "retransmit never recovered the injected drop"
        assert fi.injected["drop"] == 1
        ch.stop_receive_message()

    def test_wrap_disabled_by_default_and_knobs(self, args_factory):
        a = args_factory()
        assert maybe_wrap_reliable("com", a) == "com"
        a.reliable_comm = True
        a.comm_retry_max = 3
        a.comm_retry_base_s = 0.5
        a.rank = 2
        ch = maybe_wrap_reliable(_RecordingTransport(), a)
        assert isinstance(ch, ReliableChannel)
        assert ch.retry_max == 3 and ch.retry_base_s == 0.5

    def test_stop_cancels_pending_retransmits(self):
        rec = _RecordingTransport()
        ch = ReliableChannel(rec, rank=1, retry_max=50, retry_base_s=0.02)
        ch.send_message(_tracked_msg())
        ch.stop_receive_message()
        n = len(rec.sent)
        time.sleep(0.2)
        assert len(rec.sent) == n  # closed: no late retransmits
        assert ch.closed and ch.pending_unacked() == 0


@pytest.mark.smoke
class TestFailureDetectorUnit:
    def test_silent_rank_declared_dead_once(self):
        dead = []
        fd = FailureDetector(0.15, dead.append).start()
        fd.watch(1)
        time.sleep(0.6)
        fd.stop()
        assert dead == [1]  # exactly once, then unwatched

    def test_traffic_defers_declaration(self):
        dead = []
        fd = FailureDetector(0.3, dead.append).start()
        fd.watch(1)
        for _ in range(4):
            time.sleep(0.1)
            fd.note_alive(1)
        assert dead == []
        assert fd.seen_recently(1)
        fd.stop()

    def test_seen_recently_is_per_rank(self):
        fd = FailureDetector(0.2, lambda r: None)
        fd.note_alive(1)
        assert fd.seen_recently(1)
        assert not fd.seen_recently(2)


@pytest.mark.smoke
class TestRoundWAL:
    def test_append_records_last(self, tmp_path):
        from fedml_tpu.core.checkpoint import RoundWAL

        wal = RoundWAL(str(tmp_path))
        wal.append(0, 1, [1, 3, 2])
        wal.append(1, None, [1, 2])
        recs = wal.records()
        assert [r["round_idx"] for r in recs] == [0, 1]
        assert recs[0]["cohort"] == [1, 2, 3]  # sorted
        assert recs[0]["ckpt_step"] == 1 and recs[1]["ckpt_step"] is None
        assert wal.last()["round_idx"] == 1

    def test_torn_final_line_tolerated(self, tmp_path):
        from fedml_tpu.core.checkpoint import RoundWAL

        wal = RoundWAL(str(tmp_path))
        wal.append(0, 1, [1])
        with open(wal.path, "a") as f:
            f.write('{"round_idx": 1, "ckpt_')  # killed mid-append
        assert wal.last()["round_idx"] == 0
        # the restarted server's fresh WAL starts a clean line past the
        # torn fragment and keeps working
        wal2 = RoundWAL(str(tmp_path))
        wal2.append(1, 2, [1])
        assert wal2.last()["round_idx"] == 1
        assert [r["round_idx"] for r in wal2.records()] == [0, 1]

    def test_empty_wal(self, tmp_path):
        from fedml_tpu.core.checkpoint import RoundWAL

        wal = RoundWAL(str(tmp_path))
        assert wal.records() == [] and wal.last() is None

    def test_folded_set_and_publish_records(self, tmp_path):
        """The exactly-once ledger: sync rounds record the folded rank
        set (a subset of the cohort under a quorum close); async
        publishes record (rank, seq) pairs + the dispatch high-water
        mark — and a fresh WAL instance (the restarted server) reads
        them all back."""
        from fedml_tpu.core.checkpoint import RoundWAL

        wal = RoundWAL(str(tmp_path))
        wal.append(0, 1, [1, 2, 3], folded=[2, 1])
        wal.append(
            1, None, [1, 2], folded=[(1, 5), (2, 7)], kind="publish",
            extra={"version": 1, "max_seq": 7, "folds_total": 2},
        )
        recs = RoundWAL(str(tmp_path)).records()
        assert recs[0]["folded"] == [1, 2]
        assert "kind" not in recs[0]
        assert recs[1]["kind"] == "publish"
        assert recs[1]["folded"] == [[1, 5], [2, 7]]
        assert recs[1]["max_seq"] == 7 and recs[1]["folds_total"] == 2


class TestGrpcSendRetry:
    def test_exhausted_retries_raise_typed_error_and_count(self):
        """A send to a dead peer raises CommSendError (counted) after
        the bounded retry loop — not a raw grpc.RpcError, and never a
        300s hang."""
        import socket

        from fedml_tpu.core.comm.grpc_backend import GrpcCommunicationManager

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        Telemetry.reset()
        com = GrpcCommunicationManager(
            rank=0,
            size=2,
            port_base=base,
            send_timeout_s=0.2,
            send_retries=1,
            retry_base_s=0.01,
        )
        try:
            t0 = time.monotonic()
            with pytest.raises(CommSendError) as ei:
                com.send_message(_tracked_msg(t=3, sender=0, receiver=1))
            assert ei.value.receiver == 1 and ei.value.attempts == 2
            assert time.monotonic() - t0 < 5.0
            tel = Telemetry.get_instance()
            assert sum(
                tel.counters_matching("comm_send_errors_total").values()
            ) == 1
            assert sum(
                tel.counters_matching("comm_transport_retries_total").values()
            ) == 1
        finally:
            com.stop_receive_message()


class TestDownloadRetry:
    def test_transient_fetch_error_is_retried(self, tmp_path, monkeypatch):
        from fedml_tpu.data import download as dl

        monkeypatch.setattr(dl, "_FETCH_RETRY_BASE_S", 0.01)
        calls = []

        def flaky(url, dest):
            calls.append(url)
            if len(calls) < 3:
                raise ConnectionResetError("connection reset")
            with open(dest, "wb") as f:
                f.write(b"ok")

        monkeypatch.setattr(dl, "_fetch_once", flaky)
        dl._fetch("http://example.invalid/a.zip", str(tmp_path / "a.zip"))
        assert len(calls) == 3
        assert (tmp_path / "a.zip").read_bytes() == b"ok"

    def test_persistent_failure_still_reaches_offline_grace(
        self, tmp_path, monkeypatch
    ):
        import urllib.error

        from fedml_tpu.data import download as dl

        monkeypatch.setattr(dl, "_FETCH_RETRY_BASE_S", 0.01)
        calls = []

        def dead(url, dest):
            calls.append(url)
            raise urllib.error.URLError("no route to host")

        monkeypatch.setattr(dl, "_fetch_once", dead)
        ok = dl.download_dataset(
            "mnist", str(tmp_path), urls=("http://example.invalid/m.zip",)
        )
        assert ok is False  # offline grace: False, not an exception
        assert len(calls) == dl._FETCH_RETRIES + 1

    def test_permanent_error_not_retried(self, tmp_path, monkeypatch):
        """A 404 (gone archive) fails identically on every attempt —
        no retries, straight to offline grace."""
        import urllib.error

        from fedml_tpu.data import download as dl

        monkeypatch.setattr(dl, "_FETCH_RETRY_BASE_S", 0.01)
        calls = []

        def gone(url, dest):
            calls.append(url)
            raise urllib.error.HTTPError(url, 404, "Not Found", {}, None)

        monkeypatch.setattr(dl, "_fetch_once", gone)
        ok = dl.download_dataset(
            "mnist", str(tmp_path), urls=("http://example.invalid/m.zip",)
        )
        assert ok is False
        assert len(calls) == 1  # not retried


# ---------------------------------------------------------------------
# streaming aggregate-on-arrival (docs/robustness.md round-barrier
# failure model): the fold's exactness/fallback contracts in isolation
# ---------------------------------------------------------------------


@pytest.mark.smoke
class TestStreamingAccumulatorUnit:
    def _trees(self, n=6, seed=0):
        rng = np.random.RandomState(seed)
        trees, ws = [], []
        for _ in range(n):
            scale = 10.0 ** rng.randint(-6, 5)
            trees.append(
                {
                    "k": jax.numpy.asarray(
                        rng.randn(33, 9).astype(np.float32) * scale
                    ),
                    "b": jax.numpy.asarray(rng.randn(9).astype(np.float32)),
                }
            )
            ws.append(float(rng.randint(1, 400)))
        return trees, ws

    def test_fold_is_bitwise_order_independent(self):
        """The acceptance property the streaming worlds lean on:
        whatever order uploads arrive in, finalize() produces the SAME
        float32 bits — even with adversarial magnitude spreads."""
        from fedml_tpu.core.aggregation import StreamingAccumulator

        trees, ws = self._trees()
        rng = np.random.RandomState(7)

        def run(order):
            acc = StreamingAccumulator(trees[0])
            for i in order:
                acc.fold(trees[i], ws[i])
            return acc.finalize()

        ref = run(range(len(trees)))
        for _ in range(10):
            out = run(rng.permutation(len(trees)).tolist())
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                ),
                ref, out,
            )

    def test_fold_matches_weighted_mean(self):
        from fedml_tpu.core.aggregation import StreamingAccumulator

        trees, ws = self._trees(n=4, seed=3)
        acc = StreamingAccumulator(trees[0])
        for t, w in zip(trees, ws):
            acc.fold(t, w)
        W = sum(ws)
        want = jax.tree.map(
            lambda *xs: sum(
                w * np.asarray(x, np.float64) for w, x in zip(ws, xs)
            ) / W,
            *trees,
        )
        jax.tree.map(
            lambda got, w: np.testing.assert_allclose(
                np.asarray(got), w, rtol=5e-6, atol=1e-7
            ),
            acc.finalize(), want,
        )

    def test_partial_cohort_renormalizes(self):
        """A quorum-closed round folds a subset; the finalize divides
        by the folded weight only — identical to a federation that
        never had the stragglers."""
        from fedml_tpu.core.aggregation import StreamingAccumulator

        trees, ws = self._trees(n=5, seed=5)
        full = StreamingAccumulator(trees[0])
        sub = StreamingAccumulator(trees[0])
        for i in (0, 2):
            full.fold(trees[i], ws[i])
            sub.fold(trees[i], ws[i])
        # the subset accumulator is DONE; full would have folded more
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            full.finalize(), sub.finalize(),
        )

    def test_fused_encoded_fold_is_order_independent(self):
        from fedml_tpu.core.aggregation import StreamingAccumulator
        from fedml_tpu.core.compression import Int8Codec

        codec = Int8Codec()
        trees, ws = self._trees(n=3, seed=9)
        g = trees[0]
        encs = [
            codec.encode(jax.tree.map(lambda x: x * 0.01, t)) for t in trees
        ]
        a1 = StreamingAccumulator(g)
        a2 = StreamingAccumulator(g)
        for i in (0, 1, 2):
            a1.fold_encoded(codec, encs[i], g, ws[i])
        for i in (2, 0, 1):
            a2.fold_encoded(codec, encs[i], g, ws[i])
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            a1.finalize(), a2.finalize(),
        )

    def test_finalize_empty_raises(self):
        from fedml_tpu.core.aggregation import StreamingAccumulator

        acc = StreamingAccumulator({"a": jax.numpy.zeros(3)})
        with pytest.raises(RuntimeError, match="no folded"):
            acc.finalize()


@pytest.mark.smoke
class TestStreamingFallback:
    def test_full_cohort_reasons(self, args_factory):
        from fedml_tpu.core.aggregation import needs_full_cohort
        from fedml_tpu.core.frame import DefaultServerAggregator

        a = args_factory()
        assert needs_full_cohort(a, None) is None
        a.defense_type = "median"
        assert "median" in needs_full_cohort(a, None)
        # clipping defenses moved INTO the fold (PR 8): they stream
        for streamable in ("norm_diff_clipping", "weak_dp"):
            a.defense_type = streamable
            assert needs_full_cohort(a, None) is None
        # an unknown string is a loud error, never a silent plain mean
        a.defense_type = "norm_clip"
        with pytest.raises(ValueError, match="unknown defense_type"):
            needs_full_cohort(a, None)
        a.defense_type = None
        assert "ServerAggregator" in needs_full_cohort(
            a, DefaultServerAggregator(None)
        )

    def test_stream_mode_falls_back_loudly(self, args_factory, caplog):
        """agg_mode=stream + median defense: ONE warning, the counter,
        and the buffered path — never a silent wrong answer."""
        import logging as _logging

        import fedml_tpu
        from fedml_tpu import models
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )
        from fedml_tpu.data import load

        Telemetry.reset()
        a = _mk_args(
            args_factory, "fb1", "LOCAL", agg_mode="stream",
            defense_type="median",
        )
        a.rank = 0
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        with caplog.at_level(_logging.WARNING):
            agg = FedMLAggregator(a, m)
        assert not agg.streaming
        warns = [
            r for r in caplog.records
            if "falling back to the BUFFERED" in r.getMessage()
        ]
        assert len(warns) == 1
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("agg_stream_fallback_total").values()
        ) == 1
        # the buffered fallback applies the median over the cohort
        p1 = jax.tree.map(lambda x: jax.numpy.ones_like(x), agg.global_params)
        p2 = jax.tree.map(lambda x: 3 * jax.numpy.ones_like(x), agg.global_params)
        p3 = jax.tree.map(lambda x: 9 * jax.numpy.ones_like(x), agg.global_params)
        agg.begin_round([0, 1, 2])
        for i, p in enumerate((p1, p2, p3)):
            agg.receive_upload(i, 10.0, model_params=p)
        assert agg.peak_buffered == 3  # full cohort buffered (fallback)
        out = agg.aggregate()
        jax.tree.map(
            lambda x: np.testing.assert_allclose(np.asarray(x), 3.0),
            out,
        )


@pytest.mark.smoke
class TestStreamingEqualsBuffered:
    def test_stream_world_bit_identical_to_buffered_world(self, args_factory):
        """The tentpole's acceptance gate in miniature: the same
        federation run with agg_mode=stream (fold on arrival, arrival
        order nondeterministic) and agg_mode=buffered (sorted fold at
        close) lands on the SAME global model bit-for-bit."""
        Telemetry.reset()
        buffered = _run_world(
            args_factory, run_id="sb_buf", backend="LOCAL",
            agg_mode="buffered",
        )
        assert buffered.aggregator.peak_buffered == 4  # O(cohort) baseline
        Telemetry.reset()
        streamed = _run_world(
            args_factory, run_id="sb_str", backend="LOCAL",
            agg_mode="stream",
        )
        assert streamed.aggregator.peak_buffered == 0  # O(model) streaming
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            buffered.aggregator.get_global_model_params(),
            streamed.aggregator.get_global_model_params(),
        )

    @pytest.mark.slow  # two LOCAL worlds (>4s fast-gate budget)
    def test_stream_equals_buffered_with_compression(self, args_factory):
        """Same gate with int8 quantized uplinks: the fused decode+fold
        executable is shared by both modes, so bits still match."""
        Telemetry.reset()
        buffered = _run_world(
            args_factory, run_id="sbc_buf", backend="LOCAL",
            agg_mode="buffered", compression="int8",
        )
        Telemetry.reset()
        streamed = _run_world(
            args_factory, run_id="sbc_str", backend="LOCAL",
            agg_mode="stream", compression="int8",
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            buffered.aggregator.get_global_model_params(),
            streamed.aggregator.get_global_model_params(),
        )


@pytest.mark.smoke
class TestQuorumClose:
    def test_quorum_closes_past_delayed_and_killed_clients(
        self, args_factory, tmp_path
    ):
        """One client delayed past the grace window and one killed
        without OFFLINE (kill -9 analog): the round must close on the
        quorum — the sleeper is dropped by the grace timer, the corpse
        leaves the quorum denominator via the failure detector — late
        uploads are discarded by round tag, nothing is buffered, and
        the InvariantChecker accounts every partial close from the
        world's own artifacts."""
        from fedml_tpu.core.invariants import InvariantChecker
        from fedml_tpu.cross_silo import Client, Server

        Telemetry.reset()
        ck, td = str(tmp_path / "ck"), str(tmp_path / "td")
        kw = dict(
            comm_round=2,
            round_quorum_frac=0.5,
            round_grace_s=1.0,
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=1.0,
            # the WAL (made with the directory) is what the checker
            # reads; no per-round save inside the wall the test bounds
            checkpoint_dir=ck,
            checkpoint_freq=10_000,
            telemetry_dir=td,
        )
        a0, ds0, m0 = _build_node(args_factory, "qc1", 0, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = _build_node(args_factory, "qc1", r, **kw)
            clients.append(Client(a, None, ds, m))

        # rank 3 is slow: it holds every model until the server is
        # done, so no round can close with its upload
        drain = threading.Event()
        slow = clients[2].trainer
        orig_train = slow.train

        def slow_train(params, round_idx):
            drain.wait(30.0)
            return orig_train(params, round_idx)

        slow.train = slow_train

        # rank 2 dies mid-round-0 without OFFLINE
        victim = clients[1]
        orig_tas = victim.manager._train_and_send

        def kill(msg):
            victim.manager._heartbeat.stop()
            raise _Killed()

        victim.manager._train_and_send = kill

        threads = [
            threading.Thread(target=_client_thread, args=(c,), daemon=True)
            for c in clients
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        server.run()
        wall = time.monotonic() - t0
        drain.set()
        for t in threads:
            t.join(timeout=60)
        mgr = server.manager
        assert mgr.round_idx == 2  # every round completed
        assert mgr.quorum_closes >= 1  # the grace timer closed a round
        assert mgr.deaths == 1  # the corpse was declared, not waited on
        assert mgr.stragglers_dropped >= 1
        # both rounds closed inside the sleeper's first hold
        assert wall < 30.0, f"blocked on the straggler ({wall:.1f}s)"
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("agg_quorum_closes_total").values()
        ) >= 1
        assert server.aggregator.peak_buffered == 0  # O(model) streaming
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()
        assert "cohort_accounting" in report.checked

    def test_late_upload_discarded_and_counted(self, args_factory):
        """The quorum/deadline late-upload policy: an upload tagged
        with an already-closed round is discarded by round tag and
        counted in agg_late_uploads_total — never folded."""
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )
        from fedml_tpu.cross_silo.horizontal.fedml_server_manager import (
            FedMLServerManager,
        )

        Telemetry.reset()
        a, ds, m = _build_node(args_factory, "late1", 0)
        agg = FedMLAggregator(a, m)
        mgr = FedMLServerManager(a, agg, rank=0, size=5, backend="LOCAL")
        mgr.round_idx = 5
        up = Message(constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 2, 0)
        up.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, 3)  # stale round
        up.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, agg.global_params)
        up.add_params(constants.MSG_ARG_KEY_NUM_SAMPLES, 10.0)
        mgr.handle_message_receive_model_from_client(up)
        assert agg.num_received() == 0  # never folded
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("agg_late_uploads_total").values()
        ) == 1
        mgr.com_manager.stop_receive_message()

    def test_quorum_denominator_shrinks_with_client_num(self, args_factory):
        """Unit: quorum target follows the live cohort size the failure
        detector shrinks (drop_expected), so a dead rank stops counting
        against the quorum."""
        import fedml_tpu
        from fedml_tpu import models
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )
        from fedml_tpu.data import load

        a = _mk_args(args_factory, "qd1", "LOCAL", round_quorum_frac=0.75)
        a.rank = 0
        a = fedml_tpu.init(a)
        ds = load(a)
        m = models.create(a, ds.class_num)
        agg = FedMLAggregator(a, m)
        agg.begin_round([0, 1, 2, 3])
        assert agg.quorum_target(0.75) == 3
        p = agg.global_params
        agg.receive_upload(0, 10.0, model_params=p)
        agg.receive_upload(1, 10.0, model_params=p)
        assert not agg.quorum_met(0.75)
        # the detector declares rank 4 (index 3) dead: 0.75 * 3 -> 3,
        # ceil -> 3... with 3 alive the target is ceil(2.25)=3? No:
        # client_num shrinks to 3, target ceil(0.75*3) = 3 > 2 folded.
        # Another death (index 2) shrinks to 2: target ceil(1.5)=2 == met.
        assert agg.drop_expected(3)
        assert agg.quorum_target(0.75) == 3
        assert not agg.quorum_met(0.75)
        assert agg.drop_expected(2)
        assert agg.quorum_target(0.75) == 2
        assert agg.quorum_met(0.75)
        assert agg.missing_indexes() == []


# ---------------------------------------------------------------------
# world-level scenarios: kill, duplicate and restart, one at a time
# ---------------------------------------------------------------------


def _build_node(args_factory, run_id, rank, **kw):
    a = _mk_args(args_factory, run_id, "LOCAL", **kw)
    a.rank = rank
    a = fedml_tpu.init(a)
    ds = load(a)
    m = models.create(a, ds.class_num)
    return a, ds, m


class _Killed(Exception):
    pass


def _client_thread(c):
    try:
        c.run()
    except _Killed:  # lint: except-ok — the scripted kill IS the test
        pass


@pytest.mark.smoke
class TestKilledClientFailureDetector:
    def test_killed_client_cannot_stall_the_round(self, args_factory):
        """kill -9 analog: a client dies mid-round WITHOUT an OFFLINE
        message and with NO aggregation deadline armed — only the
        heartbeat failure detector unstalls the federation. Later
        rounds exclude the corpse from broadcasts."""
        from fedml_tpu.cross_silo import Client, Server

        # only the detector closes the victim's round, so the timeout is
        # the test's own wait; 1 s read four deaths on a starved core
        kw = dict(
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=3.0,
            comm_round=3,
        )
        a0, ds0, m0 = _build_node(args_factory, "fd_kill", 0, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = _build_node(args_factory, "fd_kill", r, **kw)
            clients.append(Client(a, None, ds, m))

        victim = clients[1]
        orig = victim.manager._train_and_send

        def kill_or_train(msg):
            if int(msg.get(constants.MSG_ARG_KEY_ROUND_INDEX, 0)) == 1:
                # all the process's threads die with it
                victim.manager._heartbeat.stop()
                raise _Killed()
            orig(msg)

        victim.manager._train_and_send = kill_or_train

        threads = [
            threading.Thread(target=_client_thread, args=(c,), daemon=True)
            for c in clients
        ]
        for t in threads:
            t.start()
        server.run()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "clients hung"
        assert server.manager.round_idx == 3  # every round completed
        assert server.manager.deaths == 1
        assert 2 in server.manager._dead_ranks
        tel = Telemetry.get_instance()
        assert (
            sum(
                tel.counters_matching(
                    "cross_silo_clients_declared_dead_total"
                ).values()
            )
            == 1
        )


    def test_replacement_is_resynced_into_the_pending_round(self, args_factory):
        """The killed client comes back (same rank, a new process): the
        server RESYNCs it into the round it died in, nobody is declared
        dead, every upload is aggregated exactly once and the model is
        the clean run's, bit for bit."""
        from fedml_tpu.cross_silo import Client, Server

        Telemetry.reset()
        clean = _run_world(args_factory, run_id="rs_clean", backend="LOCAL")
        Telemetry.reset()
        # deaths here are healed by the restart, not declared
        kw = dict(heartbeat_interval_s=0.1, heartbeat_timeout_s=60.0)
        a0, ds0, m0 = _build_node(args_factory, "rs_kill", 0, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = _build_node(args_factory, "rs_kill", r, **kw)
            clients.append(Client(a, None, ds, m))
        victim = clients[1]
        orig = victim.manager._train_and_send
        killed = threading.Event()

        def kill_or_train(msg):
            if int(msg.get(constants.MSG_ARG_KEY_ROUND_INDEX, 0)) == 1:
                victim.manager._heartbeat.stop()
                killed.set()
                raise _Killed()
            orig(msg)

        victim.manager._train_and_send = kill_or_train

        threads = [
            threading.Thread(target=_client_thread, args=(c,), daemon=True)
            for c in clients
        ]
        for t in threads:
            t.start()
        st = threading.Thread(target=server.run, daemon=True)
        st.start()
        assert killed.wait(timeout=120)
        a, ds, m = _build_node(args_factory, "rs_kill", 2, **kw)
        replacement = threading.Thread(
            target=Client(a, None, ds, m).run, daemon=True
        )
        replacement.start()
        st.join(timeout=120)
        for t in threads + [replacement]:
            t.join(timeout=60)
        assert not st.is_alive() and not replacement.is_alive()
        assert server.manager.round_idx == 3 and server.manager.deaths == 0
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("cross_silo_resyncs_total").values()
        ) >= 1
        assert sum(
            tel.counters_matching("cross_silo_clients_aggregated_total").values()
        ) == 3 * 4
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            clean.aggregator.get_global_model_params(),
            server.aggregator.get_global_model_params(),
        )


@pytest.mark.smoke
class TestExactlyOnceUnderDuplication:
    def test_dup_and_delay_aggregated_exactly_once(self, args_factory):
        """Every message duplicated and some delayed, with the reliable
        channel on: receive-side dedup means aggregation sees each
        upload exactly once (counters), and the global model matches a
        clean run bit-for-bit."""
        Telemetry.reset()
        clean = _run_world(args_factory, run_id="rel_clean", backend="LOCAL")
        Telemetry.reset()
        lossy = _run_world(
            args_factory,
            run_id="rel_dup",
            backend="LOCAL",
            reliable_comm=True,
            comm_retry_max=8,
            comm_retry_base_s=0.05,
            fault_injection={
                "duplicate_prob": 0.5,
                "delay_s": 0.05,
                "delay_prob": 0.2,
            },
        )
        tel = Telemetry.get_instance()
        dup_dropped = sum(
            tel.counters_matching("comm_dup_dropped_total").values()
        )
        aggregated = sum(
            tel.counters_matching("cross_silo_clients_aggregated_total").values()
        )
        assert dup_dropped > 0, "dedup never exercised"
        assert aggregated == 3 * 4  # comm_round x clients, exactly once
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            clean.aggregator.get_global_model_params(),
            lossy.aggregator.get_global_model_params(),
        )


@pytest.mark.smoke
class TestServerRestartResync:
    def test_restart_resumes_round_and_resyncs_clients(
        self, args_factory, tmp_path
    ):
        """Server crashes after round 0 closes; a fresh server restores
        the checkpoint + WAL, the still-running clients re-announce via
        heartbeats, and the resumed federation lands on the same global
        model as an uninterrupted run."""
        from fedml_tpu.cross_silo import Client, Server

        class _Crash(Exception):
            pass

        Telemetry.reset()
        straight = _run_world(args_factory, run_id="rs_straight", backend="LOCAL")

        Telemetry.reset()
        kw = dict(
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=60.0,
            checkpoint_dir=str(tmp_path / "rs_ck"),
            checkpoint_freq=1,
        )
        a0, ds0, m0 = _build_node(args_factory, "rs_world", 0, **kw)
        server1 = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = _build_node(args_factory, "rs_world", r, **kw)
            clients.append(Client(a, None, ds, m))

        crashed = threading.Event()
        mgr1 = server1.manager
        orig_report = mgr1._report_round

        def report_then_crash(eval_round, cohort, n_aggregated):
            orig_report(eval_round, cohort, n_aggregated)
            if eval_round == 0 and not crashed.is_set():
                if mgr1._failure_detector is not None:
                    mgr1._failure_detector.stop()
                crashed.set()
                raise _Crash()

        mgr1._report_round = report_then_crash

        threads = [
            threading.Thread(target=c.run, daemon=True) for c in clients
        ]
        for t in threads:
            t.start()

        def server1_thread():
            try:
                server1.run()
            except _Crash:  # lint: except-ok — the scripted crash IS the test
                pass

        st = threading.Thread(target=server1_thread, daemon=True)
        st.start()
        assert crashed.wait(timeout=120)
        st.join(timeout=60)
        assert not st.is_alive()

        a0b, ds0b, m0b = _build_node(args_factory, "rs_world", 0, **kw)
        server2 = Server(a0b, None, ds0b, m0b)
        # resumed at the round after the completed one (ckpt step =
        # next round to run)
        assert server2.manager.round_idx >= 1
        assert server2.manager._resumed
        server2.run()
        for t in threads:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in threads), "clients hung"
        assert server2.manager.round_idx == 3
        # the WAL saw every completed round across both incarnations,
        # each with its folded set (full cohort here — no quorum close)
        recs = server2.manager._wal.records()
        rounds_logged = [r["round_idx"] for r in recs]
        assert rounds_logged == [0, 1, 2]
        assert all(r["folded"] == [1, 2, 3, 4] for r in recs)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            straight.aggregator.get_global_model_params(),
            server2.aggregator.get_global_model_params(),
        )


class TestResyncHandler:
    def test_client_resync_trains_like_a_sync(self, args_factory):
        """A RESYNC downlink is handled exactly like a sync: train the
        assigned silo at the carried round and upload (unit-level — no
        world)."""
        from fedml_tpu.cross_silo.horizontal.fedml_client_manager import (
            FedMLClientManager, FedMLTrainer,
        )

        a, ds, m = _build_node(args_factory, "resync_unit", 1)
        trainer = FedMLTrainer(a, ds, m)
        mgr = FedMLClientManager(a, trainer, rank=1, size=5, backend="LOCAL")
        sent = []
        mgr.send_message = lambda msg: sent.append(msg)
        params = m.init(jax.random.PRNGKey(0))
        msg = Message(constants.MSG_TYPE_S2C_RESYNC, 0, 1)
        msg.add_params(constants.MSG_ARG_KEY_MODEL_PARAMS, params)
        msg.add_params(constants.MSG_ARG_KEY_CLIENT_INDEX, 0)
        msg.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, 2)
        mgr.handle_message_resync(msg)
        assert len(sent) == 1
        up = sent[0]
        assert up.get_type() == constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER
        assert up.get(constants.MSG_ARG_KEY_ROUND_INDEX) == 2
