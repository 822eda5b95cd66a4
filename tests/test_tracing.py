"""Distributed tracing (core/tracing.py, cli trace) and its satellites.

Covers the PR 6 acceptance contract:
- trace-context propagation: stamping, resend detection, causal
  continuation, msgpack wire-format survival;
- flow events: comm.send/comm.recv spans with matched ph:"s"/"f"
  pairs, retransmits reusing the original flow id + comm.retry spans,
  composition with FaultInjector/ReliableChannel;
- cross-process stitching: a deterministic two-rank shard pair with
  injected clock skew — skew recovered from the RTT flow pairs,
  per-track timestamps monotonic after correction, causality restored;
- critical-path analytics: per-round segments summing to round wall,
  straggler naming, slack;
- a real two-client LOCAL cross-silo world: matched flows end-to-end,
  round_report coverage, live SLO/segment series, and bit-identical
  aggregation with tracing on vs telemetry off;
- satellites: flight-recorder ring sizing + counted drops, the
  /metrics exposition server, profile_rounds device capture, knob
  validation.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.smoke

from fedml_tpu import constants
from fedml_tpu.core.comm.base import BaseCommunicationManager, Observer
from fedml_tpu.core.comm.faults import FaultInjector
from fedml_tpu.core.comm.instrument import (
    InstrumentedCommunicationManager,
    payload_nbytes,
)
from fedml_tpu.core.comm.reliable import ReliableChannel
from fedml_tpu.core.message import Message
from fedml_tpu.core.telemetry import FlightRecorder, MetricsServer, Telemetry
from fedml_tpu.core.tracing import (
    RoundProfiler,
    analyze_rounds,
    continue_context,
    flow_match_stats,
    stamp_context,
    stitch_shards,
    trace_run,
)

from test_telemetry import _check_trace_schema


def _msg(t=3, payload=None, sender=1, receiver=0, round_idx=None):
    m = Message(t, sender, receiver)
    if payload is not None:
        m.add_params(Message.MSG_ARG_KEY_MODEL_PARAMS, payload)
    if round_idx is not None:
        m.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, round_idx)
    return m


class _FakeTransport(BaseCommunicationManager):
    def __init__(self):
        self.sent = []
        self.observers = []

    def send_message(self, msg):
        self.sent.append(msg)

    def add_observer(self, o):
        self.observers.append(o)

    def remove_observer(self, o):
        self.observers.remove(o)

    def handle_receive_message(self):
        pass

    def stop_receive_message(self):
        pass

    def deliver(self, msg):
        for o in self.observers:
            o.receive_message(msg.get_type(), msg)


class TestTraceContext:
    def test_stamp_assigns_unique_flow_and_trace_id(self, args_factory):
        tel = Telemetry.get_instance(args_factory(run_id="ctx"))
        m1, m2 = _msg(), _msg()
        f1, r1 = stamp_context(m1, tel, rank=1)
        f2, r2 = stamp_context(m2, tel, rank=1)
        assert f1 != f2 and not r1 and not r2
        assert m1.get(constants.MSG_ARG_KEY_TRACE_ID) == "fedrun-ctx"
        assert m1.get(constants.MSG_ARG_KEY_TRACE_FLOW) == f1

    def test_restamp_is_resend_and_keeps_flow(self):
        tel = Telemetry.get_instance()
        m = _msg()
        f1, _ = stamp_context(m, tel, rank=1)
        f2, resend = stamp_context(m, tel, rank=1)
        assert f2 == f1 and resend is True

    def test_loopback_never_stamped(self):
        tel = Telemetry.get_instance()
        m = _msg(sender=0, receiver=0)
        flow, resend = stamp_context(m, tel, rank=0)
        assert flow is None and resend is False
        assert m.get(constants.MSG_ARG_KEY_TRACE_FLOW) is None

    def test_flow_ids_unique_across_ranks(self):
        tel = Telemetry.get_instance()
        f1, _ = stamp_context(_msg(), tel, rank=1)
        f2, _ = stamp_context(_msg(), tel, rank=2)
        assert f1 != f2

    def test_continue_context_links_parent(self):
        tel = Telemetry.get_instance()
        inbound = _msg(t=2, sender=0, receiver=1)
        flow, _ = stamp_context(inbound, tel, rank=0)
        out = _msg(t=3, sender=1, receiver=0)
        continue_context(inbound, out)
        assert out.get(constants.MSG_ARG_KEY_TRACE_SPAN) == flow
        assert out.get(constants.MSG_ARG_KEY_TRACE_ID) == inbound.get(
            constants.MSG_ARG_KEY_TRACE_ID
        )

    def test_context_survives_wire_format(self):
        """msgpack roundtrip (gRPC/MQTT path): the ctx params must be
        plain scalars/strings that flax msgpack handles verbatim."""
        tel = Telemetry.get_instance()
        m = _msg(payload={"w": np.ones((4,), np.float32)})
        flow, _ = stamp_context(m, tel, rank=3)
        back = Message.from_bytes(m.to_bytes())
        assert int(back.get(constants.MSG_ARG_KEY_TRACE_FLOW)) == flow
        assert back.get(constants.MSG_ARG_KEY_TRACE_ID) == m.get(
            constants.MSG_ARG_KEY_TRACE_ID
        )

    def test_payload_nbytes_excludes_ctx(self):
        m = _msg(payload={"w": np.ones((8,), np.float32)})
        before = payload_nbytes(m)
        stamp_context(m, Telemetry.get_instance(), rank=0)
        assert payload_nbytes(m) == before


class TestFlowEvents:
    def test_send_emits_span_and_flow_start(self):
        tel = Telemetry.get_instance()
        inst = InstrumentedCommunicationManager(_FakeTransport(), tel, rank=1)
        inst.send_message(_msg(round_idx=4))
        evs = tel.recorder.tail()
        b = next(e for e in evs if e["name"] == "comm.send" and e["ph"] == "B")
        assert b["args"]["round"] == 4 and b["args"]["msg_type"] == 3
        flow = b["args"]["flow"]
        s = next(e for e in evs if e["ph"] == "s")
        assert s["id"] == flow
        assert any(e["name"] == "comm.send" and e["ph"] == "E" for e in evs)

    def test_receive_completes_the_flow(self):
        tel = Telemetry.get_instance()
        rec = _FakeTransport()
        inst = InstrumentedCommunicationManager(rec, tel, rank=1)
        got = []

        class _Obs(Observer):
            def receive_message(self, t, m):
                got.append(t)

        inst.add_observer(_Obs())
        m = _msg(round_idx=2)
        inst.send_message(m)
        rec.deliver(m)  # loopback the stamped message
        assert got == [3]
        evs = tel.recorder.tail()
        s = next(e for e in evs if e["ph"] == "s")
        f = next(e for e in evs if e["ph"] == "f")
        assert s["id"] == f["id"] and f["bp"] == "e"
        rb = next(e for e in evs if e["name"] == "comm.recv" and e["ph"] == "B")
        assert rb["args"]["flow"] == s["id"]
        assert rb["args"]["round"] == 2

    def test_retransmit_managers_wrap_order(self, args_factory):
        """drop-then-retransmit through the managers' wrap order
        (reliable OUTERMOST over faults over instrumented): the
        injected drop eats the send BEFORE the wire layer (wire
        semantics: a dropped message never left, so no send span), the
        channel's retransmit re-traverses the stack under a comm.retry
        span and lands as one clean flow-carrying wire send."""
        tel = Telemetry.get_instance(args_factory())
        wire = _FakeTransport()
        inst = InstrumentedCommunicationManager(wire, tel, rank=1)
        faulty = FaultInjector(inst, drop_prob=1.0, max_faults=1)
        ch = ReliableChannel(faulty, rank=1, retry_max=3, retry_base_s=0.02)
        ch.send_message(_msg(round_idx=0))
        deadline = time.time() + 5
        while time.time() < deadline and not wire.sent:
            time.sleep(0.01)
        assert len(wire.sent) == 1  # drop, then the retransmit landed
        evs = tel.recorder.tail()
        sends = [
            e for e in evs if e["name"] == "comm.send" and e["ph"] == "B"
        ]
        assert len(sends) == 1  # the dropped attempt never hit the wire
        assert "flow" in sends[0]["args"]
        retry = [e for e in evs if e["name"] == "comm.retry"]
        assert {e["ph"] for e in retry} == {"B", "E"}
        rb = next(e for e in retry if e["ph"] == "B")
        assert rb["args"]["attempt"] == 1
        ch.stop_receive_message()

    def test_resend_through_instrument_keeps_flow_and_tags_retry(
        self, args_factory
    ):
        """When the SAME message re-enters the instrumented layer (an
        injected duplicate with the injector inside, or a retransmit in
        the instrument-outermost wrap order), the original flow id is
        kept and the second send span is tagged retry — whichever copy
        arrives first completes the one flow."""
        tel = Telemetry.get_instance(args_factory())
        wire = _FakeTransport()
        com = InstrumentedCommunicationManager(
            FaultInjector(wire, duplicate_prob=1.0, max_faults=1), tel, rank=1
        )
        # injector INNER: wrap instrument over it so both wire copies
        # traverse the instrumented layer... but a duplicate fires
        # inside the injector, below the instrument. Send twice
        # explicitly instead: the reliable channel's retransmit path in
        # the instrument-outer order does exactly this.
        m = _msg(round_idx=1)
        com.send_message(m)
        com.send_message(m)  # re-send of the already-stamped envelope
        evs = tel.recorder.tail()
        sends = [
            e for e in evs if e["name"] == "comm.send" and e["ph"] == "B"
        ]
        assert len(sends) == 2
        assert sends[0]["args"]["flow"] == sends[1]["args"]["flow"]
        assert "retry" not in sends[0]["args"]
        assert sends[1]["args"]["retry"] is True
        assert sends[0]["args"]["nbytes"] == sends[1]["args"]["nbytes"]

    def test_continued_context_surfaces_parent_on_send_span(self):
        """The upload's send span carries its causal parent (the
        broadcast's flow id) — the stamped TRACE_SPAN param is readable
        in the merged trace, not write-only wire metadata."""
        tel = Telemetry.get_instance()
        inst0 = InstrumentedCommunicationManager(_FakeTransport(), tel, rank=0)
        inbound = _msg(t=2, sender=0, receiver=1)
        inst0.send_message(inbound)  # stamps the broadcast
        parent = inbound.get(constants.MSG_ARG_KEY_TRACE_FLOW)
        out = _msg(t=3, sender=1, receiver=0)
        continue_context(inbound, out)
        inst1 = InstrumentedCommunicationManager(_FakeTransport(), tel, rank=1)
        inst1.send_message(out)
        b = [
            e for e in tel.recorder.tail()
            if e["name"] == "comm.send" and e["ph"] == "B"
        ][-1]
        assert b["args"]["parent"] == parent
        assert b["args"]["flow"] != parent

    def test_flow_events_export_schema(self, tmp_path):
        rec = FlightRecorder()
        rec.begin("comm.send", cat="comm")
        rec.flow_start(7, msg_type=3)
        rec.end("comm.send", cat="comm")
        rec.begin("comm.recv", cat="comm")
        rec.flow_end(7, msg_type=3)
        rec.end("comm.recv", cat="comm")
        path = rec.export(str(tmp_path / "trace.json"))
        payload = json.load(open(path))
        evs = _check_trace_schema(payload)
        assert flow_match_stats(evs)["matched"] == 1
        assert payload["otherData"]["wall_t0_us"] > 0


class TestRingOverflow:
    def test_ring_capacity_knob_and_drop_counter(self, tmp_path, args_factory):
        args = args_factory(trace_ring_size=8)
        tel = Telemetry.get_instance(args)
        assert tel.recorder.capacity == 8
        for i in range(20):
            tel.recorder.instant(f"e{i}")
        assert len(tel.recorder) == 8
        assert tel.recorder.dropped == 12
        # counted in the registry...
        snap = tel.snapshot()
        assert snap["counters"]["telemetry_trace_dropped_total"] == 12
        assert "telemetry_trace_dropped_total" in tel.prometheus_text()
        # ...and recorded in the exported trace's meta
        path = tel.recorder.export(str(tmp_path / "t.json"))
        assert json.load(open(path))["otherData"]["events_dropped"] == 12

    def test_ring_size_validated(self, args_factory):
        with pytest.raises(ValueError, match="trace_ring_size"):
            args_factory(trace_ring_size=0)

    def test_resize_preserves_buffered_events(self):
        rec = FlightRecorder(capacity=4)
        for i in range(3):
            rec.instant(f"e{i}")
        rec.resize(16)
        assert rec.capacity == 16 and len(rec) == 3

    def test_shrink_counts_evictions_as_dropped(self):
        rec = FlightRecorder(capacity=16)
        for i in range(10):
            rec.instant(f"e{i}")
        rec.resize(4)
        assert len(rec) == 4
        assert rec.dropped == 6  # a silent shrink would report 0


class _Bridge(BaseCommunicationManager):
    """Synchronous two-endpoint wire: send delivers straight into the
    peer's observers (so send/receive timestamps land on the two fake
    'processes' deterministically)."""

    def __init__(self):
        self.peer = None
        self.observers = []

    def send_message(self, msg):
        for o in list(self.peer.observers):
            o.receive_message(msg.get_type(), msg)

    def add_observer(self, o):
        self.observers.append(o)

    def remove_observer(self, o):
        self.observers.remove(o)

    def handle_receive_message(self):
        pass

    def stop_receive_message(self):
        pass


class _Null(Observer):
    def receive_message(self, t, m):
        pass


class TestStitchAndSkew:
    SKEW_S = 0.5

    def _two_rank_shards(self, tmp_path, skew_s=SKEW_S):
        """Two standalone Telemetry 'processes' exchanging messages
        both ways, rank 1's wall clock skewed ahead by ``skew_s``."""
        tel0, tel1 = Telemetry(), Telemetry()
        tel1.rank = 1
        a, b = _Bridge(), _Bridge()
        i0 = InstrumentedCommunicationManager(a, tel0, rank=0)
        i1 = InstrumentedCommunicationManager(b, tel1, rank=1)
        a.peer, b.peer = b, a
        i0.add_observer(_Null())
        i1.add_observer(_Null())
        for r in range(3):
            i0.send_message(_msg(t=2, sender=0, receiver=1, round_idx=r))
            i1.send_message(_msg(t=3, sender=1, receiver=0, round_idx=r))
        tel1.recorder.wall_t0 += skew_s  # rank 1's clock runs ahead
        tel0.recorder.export(str(tmp_path / "trace.json"), meta={"rank": 0})
        tel1.recorder.export(
            str(tmp_path / "trace_rank1.json"), meta={"rank": 1}
        )
        return str(tmp_path)

    def test_skew_recovered_from_flow_pairs(self, tmp_path):
        tdir = self._two_rank_shards(tmp_path)
        merged = stitch_shards(tdir)
        est = merged["otherData"]["skew_us"]["1"]
        assert abs(est - self.SKEW_S * 1e6) < 0.02e6, est

    def test_matched_flows_and_causality_after_correction(self, tmp_path):
        tdir = self._two_rank_shards(tmp_path)
        merged = stitch_shards(tdir)
        evs = merged["traceEvents"]
        stats = flow_match_stats(evs)
        assert stats["flow_starts"] == 6
        assert stats["matched"] == 6 and stats["unmatched_starts"] == 0
        starts = {e["id"]: e["ts"] for e in evs if e.get("ph") == "s"}
        ends = {e["id"]: e["ts"] for e in evs if e.get("ph") == "f"}
        for fid, s_ts in starts.items():
            # a receive may not precede its send once skew-corrected
            # (tolerance: the estimator's half-min-RTT residual)
            assert ends[fid] >= s_ts - 2e3, (fid, s_ts, ends[fid])

    def test_per_track_timestamps_monotonic_after_correction(self, tmp_path):
        tdir = self._two_rank_shards(tmp_path)
        merged = stitch_shards(tdir)
        by_track = {}
        for ev in merged["traceEvents"]:
            if ev.get("ph") == "M":
                continue
            by_track.setdefault((ev["pid"], ev["tid"]), []).append(ev["ts"])
        assert len(by_track) >= 2  # two process tracks survived the merge
        for track, ts in by_track.items():
            assert ts == sorted(ts), f"track {track} not monotonic"

    def test_merged_trace_has_named_process_tracks(self, tmp_path):
        tdir = self._two_rank_shards(tmp_path)
        merged = stitch_shards(tdir)
        names = {
            e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert names == {"rank0 (server)", "rank1"}

    def test_stitch_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            stitch_shards(str(tmp_path))


def _run_cross_silo_world(args_factory, tmp_path, **overrides):
    """Two-client LOCAL cross-silo world (threads); returns (server,
    final params as numpy)."""
    from fedml_tpu import models
    from fedml_tpu.cross_silo.horizontal.fedml_aggregator import FedMLAggregator
    from fedml_tpu.cross_silo.horizontal.fedml_client_manager import (
        FedMLClientManager,
        FedMLTrainer,
    )
    from fedml_tpu.cross_silo.horizontal.fedml_server_manager import (
        FedMLServerManager,
    )
    from fedml_tpu.data import load

    import jax

    args = args_factory(
        training_type="cross_silo",
        backend="LOCAL",
        dataset="mnist",
        synthetic_train_size=200,
        synthetic_test_size=40,
        model="lr",
        client_num_in_total=2,
        client_num_per_round=2,
        comm_round=2,
        epochs=1,
        batch_size=25,
        learning_rate=0.1,
        shuffle=False,
        frequency_of_the_test=2,
        **overrides,
    )
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    agg = FedMLAggregator(args, model, test_data=dataset.test_data_global)
    server = FedMLServerManager(args, agg, rank=0, size=3)
    clients = [
        FedMLClientManager(
            args, FedMLTrainer(args, dataset, model), rank=r, size=3
        )
        for r in (1, 2)
    ]
    threads = [
        threading.Thread(target=m.run, daemon=True)
        for m in [server] + clients
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads), "world hung"
    params = jax.tree.map(
        np.asarray, agg.get_global_model_params()
    )
    return server, params


class TestCrossSiloWorldTracing:
    def test_world_traces_stitch_and_aggregation_identical_on_off(
        self, tmp_path, args_factory
    ):
        """The satellite contract in one world pair: tracing on yields
        matched flows, a full round_report with >=95% critical-path
        coverage and live SLO/segment series — and the aggregation
        result is bit-identical to the telemetry-off run."""
        Telemetry.reset()
        _, params_off = _run_cross_silo_world(
            args_factory, tmp_path, run_id="trc_world_off", telemetry=False
        )
        Telemetry.reset()
        tdir = str(tmp_path / "tel")
        _, params_on = _run_cross_silo_world(
            args_factory,
            tmp_path,
            run_id="trc_world_on",
            telemetry_dir=tdir,
            round_deadline_s=1e-4,  # every round violates: SLO fires
        )
        # identical aggregation with tracing on vs off
        import jax

        diffs = jax.tree.leaves(
            jax.tree.map(
                lambda x, y: float(np.max(np.abs(x - y))),
                params_on,
                params_off,
            )
        )
        assert max(diffs) == 0.0
        # live series landed
        tel = Telemetry.get_instance()
        assert tel.get_counter("slo_violations_total") == 2
        hists = tel.snapshot()["histograms"]
        assert "round_segment_seconds{segment=aggregate}" in hists
        assert "round_segment_seconds{segment=client_compute}" in hists
        assert "round_straggler_slack_s" in hists
        # stitched + analyzed offline
        out = trace_run(tdir)
        assert out["flows"]["unmatched_starts"] == 0
        assert out["flows"]["flow_starts"] > 0
        report = json.load(open(out["round_report"]))
        assert [r["round"] for r in report["rounds"]] == [0, 1]
        for r in report["rounds"]:
            assert r["coverage"] >= 0.95, r
            assert r["straggler_rank"] in (1, 2)
            assert set(r["slack_s"]) == {"1", "2"}
            assert min(r["slack_s"].values()) == 0.0
            total = sum(r["segments_s"].values())
            assert abs(total - r["wall_s"]) <= 0.05 * r["wall_s"] + 1e-6
        payload = json.load(open(out["merged_trace"]))
        _check_trace_schema(payload)

    def test_cli_trace_subcommand(self, tmp_path, args_factory, capsys):
        from fedml_tpu.cli import main as cli_main

        Telemetry.reset()
        tdir = str(tmp_path / "tel")
        _run_cross_silo_world(
            args_factory, tmp_path, run_id="trc_cli", telemetry_dir=tdir
        )
        rc = cli_main(["trace", "--telemetry-dir", tdir, "--summary"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["rounds_analyzed"] == 2
        assert os.path.exists(os.path.join(tdir, "trace_merged.json"))
        assert os.path.exists(os.path.join(tdir, "round_report.json"))

    def test_cli_trace_missing_dir_fails_loudly(self, tmp_path):
        from fedml_tpu.cli import main as cli_main

        assert cli_main(["trace", "--telemetry-dir", str(tmp_path)]) == 2


class TestAnalyzerUnits:
    def _span(self, name, ts, dur, pid=1, tid=1, **args):
        return [
            {"name": name, "ph": "B", "ts": ts, "pid": pid, "tid": tid,
             "cat": "x", "args": args},
            {"name": name, "ph": "E", "ts": ts + dur, "pid": pid, "tid": tid,
             "cat": "x"},
        ]

    def test_synthetic_round_attribution(self):
        """Hand-built timeline: 1 server (pid 1) + 2 clients (pids 2/3),
        client 3 the straggler; segments must reconstruct the walk."""
        evs = []
        # broadcasts at t=0 (rank1) and t=100 (rank2)
        evs += self._span("comm.send", 0, 50, pid=1, msg_type=2, round=0,
                          sender=0, receiver=1, flow=11)
        evs += self._span("comm.send", 100, 50, pid=1, msg_type=2, round=0,
                          sender=0, receiver=2, flow=12)
        # receipts
        evs += self._span("comm.recv", 300, 5000, pid=2, msg_type=2,
                          round=0, sender=0, flow=11)
        evs += self._span("comm.recv", 400, 9000, pid=3, msg_type=2,
                          round=0, sender=0, flow=12)
        # train spans
        evs += self._span("train", 350, 4000, pid=2, round=0, rank=1)
        evs += self._span("train", 500, 8000, pid=3, round=0, rank=2)
        # uploads
        evs += self._span("comm.send", 4400, 100, pid=2, msg_type=3,
                          round=0, sender=1, receiver=0, flow=21)
        evs += self._span("comm.send", 8600, 100, pid=3, msg_type=3,
                          round=0, sender=2, receiver=0, flow=22)
        # server receipts; straggler (rank 2, pid 3) lands last at 9000
        evs += self._span("comm.recv", 4600, 100, pid=1, msg_type=3,
                          round=0, sender=1, flow=21)
        evs += self._span("comm.recv", 9000, 2000, pid=1, msg_type=3,
                          round=0, sender=2, flow=22)
        evs += self._span("aggregate", 9500, 1000, pid=1, round=0)
        rounds = analyze_rounds(evs)
        assert len(rounds) == 1
        r = rounds[0]
        assert r["straggler_rank"] == 2
        seg = {k: v * 1e6 for k, v in r["segments_s"].items()}
        assert seg["broadcast_send"] == pytest.approx(100)
        assert seg["broadcast_wire"] == pytest.approx(300)
        assert seg["client_dispatch"] == pytest.approx(100)
        assert seg["client_compute"] == pytest.approx(8000)
        assert seg["client_encode"] == pytest.approx(100)
        assert seg["upload_wire"] == pytest.approx(400)
        assert seg["server_decode"] == pytest.approx(500)
        assert seg["aggregate"] == pytest.approx(1000)
        assert r["wall_s"] * 1e6 == pytest.approx(10500)
        assert sum(seg.values()) == pytest.approx(r["wall_s"] * 1e6)
        assert r["coverage"] == pytest.approx(1.0)
        # slack: rank 1's upload arrived 4400us before the straggler's
        assert r["slack_s"]["1"] * 1e6 == pytest.approx(4400)
        assert r["slack_s"]["2"] == 0.0

    def test_incomplete_round_skipped(self):
        evs = self._span("comm.send", 0, 10, pid=1, msg_type=2, round=0,
                         sender=0, receiver=1, flow=1)
        assert analyze_rounds(evs) == []

    def test_duplicate_and_retry_spans_first_wins(self):
        """A duplicated delivery re-emits comm.recv with the same flow
        id and a retransmit re-emits comm.send — the analyzer must keep
        the FIRST of each, or a late duplicate of a fast client's
        upload would flip the straggler and inflate its wire time."""
        evs = []
        evs += self._span("comm.send", 0, 10, pid=1, msg_type=2, round=0,
                          sender=0, receiver=1, flow=11)
        evs += self._span("comm.send", 0, 10, pid=1, msg_type=2, round=0,
                          sender=0, receiver=2, flow=12)
        evs += self._span("comm.recv", 100, 1000, pid=2, msg_type=2,
                          round=0, sender=0, flow=11)
        evs += self._span("comm.recv", 100, 1000, pid=3, msg_type=2,
                          round=0, sender=0, flow=12)
        evs += self._span("train", 150, 800, pid=2, round=0, rank=1)
        evs += self._span("train", 150, 1800, pid=3, round=0, rank=2)
        evs += self._span("comm.send", 1000, 10, pid=2, msg_type=3,
                          round=0, sender=1, receiver=0, flow=21)
        evs += self._span("comm.send", 2000, 10, pid=3, msg_type=3,
                          round=0, sender=2, receiver=0, flow=22)
        evs += self._span("comm.recv", 1100, 10, pid=1, msg_type=3,
                          round=0, sender=1, flow=21)
        evs += self._span("comm.recv", 2100, 500, pid=1, msg_type=3,
                          round=0, sender=2, flow=22)
        evs += self._span("aggregate", 2300, 100, pid=1, round=0)
        # the corruption: a RETRANSMIT of rank 1's upload send and a
        # late DUPLICATE delivery of it, both after the round closed
        evs += self._span("comm.send", 5000, 10, pid=2, msg_type=3,
                          round=0, sender=1, receiver=0, flow=21, retry=True)
        evs += self._span("comm.recv", 6000, 10, pid=1, msg_type=3,
                          round=0, sender=1, flow=21)
        rounds = analyze_rounds(evs)
        assert len(rounds) == 1
        r = rounds[0]
        assert r["straggler_rank"] == 2  # NOT flipped by the duplicate
        assert r["slack_s"]["1"] * 1e6 == pytest.approx(1000)  # 2100-1100
        assert r["segments_s"]["upload_wire"] * 1e6 == pytest.approx(100)


class TestMetricsServer:
    def test_binds_loopback_by_default(self):
        srv = MetricsServer(Telemetry.get_instance(), 0)
        try:
            # an unauthenticated endpoint must never default to 0.0.0.0
            assert srv._httpd.server_address[0] == "127.0.0.1"
        finally:
            srv._httpd.server_close()

    def test_serves_prometheus_text(self, args_factory):
        tel = Telemetry.get_instance(args_factory(run_id="scrape"))
        tel.inc("comm_messages_sent_total", 3, msg_type=3)
        srv = MetricsServer(tel, 0).start()  # port 0: ephemeral
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
            assert "comm_messages_sent_total" in body
            assert 'run_id="scrape"' in body
            with pytest.raises(Exception):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5
                )
        finally:
            srv.stop()
        assert not srv.alive()

    def test_maybe_start_off_by_default(self, args_factory):
        args = args_factory()  # metrics_port defaults to 0
        tel = Telemetry.get_instance(args)
        assert tel.maybe_start_metrics_server(args) is None

    def test_maybe_start_idempotent_and_reset_stops(self, args_factory):
        import socket

        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        args = args_factory(metrics_port=port)
        tel = Telemetry.get_instance(args)
        srv = tel.maybe_start_metrics_server(args)
        assert srv is not None and srv.alive()
        assert tel.maybe_start_metrics_server(args) is srv
        Telemetry.reset()
        assert not srv.alive()

    def test_port_validated(self, args_factory):
        with pytest.raises(ValueError, match="metrics_port"):
            args_factory(metrics_port=70000)


class TestRoundProfiler:
    def test_capture_listed_round(self, tmp_path, args_factory):
        args = args_factory(
            profile_rounds="1", telemetry_dir=str(tmp_path)
        )
        prof = RoundProfiler(args)
        assert prof.enabled
        prof.tick(0)
        assert prof._active is None
        prof.tick(1)
        assert prof._active == 1
        import jax.numpy as jnp

        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        prof.tick(2)  # stops the round-1 capture
        prof.close()
        pdir = tmp_path / "profile" / "round_0001"
        assert pdir.is_dir() and any(pdir.rglob("*")), "no capture written"

    def test_unsupported_backend_warns_once_and_disables(
        self, args_factory, tmp_path, monkeypatch, caplog
    ):
        import logging

        import jax.profiler

        def boom(path):
            raise RuntimeError("no profiler on this backend")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        args = args_factory(
            profile_rounds=[0, 1], telemetry_dir=str(tmp_path)
        )
        prof = RoundProfiler(args)
        with caplog.at_level(logging.WARNING):
            prof.tick(0)
            prof.tick(1)
            prof.close()
        hits = [
            r for r in caplog.records if "device profiling unsupported" in r.message
        ]
        assert len(hits) == 1
        assert not prof.enabled

    def test_requires_telemetry_dir(self, args_factory, caplog):
        import logging

        with caplog.at_level(logging.WARNING):
            prof = RoundProfiler(args_factory(profile_rounds=[2]))
        assert not prof.enabled
        assert any("telemetry_dir is unset" in r.message for r in caplog.records)

    def test_list_and_string_forms(self, args_factory, tmp_path):
        td = str(tmp_path)
        assert RoundProfiler(
            args_factory(profile_rounds="1, 3", telemetry_dir=td)
        ).rounds == {1, 3}
        assert RoundProfiler(
            args_factory(profile_rounds=[2, 5], telemetry_dir=td)
        ).rounds == {2, 5}
        assert not RoundProfiler(args_factory()).enabled

    def test_bad_knob_rejected(self, args_factory):
        with pytest.raises(ValueError, match="profile_rounds"):
            args_factory(profile_rounds=3.5)

    def test_round_deadline_validated(self, args_factory):
        with pytest.raises(ValueError, match="round_deadline_s"):
            args_factory(round_deadline_s=-1)
