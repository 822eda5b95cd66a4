"""Deterministic chaos plane (docs/robustness.md chaos-schedule DSL):
scheduled multi-layer fault injection — exact-message comm faults
through the FaultInjector plan seam, WAL/checkpoint IO faults through
the DurableIO seam, process kills at named barriers, clock skew — plus
the crash-point enumeration and one world per kind of crash point.
"""

import os
import stat
import threading
import time

import numpy as np
import pytest

from fedml_tpu import constants
from fedml_tpu.core import chaos
from fedml_tpu.core import checkpoint as ckpt_mod
from fedml_tpu.core.chaos import (
    ChaosError,
    ChaosSchedule,
    FaultyIO,
    ProcessKilled,
    RecordingIO,
    chaos_barrier,
    comm_plan,
    crash_point_schedule,
    enumerate_crash_points,
    elastic_event,
    install_chaos,
    maybe_install_chaos,
    reset_chaos,
    validate_schedule,
)
from fedml_tpu.core.checkpoint import DurableIO, RoundWAL
from fedml_tpu.core.comm.base import BaseCommunicationManager, Observer
from fedml_tpu.core.comm.faults import FaultInjector
from fedml_tpu.core.message import Message
from fedml_tpu.core.telemetry import Telemetry

pytestmark = pytest.mark.smoke


class _RecordingTransport(BaseCommunicationManager):
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


def _msg(t=3, sender=1, receiver=0, round_idx=None):
    m = Message(t, sender, receiver)
    if round_idx is not None:
        m.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, round_idx)
    return m


class TestScheduleValidation:
    def test_normalizes_and_defaults(self):
        steps = validate_schedule(
            [{"at": {"event": "wal_append"}, "fault": "kill_server"}]
        )
        assert steps[0]["at"]["occurrence"] == 1
        assert steps[0]["fault"]["kind"] == "kill_server"

    @pytest.mark.parametrize(
        "bad",
        [
            [{"at": {"event": "nope"}, "fault": "drop"}],
            [{"at": {"event": "send"}, "fault": "frobnicate"}],
            [{"at": {"event": "send"}, "fault": "kill_server"}],  # wrong layer
            [{"at": {"event": "wal_append"}, "fault": "drop"}],  # wrong layer
            # inert (kind, event) pairs: would fire (count + trace) but
            # apply NOTHING — phantom faults are rejected outright
            [{"at": {"event": "ckpt_publish"}, "fault": "torn_write"}],
            [{"at": {"event": "wal_append"}, "fault": "torn_publish"}],
            [{"at": {"event": "wal_create"}, "fault": "fsync_fail"}],
            [{"at": {"event": "wal_create"}, "fault": "torn_write"}],
            # matchers the event's adapter never supplies in ctx: the
            # step would validate but silently never fire
            [{"at": {"event": "ckpt_publish", "rank": 0},
              "fault": "torn_publish"}],
            [{"at": {"event": "send", "name": "server.broadcast"},
              "fault": "drop"}],
            [{"at": {"event": "wal_create", "round": 1},
              "fault": "kill_server"}],
            [{"at": {"event": "wal_append", "msg_type": 3},
              "fault": "fsync_fail"}],
            [{"at": {"event": "send", "occurrence": 0}, "fault": "drop"}],
            [{"at": {"event": "send", "bogus": 1}, "fault": "drop"}],
            [{"fault": "drop"}],
            [{"at": {"event": "wal_append"},
              "fault": {"kind": "kill_server", "when": "during"}}],
            "not a list",
        ],
    )
    def test_rejects_malformed_steps(self, bad):
        with pytest.raises(ValueError):
            validate_schedule(bad)

    def test_knob_validation_names_the_knob(self, args_factory):
        with pytest.raises(ValueError, match="chaos_schedule"):
            args_factory(chaos_schedule=[{"at": {"event": "x"}, "fault": "drop"}])
        with pytest.raises(ValueError, match="io_faults"):
            # io_faults takes IO events only, not comm steps
            args_factory(io_faults=[{"at": {"event": "send"}, "fault": "drop"}])
        with pytest.raises(ValueError, match="chaos_seed"):
            args_factory(chaos_seed="not-a-number")

    def test_valid_knobs_accepted(self, args_factory):
        a = args_factory(
            chaos_schedule=[
                {"at": {"event": "send", "msg_type": 3, "rank": 1,
                        "occurrence": 2}, "fault": "drop"},
            ],
            io_faults=[
                {"at": {"event": "ckpt_publish"}, "fault": "torn_publish"},
            ],
            chaos_seed=7,
        )
        assert a.chaos_seed == 7


class TestElasticCheckEvent:
    """The elastic plane's chaos hook (``elastic.check``): preempt /
    device.loss faults ride the deterministic schedule machinery, and
    ONLY that event's adapter can apply them — everywhere else the
    pair is inert and validation rejects it outright."""

    def test_preempt_and_device_loss_validate_on_elastic_check(self):
        steps = validate_schedule([
            {"at": {"event": "elastic.check", "round": 2},
             "fault": "preempt"},
            {"at": {"event": "elastic.check"}, "fault": "device.loss"},
        ])
        assert steps[0]["fault"]["kind"] == "preempt"
        assert steps[1]["fault"]["kind"] == "device.loss"

    @pytest.mark.parametrize(
        "bad",
        [
            # preempt/device.loss anywhere else would fire-and-apply
            # nothing (a phantom fault) — rejected outright
            [{"at": {"event": "barrier"}, "fault": "preempt"}],
            [{"at": {"event": "send"}, "fault": "preempt"}],
            [{"at": {"event": "wal_append"}, "fault": "device.loss"}],
            [{"at": {"event": "ckpt_publish"}, "fault": "device.loss"}],
            # elastic.check applies no other layer's kinds either
            [{"at": {"event": "elastic.check"}, "fault": "drop"}],
            [{"at": {"event": "elastic.check"}, "fault": "kill_server"}],
            # and the only matcher its adapter supplies is `round`
            [{"at": {"event": "elastic.check", "rank": 0},
              "fault": "preempt"}],
        ],
    )
    def test_inert_pairs_rejected(self, bad):
        with pytest.raises(ValueError):
            validate_schedule(bad)

    def test_elastic_event_adapter_fires_on_round_match(self):
        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "elastic.check", "round": 2},
             "fault": "device.loss"},
        ]))
        try:
            assert elastic_event(0) is None
            assert elastic_event(1) is None
            fault = elastic_event(2)
            assert fault is not None and fault["kind"] == "device.loss"
            assert elastic_event(2) is None  # one-shot
        finally:
            reset_chaos()

    def test_elastic_event_noop_without_schedule(self):
        reset_chaos()
        assert elastic_event(0) is None


class TestScheduleFiring:
    def test_occurrence_counting_and_one_shot(self):
        s = ChaosSchedule([
            {"at": {"event": "send", "msg_type": 3, "occurrence": 2},
             "fault": "drop"},
        ])
        assert s.on_event("send", msg_type=4) == []  # no match, no count
        assert s.on_event("send", msg_type=3) == []  # occurrence 1
        hits = s.on_event("send", msg_type=3)  # occurrence 2: fires
        assert hits[0]["kind"] == "drop"
        assert s.on_event("send", msg_type=3) == []  # one-shot
        assert s.pending() == 0
        assert len(s.fired) == 1

    def test_matchers_must_all_agree(self):
        s = ChaosSchedule([
            {"at": {"event": "barrier", "name": "client.train", "rank": 2},
             "fault": "kill_client"},
        ])
        assert s.on_event("barrier", name="client.train", rank=1) == []
        assert s.on_event("barrier", name="server.publish", rank=2) == []
        # a matcher against MISSING ctx never fires (rank unknown)
        assert s.on_event("barrier", name="client.train") == []
        assert s.on_event("barrier", name="client.train", rank=2) != []

    @pytest.mark.parametrize(
        "spec, events",
        [
            (
                [
                    {"at": {"event": "send", "msg_type": 3, "occurrence": 2},
                     "fault": "drop"},
                    {"at": {"event": "wal_append", "occurrence": 1},
                     "fault": "fsync_fail"},
                ],
                [
                    ("send", {"msg_type": 3}),
                    ("wal_append", {"round": 0}),
                    ("send", {"msg_type": 3}),
                    ("send", {"msg_type": 3}),
                ],
            ),
            # one step a seam, as a three-client world would raise them:
            # the injector's plan, the durable-IO seam, a barrier
            (
                [
                    {"at": {"event": "send", "msg_type": 3, "rank": 1,
                            "occurrence": 1}, "fault": "drop"},
                    {"at": {"event": "send", "msg_type": 3, "rank": 2,
                            "occurrence": 2}, "fault": "duplicate"},
                    {"at": {"event": "send", "msg_type": 3, "rank": 3,
                            "occurrence": 1},
                     "fault": {"kind": "delay", "delay_s": 0.2}},
                    {"at": {"event": "wal_append", "occurrence": 1},
                     "fault": {"kind": "latency", "delay_s": 0.05}},
                    {"at": {"event": "wal_append", "occurrence": 2},
                     "fault": "fsync_fail"},
                    {"at": {"event": "barrier", "name": "server.round_close",
                            "occurrence": 2},
                     "fault": {"kind": "clock_skew", "skew_s": 0.5}},
                ],
                [
                    (ev, ctx)
                    for rnd in range(2)
                    for ev, ctx in [
                        ("send", {"msg_type": 3, "rank": 1}),
                        ("send", {"msg_type": 3, "rank": 2}),
                        ("send", {"msg_type": 3, "rank": 3}),
                        ("barrier", {"name": "server.round_close",
                                     "round": rnd}),
                        ("wal_append", {"round": rnd}),
                    ]
                ],
            ),
        ],
        ids=["two_steps", "one_step_a_seam"],
    )
    def test_identical_schedule_and_seed_fire_identically(self, spec, events):
        runs = []
        for _ in range(2):
            s = ChaosSchedule(spec, seed=5)
            for ev, ctx in events:
                s.on_event(ev, **ctx)
            assert s.pending() == 0
            runs.append([(f["step"], f["event"], f["fault"]) for f in s.fired])
        assert runs[0] == runs[1] and len(runs[0]) == len(spec)

    def test_one_firing_per_event_no_phantom_burn(self):
        # two steps reaching their occurrence on the SAME event: only
        # one fault can apply to a single message/boundary, so the
        # second must fire on the NEXT matching event — never burn as a
        # counted-but-unapplied phantom
        s = ChaosSchedule([
            {"at": {"event": "send", "msg_type": 3, "occurrence": 1},
             "fault": "drop"},
            {"at": {"event": "send", "occurrence": 1},
             "fault": {"kind": "delay", "delay_s": 0.5}},
        ])
        hits = s.on_event("send", msg_type=3)
        assert len(hits) == 1 and hits[0]["kind"] == "drop"
        assert s.pending() == 1  # the delay is still armed
        hits = s.on_event("send", msg_type=4)
        assert len(hits) == 1 and hits[0]["kind"] == "delay"
        assert s.pending() == 0

    def test_validation_does_not_mutate_the_caller_spec(self):
        fault = {"kind": "delay", "delay_s": "0.5"}
        spec = [{"at": {"event": "send"}, "fault": fault}]
        steps = validate_schedule(spec)
        assert steps[0]["fault"]["delay_s"] == 0.5  # normalized copy
        assert fault["delay_s"] == "0.5"  # caller's dict untouched

    def test_firing_is_counted_and_traced(self):
        Telemetry.reset()
        s = ChaosSchedule([
            {"at": {"event": "send"}, "fault": "drop"},
        ])
        s.on_event("send", msg_type=3)
        tel = Telemetry.get_instance()
        assert tel.get_counter(
            "chaos_faults_injected_total", fault="drop", event="send"
        ) == 1
        faults = [
            e for e in tel.recorder.tail(50) if e["name"] == "chaos.fault"
        ]
        assert len(faults) == 1 and faults[0]["args"]["fault"] == "drop"


class TestFaultInjectorPlan:
    def _injector(self, spec):
        reset_chaos()
        install_chaos(ChaosSchedule(spec))
        transport = _RecordingTransport()
        return FaultInjector(transport, plan=comm_plan(rank=1)), transport

    def test_exact_message_drop(self):
        Telemetry.reset()
        inj, transport = self._injector([
            {"at": {"event": "send", "msg_type": 3, "rank": 1,
                    "occurrence": 2}, "fault": "drop"},
        ])
        for _ in range(3):
            inj.send_message(_msg(3))
        # exactly the SECOND send dropped — not a probability
        assert len(transport.sent) == 2
        # counted by the SCHEDULE (chaos_faults_injected_total), never
        # by the probabilistic tally: injected feeds the max_faults
        # budget and comm_faults_injected_total, which existing worlds
        # assert against
        assert inj.injected["drop"] == 0
        tel = Telemetry.get_instance()
        assert tel.get_counter(
            "chaos_faults_injected_total", fault="drop", event="send"
        ) == 1

    def test_scheduled_faults_spare_the_probabilistic_budget(self):
        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "send", "occurrence": 1}, "fault": "drop"},
            {"at": {"event": "send", "occurrence": 2}, "fault": "drop"},
        ]))
        transport = _RecordingTransport()
        # drop_prob=1 with a budget of ONE probabilistic fault: the two
        # scheduled drops must not spend it
        inj = FaultInjector(
            transport, drop_prob=1.0, max_faults=1, plan=comm_plan(rank=1)
        )
        for _ in range(3):
            inj.send_message(_msg(3))
        # sends 1+2 scheduled drops, send 3 the probabilistic drop —
        # which still had its budget
        assert len(transport.sent) == 0
        assert inj.injected["drop"] == 1

    def test_exact_message_duplicate_and_delay(self):
        inj, transport = self._injector([
            {"at": {"event": "send", "msg_type": 3, "occurrence": 1},
             "fault": "duplicate"},
            {"at": {"event": "send", "msg_type": 3, "occurrence": 2},
             "fault": {"kind": "delay", "delay_s": 0.05}},
        ])
        inj.send_message(_msg(3))  # duplicated
        assert len(transport.sent) == 2
        inj.send_message(_msg(3))  # delayed
        assert len(transport.sent) == 2
        time.sleep(0.2)
        assert len(transport.sent) == 3

    def test_loopback_never_matches(self):
        inj, transport = self._injector([
            {"at": {"event": "send", "occurrence": 1}, "fault": "drop"},
        ])
        inj.send_message(_msg(3, sender=0, receiver=0))  # loopback
        assert len(transport.sent) == 1  # not dropped, not even counted
        inj.send_message(_msg(3))
        assert len(transport.sent) == 1  # the real link send was dropped

    def test_round_matcher_reads_the_message(self):
        inj, transport = self._injector([
            {"at": {"event": "send", "round": 2, "occurrence": 1},
             "fault": "drop"},
        ])
        inj.send_message(_msg(3, round_idx=1))
        inj.send_message(_msg(3, round_idx=2))
        inj.send_message(_msg(3, round_idx=2))
        assert len(transport.sent) == 2  # only round 2's first send died

    def test_retransmits_do_not_advance_occurrence(self):
        # the reliable channel stacks OUTSIDE the injector, so its
        # retransmits re-traverse the plan with the original (chan,
        # seq) id — they must be invisible to occurrence counting or
        # "the Nth message" becomes a function of ack/backoff races
        inj, transport = self._injector([
            {"at": {"event": "send", "msg_type": 3, "occurrence": 2},
             "fault": "drop"},
        ])

        def _wire_msg(seq):
            m = _msg(3)
            m.add_params(constants.MSG_ARG_KEY_COMM_SEQ, seq)
            m.add_params(constants.MSG_ARG_KEY_COMM_CHAN, 0)
            return m

        inj.send_message(_wire_msg(0))  # message 1
        inj.send_message(_wire_msg(0))  # its retransmit: NOT message 2
        inj.send_message(_wire_msg(0))
        assert len(transport.sent) == 3  # nothing dropped yet
        inj.send_message(_wire_msg(1))  # the real message 2: dropped
        assert len(transport.sent) == 3

    def test_no_send_steps_means_no_plan(self):
        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "wal_append"}, "fault": "kill_server"},
        ]))
        assert comm_plan(rank=0) is None


class TestFaultyIOWal:
    def _wal(self, tmp_path, spec):
        reset_chaos()
        install_chaos(ChaosSchedule(spec))
        return RoundWAL(str(tmp_path))

    def test_torn_write_kills_midway_and_next_incarnation_recovers(
        self, tmp_path
    ):
        wal = self._wal(tmp_path, [
            {"at": {"event": "wal_append", "occurrence": 2},
             "fault": {"kind": "torn_write", "at_byte": 7}},
        ])
        wal.append(0, 1, [1, 2], folded=[1, 2])
        with pytest.raises(ProcessKilled):
            wal.append(1, 2, [1, 2], folded=[1, 2])
        reset_chaos()
        # the torn tail holds exactly 7 bytes of record 1
        wal2 = RoundWAL(str(tmp_path))
        assert [r["round_idx"] for r in wal2.records()] == [0]
        wal2.append(1, 2, [1, 2], folded=[1])
        assert [r["round_idx"] for r in wal2.records()] == [0, 1]

    def test_enospc_is_an_oserror_and_writes_nothing(self, tmp_path):
        wal = self._wal(tmp_path, [
            {"at": {"event": "wal_append", "occurrence": 1},
             "fault": "enospc"},
        ])
        with pytest.raises(OSError) as ei:
            wal.append(0, None, [1])
        assert isinstance(ei.value, ChaosError)
        assert wal.records() == []  # nothing reached the log
        wal.append(0, None, [1])  # one-shot: next append succeeds
        assert len(wal.records()) == 1

    def test_fsync_fail_leaves_the_record_but_raises(self, tmp_path):
        wal = self._wal(tmp_path, [
            {"at": {"event": "wal_append", "occurrence": 1},
             "fault": "fsync_fail"},
        ])
        with pytest.raises(OSError):
            wal.append(0, None, [1], folded=[1])
        # the bytes were written (only the fsync was refused): the
        # record is readable — degraded durability, not data loss
        assert [r["round_idx"] for r in wal.records()] == [0]

    def test_kill_before_wal_create_leaves_no_file(self, tmp_path):
        wal = self._wal(tmp_path, [
            {"at": {"event": "wal_create"}, "fault": "kill_server"},
        ])
        with pytest.raises(ProcessKilled):
            wal.append(0, None, [1])
        assert not os.path.exists(wal.path)

    def test_kill_after_append_leaves_the_record(self, tmp_path):
        wal = self._wal(tmp_path, [
            {"at": {"event": "wal_append", "occurrence": 1},
             "fault": {"kind": "kill_server", "when": "after"}},
        ])
        with pytest.raises(ProcessKilled):
            wal.append(0, None, [1], folded=[1])
        assert len(RoundWAL(str(tmp_path)).records()) == 1


class TestWalCreateDirFsync:
    def test_first_append_fsyncs_the_parent_directory(
        self, tmp_path, monkeypatch
    ):
        """Satellite: file data was already fsynced, but the directory
        ENTRY of a just-created WAL is its own durable object — the
        first append must fsync the parent dir too."""
        synced = []
        real_fsync = os.fsync

        def spy(fd):
            synced.append(stat.S_ISDIR(os.fstat(fd).st_mode))
            return real_fsync(fd)

        monkeypatch.setattr(os, "fsync", spy)
        wal = RoundWAL(str(tmp_path))
        wal.append(0, None, [1])
        assert True in synced, "parent directory was never fsynced"
        assert False in synced, "file data was never fsynced"
        # later appends touch only the file, not the directory
        synced.clear()
        wal.append(1, None, [1])
        assert synced == [False]

    def test_recording_io_sees_create_once(self, tmp_path):
        rec = RecordingIO()
        ckpt_mod.install_io_seam(rec)
        try:
            wal = RoundWAL(str(tmp_path))
            wal.append(0, None, [1])
            wal.append(1, None, [1])
        finally:
            ckpt_mod.reset_io_seam()
        assert [e for e, _ in rec.events] == [
            "wal_create", "wal_append", "wal_append",
        ]


class TestBarriersAndClock:
    def test_barrier_noop_without_schedule(self):
        reset_chaos()
        chaos_barrier("server.round_close", round=0, rank=0)  # no raise

    def test_kill_at_named_barrier(self):
        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "barrier", "name": "server.round_close",
                    "round": 1}, "fault": "kill_server"},
        ]))
        chaos_barrier("server.round_close", round=0, rank=0)
        chaos_barrier("server.broadcast", round=1, rank=0)
        with pytest.raises(ProcessKilled):
            chaos_barrier("server.round_close", round=1, rank=0)

    def test_clock_skew_steps_the_wall_anchor_only(self):
        Telemetry.reset()
        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "barrier", "name": "b"},
             "fault": {"kind": "clock_skew", "skew_s": 2.5}},
        ]))
        rec = Telemetry.get_instance().recorder
        before = rec.wall_t0
        t0 = time.monotonic()
        chaos_barrier("b")
        assert rec.wall_t0 == pytest.approx(before + 2.5)
        # the monotonic clock (heartbeats, staleness) is untouched
        assert time.monotonic() - t0 < 1.0


class TestInstallFromArgs:
    def test_maybe_install_and_reuse(self, args_factory):
        reset_chaos()
        spec = [{"at": {"event": "wal_append"}, "fault": "kill_server"}]
        a = args_factory(chaos_schedule=spec)
        s1 = maybe_install_chaos(a)
        s2 = maybe_install_chaos(a)
        assert s1 is s2  # a LOCAL world's ranks share one schedule
        b = args_factory(io_faults=[
            {"at": {"event": "ckpt_publish"}, "fault": "torn_publish"},
        ])
        s3 = maybe_install_chaos(b)
        assert s3 is not s1  # a different spec replaces
        reset_chaos()
        assert chaos.active_chaos() is None

    def test_no_knobs_is_a_noop(self, args_factory):
        reset_chaos()
        assert maybe_install_chaos(args_factory()) is None


class TestCrashPointEnumeration:
    def test_enumerates_every_boundary(self):
        events = [
            ("wal_create", {}),
            ("wal_append", {"round": 0, "nbytes": 60}),
            ("ckpt_publish", {"step": 1}),
            ("wal_append", {"round": 1, "nbytes": 62}),
        ]
        points = enumerate_crash_points(events)
        by_mode = {}
        for p in points:
            by_mode.setdefault((p["event"], p["mode"]), 0)
            by_mode[(p["event"], p["mode"])] += 1
        assert by_mode[("wal_create", "before")] == 1
        assert by_mode[("wal_append", "before")] == 2
        assert by_mode[("wal_append", "torn")] == 2
        assert by_mode[("wal_append", "after")] == 2
        assert by_mode[("ckpt_publish", "before")] == 1
        assert by_mode[("ckpt_publish", "after")] == 1
        assert len(points) == 9

    def test_crash_point_schedule_shapes(self):
        kill = crash_point_schedule(
            {"event": "ckpt_publish", "occurrence": 2, "mode": "before"}
        )
        assert kill[0]["fault"] == {"kind": "kill_server", "when": "before"}
        torn = crash_point_schedule(
            {"event": "wal_append", "occurrence": 1, "mode": "torn",
             "nbytes": 60}
        )
        assert torn[0]["fault"] == {"kind": "torn_write", "at_byte": 30}
        # schedules built from points must validate
        validate_schedule(kill)
        validate_schedule(torn)


class TestCheckpointWatcherTornPublish:
    def _save(self, ckpt, step, scale):
        ckpt.save(step, {"params": {"w": np.full(4, scale, np.float32)},
                         "round_idx": step})

    def test_torn_publish_falls_back_and_never_retries(self, tmp_path):
        """Satellite: a PARTIAL (torn mid-write) checkpoint publish —
        injected through the IO seam, not hand-corrupted files — must
        degrade the watcher to the previous version, remember the bad
        step, and resume on the next good publish."""
        from fedml_tpu.core.checkpoint import CheckpointWatcher, RoundCheckpointer

        reset_chaos()
        install_chaos(ChaosSchedule([
            {"at": {"event": "ckpt_publish", "occurrence": 2},
             "fault": "torn_publish"},
        ]))
        ckpt = RoundCheckpointer(str(tmp_path))
        self._save(ckpt, 0, 1.0)
        self._save(ckpt, 1, 2.0)  # torn: listed on disk, content garbage
        watcher = CheckpointWatcher(str(tmp_path))
        step, state = watcher.poll()
        assert step == 0
        assert float(np.asarray(state["params"]["w"])[0]) == 1.0
        assert watcher.poll() is None  # bad step 1 is never retried
        self._save(ckpt, 2, 3.0)  # schedule is one-shot: clean publish
        step, state = watcher.poll()
        assert step == 2
        assert float(np.asarray(state["params"]["w"])[0]) == 3.0
        ckpt.close()
        watcher.close()


class TestReliableInternalErrors:
    def test_initial_send_failure_counted_per_site(self):
        """Satellite: the channel's absorbed transport errors are
        telemetry-counted per site (comm_internal_errors_total) so a
        chaos run cannot hide a channel bug behind injected faults."""
        from fedml_tpu.core.comm.reliable import ReliableChannel

        Telemetry.reset()

        class _Exploding(_RecordingTransport):
            def send_message(self, msg):
                raise RuntimeError("boom")

        ch = ReliableChannel(_Exploding(), rank=1, retry_max=1,
                             retry_base_s=0.02)
        ch.send_message(_msg(3))
        tel = Telemetry.get_instance()
        assert tel.get_counter(
            "comm_internal_errors_total", site="initial_send"
        ) == 1
        deadline = time.monotonic() + 3.0
        while (
            tel.get_counter("comm_internal_errors_total", site="retransmit")
            < 1 and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert tel.get_counter(
            "comm_internal_errors_total", site="retransmit"
        ) >= 1
        ch.stop_receive_message()

    def test_ack_send_failure_counted(self):
        from fedml_tpu.core.comm.reliable import ReliableChannel

        Telemetry.reset()

        class _AckExploding(_RecordingTransport):
            def send_message(self, msg):
                if int(msg.get_type()) == constants.MSG_TYPE_COMM_ACK:
                    raise RuntimeError("ack boom")
                super().send_message(msg)

        ch = ReliableChannel(_AckExploding(), rank=0)
        ch._send_ack(sender=1, chan=7, seq=1)
        tel = Telemetry.get_instance()
        deadline = time.monotonic() + 3.0
        while (
            tel.get_counter("comm_internal_errors_total", site="ack_send") < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert tel.get_counter(
            "comm_internal_errors_total", site="ack_send"
        ) == 1
        ch.stop_receive_message()


def _build_rank(args_factory, run_id, rank, **kw):
    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.data import load
    from test_cross_silo import _mk_args

    a = _mk_args(args_factory, run_id, "LOCAL", **kw)
    a.rank = rank
    a = fedml_tpu.init(a)
    ds = load(a)
    m = models.create(a, ds.class_num)
    return a, ds, m


def _build_world(args_factory, run_id, n_clients, **kw):
    """Server, clients and the server's dataset (a restart reuses it)."""
    from fedml_tpu.cross_silo import Client, Server

    a0, ds0, m0 = _build_rank(args_factory, run_id, 0, **kw)
    server = Server(a0, None, ds0, m0)
    clients = []
    for r in range(1, n_clients + 1):
        a, ds, m = _build_rank(args_factory, run_id, r, **kw)
        clients.append(Client(a, None, ds, m))
    return server, clients, ds0


def _start_clients(clients):
    def run(c):
        try:
            c.run()
        except ProcessKilled:  # lint: except-ok — a scheduled kill_client IS the test
            pass

    threads = [
        threading.Thread(target=run, args=(c,), daemon=True) for c in clients
    ]
    for t in threads:
        t.start()
    return threads


class TestScheduledCrashWorld:
    # one point of every kind the sweep enumerates (WAL append: record
    # lost, torn, durable; checkpoint publish: params lost, WAL behind)
    @pytest.mark.parametrize(
        "point",
        [
            {"event": "wal_append", "occurrence": 2, "mode": "before"},
            {"event": "wal_append", "occurrence": 2, "mode": "torn",
             "nbytes": 40},
            {"event": "wal_append", "occurrence": 2, "mode": "after"},
            {"event": "ckpt_publish", "occurrence": 2, "mode": "before"},
            {"event": "ckpt_publish", "occurrence": 2, "mode": "after"},
        ],
        ids=lambda p: f"{p['event']}-{p['mode']}",
    )
    def test_scheduled_server_kill_recovers_with_clean_invariants(
        self, args_factory, tmp_path, point
    ):
        """The crash-point sweep, one point a case: a schedule kills
        the server at an exact durable-write boundary; a restarted
        server resumes from checkpoint+WAL, the world completes, and
        the post-hoc InvariantChecker is clean on the artifacts."""
        from fedml_tpu.core.invariants import InvariantChecker
        from fedml_tpu.cross_silo import Server

        reset_chaos()
        Telemetry.reset()
        ck = str(tmp_path / "ck")
        td = str(tmp_path / "td")
        kw = dict(
            comm_round=3,
            checkpoint_dir=ck,
            checkpoint_freq=1,
            telemetry_dir=td,
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=60.0,
            client_num_in_total=2,
            client_num_per_round=2,
            chaos_schedule=crash_point_schedule(point),
        )
        run_id = "chaos_kill_world"
        server, clients, ds0 = _build_world(args_factory, run_id, 2, **kw)
        killed = {}

        def srv():
            try:
                server.run()
            except ProcessKilled as e:
                killed["where"] = e.where
                if server.manager._failure_detector is not None:
                    server.manager._failure_detector.stop()

        threads = _start_clients(clients)
        st = threading.Thread(target=srv, daemon=True)
        st.start()
        st.join(timeout=120)
        assert killed, "scheduled kill never fired"
        a0b, _, m0b = _build_rank(args_factory, run_id, 0, **kw)
        server2 = Server(a0b, None, ds0, m0b)
        server2.run()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert server2.manager.round_idx == 3
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()
        assert "chaos_trace_consistent" in report.checked
        assert "cohort_accounting" in report.checked


class TestScheduledWorlds:
    def test_async_defended_world_reaches_its_fold_target(
        self, args_factory, tmp_path
    ):
        """The two seams only an async world has, under the clipping
        defense: a client killed at its ``client.train`` barrier and a
        clock step at a ``server.publish`` barrier. The survivors reach
        the fold target and the checker proves exactly-once folds,
        monotone versions and no reissued sequence numbers from the
        artifacts."""
        from fedml_tpu.core.chaos import active_chaos
        from fedml_tpu.core.invariants import InvariantChecker

        reset_chaos()
        Telemetry.reset()
        ck, td = str(tmp_path / "ck"), str(tmp_path / "td")
        kw = dict(
            client_num_in_total=3,
            client_num_per_round=3,
            agg_mode="async",
            async_publish_every=2,
            staleness_decay=0.5,
            staleness_max=64,
            defense_type="norm_diff_clipping",
            norm_bound=1.0,
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=1.5,
            checkpoint_dir=ck,
            checkpoint_freq=1,
            telemetry_dir=td,
            chaos_schedule=[
                # every rank trains once: the kill cannot miss
                {"at": {"event": "barrier", "name": "client.train",
                        "rank": 2, "occurrence": 1},
                 "fault": "kill_client"},
                {"at": {"event": "barrier", "name": "server.publish",
                        "occurrence": 2},
                 "fault": {"kind": "clock_skew", "skew_s": 0.25}},
            ],
        )
        run_id = "chaos_async_defended"
        server, clients, ds0 = _build_world(args_factory, run_id, 3, **kw)
        threads = _start_clients(clients)
        server.run()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        mgr = server.manager
        assert mgr.async_folds >= mgr._async_target_folds()
        assert server.aggregator.defense_clipped > 0
        assert active_chaos().pending() == 0, "a scheduled step never fired"
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()
        for name in ("exactly_once_folds", "version_monotone",
                     "no_reissued_seqs", "no_lost_unreported_folds"):
            assert name in report.checked, report.to_dict()
        reset_chaos()


class TestAsyncRestartRace:
    """PR 10's pinned pre-existing race, reproduced deterministically
    with a chaos schedule and fixed: a client killed BEFORE the server
    crash never re-announces, and the restarted server's init used to
    await ALL ranks — hanging forever. The resumed server now arms the
    failure detector over every expected rank at construction; a rank
    silent past heartbeat_timeout_s is declared dead pre-init and
    leaves the awaited set, so the handshake completes over the
    survivors."""

    def test_client_killed_before_server_crash_does_not_stall_resume(
        self, args_factory, tmp_path
    ):
        import fedml_tpu
        from fedml_tpu.core.invariants import InvariantChecker
        from fedml_tpu.cross_silo import Server

        reset_chaos()
        Telemetry.reset()
        ck = str(tmp_path / "ck")
        td = str(tmp_path / "td")
        kw = dict(
            comm_round=3,
            checkpoint_dir=ck,
            checkpoint_freq=1,
            telemetry_dir=td,
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=1.0,
            client_num_in_total=2,
            client_num_per_round=2,
            chaos_schedule=[
                # rank 1 dies mid-train of its FIRST round: its
                # heartbeats die with it, long before the server does
                {"at": {"event": "barrier", "name": "client.train",
                        "rank": 1, "occurrence": 1},
                 "fault": "kill_client"},
                # ... then the server is killed at the next round's
                # WAL-append boundary
                {"at": {"event": "wal_append", "occurrence": 2},
                 "fault": {"kind": "kill_server", "when": "before"}},
            ],
        )
        run_id = "async_restart_race"
        server, clients, ds0 = _build_world(args_factory, run_id, 2, **kw)
        killed = {}

        def srv():
            try:
                server.run()
            except ProcessKilled as e:
                killed["where"] = e.where
                if server.manager._failure_detector is not None:
                    server.manager._failure_detector.stop()

        threads = _start_clients(clients)
        st = threading.Thread(target=srv, daemon=True)
        st.start()
        st.join(timeout=120)
        assert not st.is_alive(), "first incarnation never crashed"
        assert killed, "scheduled server kill never fired"

        # restart: rank 1 is long dead and will never re-announce.
        # Pre-fix, this run() blocked forever awaiting rank 1's ONLINE.
        a0b, _, m0b = _build_rank(args_factory, run_id, 0, **kw)
        server2 = Server(a0b, None, ds0, m0b)
        done = {}

        def srv2():
            server2.run()
            done["ok"] = True

        st2 = threading.Thread(target=srv2, daemon=True)
        st2.start()
        st2.join(timeout=90)
        assert done.get("ok"), (
            "resumed server never initialized: a dead rank still "
            "stalls the restart handshake"
        )
        # the world actually recovered: all rounds ran, the dead rank
        # was declared (not silently forgotten), and the surviving
        # client was released cleanly
        assert server2.manager.round_idx == 3
        assert 1 in server2.manager._dead_ranks
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        # acceptance: the invariant checker is green on the artifacts
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()
