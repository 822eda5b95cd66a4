"""Federation-wide distributed tracing: context propagation, shard
stitching, and round critical-path analytics.

PR 3's flight recorder (``core/telemetry.py``) is process-local: a
cross-silo run produces one trace per process with no causal links, so
nobody can answer "where did round N's 4.2s go — broadcast wire,
client compute, upload wire, or server aggregate?". That attribution
is the precondition for every latency play on the roadmap: streaming
aggregate-on-arrival and PiPar-style compute/comm overlap
(arXiv:2302.12803) are both claims about wire utilization and
straggler slack, and the Smart-NIC server-offload line of work
(arXiv:2307.06561) makes the same point that the server-side
bottleneck must be measured per-segment before it can be moved.

Three layers, bottom up:

- **Context propagation** (W3C-trace-context shaped, msgpack-native):
  the instrumented comm wrapper (``core/comm/instrument.py``) stamps
  every outbound :class:`~fedml_tpu.core.message.Message` with
  ``trace_id`` / ``trace_flow`` (a per-send unique id) via
  :func:`stamp_context`, and the cross-silo managers link effect to
  cause with :func:`continue_context` (a client's upload carries the
  broadcast's flow id as its parent span). Every wire send/receive is
  a ``comm.send``/``comm.recv`` span with Chrome-trace flow events
  (``ph:"s"``/``"f"``) across the edge, so the chain
  broadcast → local-train → upload → aggregate is causally linked
  across processes and backends (LOCAL, gRPC, MQTT), composing with
  ``FaultInjector``/``ReliableChannel`` in any wrap order —
  retransmits show up as ``comm.retry`` spans reusing the original
  flow id.
- **Stitching** (:func:`stitch_shards`): every process exports a trace
  shard into ``telemetry_dir`` (``trace.json`` / ``trace_rankN.json``,
  ``core/telemetry.py``); the stitcher aligns shards on their
  ``wall_t0_us`` anchors, corrects per-rank clock skew from the
  matched flow pairs themselves (the RTT-pair estimate — heartbeat/ACK
  traffic flows both directions through ``core/comm/heartbeat.py`` and
  ``reliable.py``, so both one-way deltas exist), and merges them into
  ONE perfetto-loadable timeline with named process tracks.
- **Critical-path analytics** (:func:`analyze_rounds`): walks the
  stitched timeline per round and attributes wall time to segments —
  ``broadcast_send`` (server-side send serialization), ``broadcast_wire``
  (downlink to the straggler), ``client_compute`` (the straggler's
  train span), ``upload_wire`` (straggler uplink), ``aggregate``, and
  ``other`` (dispatch gaps) — naming the straggler rank and each
  rank's slack. ``fedml_tpu.cli trace`` drives stitch + analyze and
  writes ``trace_merged.json`` + ``round_report.json``.

The live (online) counterparts — ``round_segment_seconds{segment=}``,
the ``round_straggler_slack_s`` histogram and ``slo_violations_total``
against ``round_deadline_s`` — are fed by the cross-silo server per
round (``fedml_server_manager.py``) from server-observable times plus
the client-reported ``train_seconds`` upload param; this module's
analyzer is the precise offline version computed from the stitched
flows.
"""

from __future__ import annotations

import glob
import itertools
import json
import logging
import os
import threading
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from .. import constants

__all__ = [
    "stamp_context",
    "continue_context",
    "RoundProfiler",
    "stitch_shards",
    "analyze_rounds",
    "trace_run",
]

# Message-envelope keys the comm layer's byte estimator must ignore
# (comm metadata, not payload) — see instrument.payload_nbytes.
TRACE_CTX_KEYS = (
    constants.MSG_ARG_KEY_TRACE_ID,
    constants.MSG_ARG_KEY_TRACE_SPAN,
    constants.MSG_ARG_KEY_TRACE_FLOW,
)

# Downlink message types that open a round on a client; uplink type
# that closes it on the server — the analyzer's segment vocabulary.
_BROADCAST_TYPES = (
    constants.MSG_TYPE_S2C_INIT_CONFIG,
    constants.MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT,
    constants.MSG_TYPE_S2C_RESYNC,
)
_UPLOAD_TYPE = constants.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER

# flow-id space: (rank+1) in the high bits, a process-wide counter low,
# so ids are unique across every rank of a world without coordination
_flow_counter = itertools.count(1)
_flow_lock = threading.Lock()


def _next_flow_id(rank: int) -> int:
    with _flow_lock:
        n = next(_flow_counter)
    return ((int(rank) + 1) << 40) | n


def trace_id_for(telemetry) -> str:
    """One trace per run: every process of a federation derives the
    same id from the shared ``run_id``, so cross-process spans join
    without a handshake."""
    return f"fedrun-{telemetry.run_id}"


def stamp_context(msg, telemetry, rank: int = 0):
    """Stamp W3C-style trace context onto an outbound message.

    Returns ``(flow_id, is_resend)``: ``flow_id`` is None for
    self-addressed loopback signals (deadline / death notices that
    never cross a wire — a flow arrow to yourself is noise);
    ``is_resend`` is True when the message already carried a flow id
    (a ReliableChannel retransmit or an injected duplicate re-entering
    the instrumented layer) — the original id is kept so whichever
    copy arrives first completes the SAME flow, and the send span is
    tagged as a retry.
    """
    existing = msg.get(constants.MSG_ARG_KEY_TRACE_FLOW)
    if existing is not None:
        return int(existing), True
    if int(msg.get_sender_id()) == int(msg.get_receiver_id()):
        return None, False
    flow_id = _next_flow_id(rank)
    msg.add_params(constants.MSG_ARG_KEY_TRACE_ID, trace_id_for(telemetry))
    msg.add_params(constants.MSG_ARG_KEY_TRACE_FLOW, flow_id)
    return flow_id, False


def continue_context(in_msg, out_msg) -> None:
    """Causally link ``out_msg`` to the message that triggered it: the
    client's upload carries the broadcast's trace id and names the
    broadcast's flow as its parent span. Safe no-op when the inbound
    message was never stamped (telemetry off, or a bare peer)."""
    trace_id = in_msg.get(constants.MSG_ARG_KEY_TRACE_ID)
    parent_flow = in_msg.get(constants.MSG_ARG_KEY_TRACE_FLOW)
    if trace_id is not None:
        out_msg.add_params(constants.MSG_ARG_KEY_TRACE_ID, trace_id)
    if parent_flow is not None:
        out_msg.add_params(constants.MSG_ARG_KEY_TRACE_SPAN, int(parent_flow))


class RoundProfiler:
    """On-demand device profiling for listed rounds
    (``args.profile_rounds``: a list or comma-separated string of round
    indices). ``tick(round_idx)`` at each round boundary stops any
    capture for an earlier round and starts one when ``round_idx`` is
    listed, writing a ``jax.profiler`` trace into
    ``<telemetry_dir>/profile/round_NNNN``; ``close()`` stops a still-
    open capture at run end. A backend that cannot capture (or a second
    concurrent profiler) logs ONE warning and disables itself — the
    run always survives the knob."""

    def __init__(self, args=None) -> None:
        raw = getattr(args, "profile_rounds", None) if args else None
        if raw is None:
            rounds = set()
        elif isinstance(raw, str):
            rounds = {int(r) for r in raw.replace(",", " ").split() if r.strip()}
        else:
            rounds = {int(r) for r in raw}
        self.rounds = rounds
        base = getattr(args, "telemetry_dir", None) if args else None
        self.out_dir = os.path.join(base, "profile") if base else None
        if self.rounds and not self.out_dir:
            logging.warning(
                "profile_rounds=%s ignored: telemetry_dir is unset (the "
                "capture needs somewhere to land)", sorted(self.rounds),
            )
            self.rounds = set()
        self._active: Optional[int] = None
        self._disabled = False

    @property
    def enabled(self) -> bool:
        return bool(self.rounds) and not self._disabled

    def tick(self, round_idx: int) -> None:
        if not self.enabled:
            return
        if self._active is not None and round_idx != self._active:
            self._stop()
        if round_idx in self.rounds and self._active is None:
            self._start(int(round_idx))

    def close(self) -> None:
        if self._active is not None:
            self._stop()

    def _start(self, round_idx: int) -> None:
        import jax.profiler

        path = os.path.join(self.out_dir, f"round_{round_idx:04d}")
        try:
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception as e:  # noqa: BLE001 — backend may not support capture
            logging.warning(
                "profile_rounds: device profiling unsupported on this "
                "backend (%s: %s); disabling for this run",
                type(e).__name__, e,
            )
            self._disabled = True
            return
        self._active = round_idx
        logging.info("profile_rounds: capturing round %d to %s", round_idx, path)

    def _stop(self) -> None:
        import jax.profiler

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — never kill the run on teardown
            logging.warning(
                "profile_rounds: stop_trace for round %s failed (%s: %s)",
                self._active, type(e).__name__, e,
            )
            self._disabled = True
        self._active = None


# ---------------------------------------------------------------------
# shard stitching
# ---------------------------------------------------------------------

MERGED_TRACE_BASENAME = "trace_merged.json"
ROUND_REPORT_BASENAME = "round_report.json"


def _load_shards(telemetry_dir: str) -> List[Dict[str, Any]]:
    """Read every per-process trace shard (``trace.json`` /
    ``trace_rankN.json``) exported into ``telemetry_dir``."""
    shards = []
    for path in sorted(glob.glob(os.path.join(telemetry_dir, "trace*.json"))):
        if os.path.basename(path) == MERGED_TRACE_BASENAME:
            continue
        with open(path) as fh:
            payload = json.load(fh)
        meta = payload.get("otherData", {})
        shards.append(
            {
                "path": path,
                "rank": int(meta.get("rank", 0) or 0),
                "wall_t0_us": float(meta.get("wall_t0_us", 0.0) or 0.0),
                "events_dropped": int(meta.get("events_dropped", 0) or 0),
                "events": payload.get("traceEvents", []),
            }
        )
    return shards


def _estimate_skews(
    shards: List[Dict[str, Any]]
) -> Dict[int, float]:
    """Per-shard clock-skew estimate (µs, relative to the rank-0 shard)
    from matched flow pairs — the classic RTT-pair offset: with
    ``fwd = recv_ts - send_ts`` for ref→shard flows and ``back`` for
    shard→ref flows, ``skew ≈ (min(fwd) - min(back)) / 2`` (symmetric
    minimum network delay cancels; the shard's events are then shifted
    by -skew). Heartbeats, ACKs and round traffic all contribute pairs.
    A shard with traffic in only one direction falls back to the
    causality bound (shift so the earliest violated flow becomes
    non-negative); a shard with no matched flows keeps its wall-clock
    alignment."""
    if not shards:
        return {}
    ref_idx = min(range(len(shards)), key=lambda i: shards[i]["rank"])
    # flow id -> (shard idx, aligned ts) for "s" and "f" events.
    # FIRST-wins per id: a retransmit re-emits "s" with the original
    # flow id and a duplicate delivery re-emits "f" — pairing a retry
    # send against the first arrival (or vice versa) would feed the
    # estimator a negative/backoff-sized delta and shift the whole
    # shard ("whichever copy arrives first completes the flow")
    starts: Dict[int, Tuple[int, float]] = {}
    ends: Dict[int, Tuple[int, float]] = {}
    for i, sh in enumerate(shards):
        base = sh["wall_t0_us"]
        for ev in sh["events"]:
            ph = ev.get("ph")
            if ph == "s":
                starts.setdefault(ev["id"], (i, ev["ts"] + base))
            elif ph == "f":
                ends.setdefault(ev["id"], (i, ev["ts"] + base))
    skews: Dict[int, float] = {ref_idx: 0.0}
    for i in range(len(shards)):
        if i == ref_idx:
            continue
        fwd = []  # ref (or any corrected shard) -> shard i
        back = []  # shard i -> ref
        for fid, (si, s_ts) in starts.items():
            fi_ts = ends.get(fid)
            if fi_ts is None:
                continue
            fi, e_ts = fi_ts
            if si == ref_idx and fi == i:
                fwd.append(e_ts - s_ts)
            elif si == i and fi == ref_idx:
                back.append(s_ts - e_ts)  # negated: skew_i + (-delay)
        if fwd and back:
            # back stored negated, so min(fwd) ≈ d + skew_i and
            # max(back) ≈ skew_i - d  =>  skew = (min(fwd)+max(back))/2
            skews[i] = (min(fwd) + max(back)) / 2.0
        elif fwd:
            # one-way only: causality bound — a receive must not
            # precede its send; shift just enough
            worst = min(fwd)
            skews[i] = min(worst, 0.0)
        elif back:
            worst = max(back)
            skews[i] = max(worst, 0.0)
        else:
            skews[i] = 0.0
    return skews


def stitch_shards(telemetry_dir: str) -> Dict[str, Any]:
    """Merge every trace shard in ``telemetry_dir`` into one
    perfetto-loadable Chrome-trace payload.

    Steps: wall-clock alignment (each shard's ``wall_t0_us`` anchor),
    per-shard skew correction (:func:`_estimate_skews`), per-rank
    ``pid`` namespacing with process_name metadata (two shards from
    one host share an OS pid; the merged view needs one track group
    per rank), and a global sort. Flow events pass through untouched —
    their ids already match across shards."""
    shards = _load_shards(telemetry_dir)
    if not shards:
        raise FileNotFoundError(
            f"no trace shards (trace*.json) found in {telemetry_dir!r}"
        )
    t0 = min(sh["wall_t0_us"] for sh in shards)
    skews = _estimate_skews(shards)
    merged: List[Dict[str, Any]] = []
    dropped_total = 0
    for i, sh in enumerate(shards):
        offset = sh["wall_t0_us"] - t0 - skews.get(i, 0.0)
        pid = 1000 + sh["rank"]
        dropped_total += sh["events_dropped"]
        merged.append(
            {
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {
                    "name": f"rank{sh['rank']}"
                    + (" (server)" if sh["rank"] == 0 else "")
                },
            }
        )
        for ev in sh["events"]:
            ev = dict(ev)
            ev["ts"] = round(ev["ts"] + offset, 1)
            ev["pid"] = pid
            merged.append(ev)
    meta_evs = [e for e in merged if e.get("ph") == "M"]
    data_evs = sorted(
        (e for e in merged if e.get("ph") != "M"), key=lambda e: e["ts"]
    )
    return {
        "traceEvents": meta_evs + data_evs,
        "displayTimeUnit": "ms",
        "otherData": {
            "shards": [os.path.basename(sh["path"]) for sh in shards],
            "ranks": sorted({sh["rank"] for sh in shards}),
            "skew_us": {
                str(shards[i]["rank"]): round(s, 1) for i, s in skews.items()
            },
            "events_dropped": dropped_total,
        },
    }


def flow_match_stats(events: List[Dict[str, Any]]) -> Dict[str, int]:
    """How many flow starts found their finish (the acceptance gate:
    every comm send span must have a matched receive flow)."""
    starts = {e["id"] for e in events if e.get("ph") == "s"}
    ends = {e["id"] for e in events if e.get("ph") == "f"}
    return {
        "flow_starts": len(starts),
        "flow_ends": len(ends),
        "matched": len(starts & ends),
        "unmatched_starts": len(starts - ends),
        "unmatched_ends": len(ends - starts),
    }


# ---------------------------------------------------------------------
# critical-path analytics
# ---------------------------------------------------------------------


def _spans_from_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Pair B/E events per (pid, tid, name) into [{name, ts, dur, args,
    pid, tid}] (µs). Nested same-name spans pair LIFO."""
    open_stack: Dict[Tuple, List[Dict[str, Any]]] = defaultdict(list)
    spans: List[Dict[str, Any]] = []
    for ev in events:
        ph = ev.get("ph")
        if ph not in ("B", "E"):
            continue
        key = (ev["pid"], ev["tid"], ev["name"])
        if ph == "B":
            open_stack[key].append(ev)
        else:
            if not open_stack[key]:
                continue
            b = open_stack[key].pop()
            spans.append(
                {
                    "name": ev["name"],
                    "pid": ev["pid"],
                    "tid": ev["tid"],
                    "ts": b["ts"],
                    "dur": ev["ts"] - b["ts"],
                    "args": b.get("args", {}),
                }
            )
    return spans


def analyze_rounds(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-round critical-path attribution over a stitched timeline.

    For each round r with a complete broadcast → train → upload →
    aggregate chain, walk the straggler's path (the client whose upload
    lands last at the server) and attribute the round's wall time
    (first broadcast send B → aggregate E) to consecutive segments:

    - ``broadcast_send``: first downlink send B → straggler's downlink
      send B (server-side send-loop serialization);
    - ``broadcast_wire``: straggler's downlink send B → its comm.recv B;
    - ``client_dispatch``: downlink receipt → train span B (handler
      dispatch, dataset switch);
    - ``client_compute``: the straggler's train span;
    - ``client_encode``: train E → upload send B (delta encode);
    - ``upload_wire``: straggler's upload send B → server comm.recv B
      (includes server dispatch-queue wait);
    - ``server_decode``: upload receipt → aggregate B (payload decode);
    - ``aggregate``: the server's aggregate span;
    - ``edge_merge`` / ``root_fold`` (hierarchical server plane only):
      when a round carries edge-tier spans, the two-hop flow
      client→edge→root is split out — ``edge_merge`` is the
      last-closing edge's limb-set export span and ``root_fold`` the
      sum of the root's per-edge merge spans; ``server_decode`` then
      shrinks to the residual of the upload-receipt→aggregate window
      (uplink wire + sibling-edge waits);
    - ``other``: wall − sum(above) — ≈0 when the chain is complete
      (the segments are consecutive walks of the same path); it grows
      exactly when a span is missing or the aggregate was triggered by
      a different client than the straggler (deadline path), so
      ``coverage`` (= named segments / wall) is the chain-consistency
      honesty metric tests/test_tracing.py gates on.

    Slack per rank = straggler upload arrival − that rank's arrival
    (how much longer the slowest client ran past each client).
    """
    spans = sorted(_spans_from_events(events), key=lambda s: s["ts"])
    # FIRST-wins everywhere a flow id or (round, rank) keys a span:
    # retransmits re-emit comm.send with the original flow id and
    # duplicate deliveries re-emit comm.recv — last-wins would let a
    # late duplicate inflate a fast client's arrival (flipping the
    # straggler) or pair a retry send against the first receipt
    # (negative wire segments)
    sends = defaultdict(list)   # round -> [send span]
    seen_send_flows = set()
    recvs = {}                  # flow id -> first recv span
    trains = defaultdict(dict)  # round -> rank -> train span
    aggregates = {}             # round -> aggregate span
    edge_merges = defaultdict(list)  # round -> edge_merge spans (hier)
    root_folds = defaultdict(list)   # round -> root_fold spans (hier)
    for sp in spans:
        a = sp["args"] or {}
        if sp["name"] == "comm.send" and "round" in a:
            flow = a.get("flow")
            if flow is not None:
                if flow in seen_send_flows:
                    continue  # retransmit of an already-seen send
                seen_send_flows.add(flow)
            sends[int(a["round"])].append(sp)
        elif sp["name"] == "comm.recv" and a.get("flow") is not None:
            recvs.setdefault(int(a["flow"]), sp)
        elif sp["name"] == "train" and "round" in a and "rank" in a:
            trains[int(a["round"])].setdefault(int(a["rank"]), sp)
        elif sp["name"] == "aggregate" and "round" in a:
            aggregates.setdefault(int(a["round"]), sp)
        elif sp["name"] == "edge_merge" and "round" in a:
            edge_merges[int(a["round"])].append(sp)
        elif sp["name"] == "root_fold" and "round" in a:
            root_folds[int(a["round"])].append(sp)

    reports = []
    for r in sorted(sends):
        downlinks = {}  # receiver rank -> (send span, recv span)
        uploads = {}    # sender rank -> (send span, recv span)
        for sp in sends[r]:
            a = sp["args"]
            rx = recvs.get(int(a.get("flow", -1)))
            if int(a.get("msg_type", -1)) in _BROADCAST_TYPES:
                downlinks.setdefault(int(a["receiver"]), (sp, rx))
            elif int(a.get("msg_type", -1)) == _UPLOAD_TYPE:
                uploads.setdefault(int(a["sender"]), (sp, rx))
        agg = aggregates.get(r)
        arrivals = {
            rank: rx["ts"] for rank, (_, rx) in uploads.items() if rx
        }
        if not downlinks or not arrivals or agg is None:
            continue  # incomplete chain (deadline-dropped round, crash)
        straggler = max(arrivals, key=arrivals.get)
        first_bcast = min(sp["ts"] for sp, _ in downlinks.values())
        wall = (agg["ts"] + agg["dur"]) - first_bcast
        seg = {}
        s_down, s_down_rx = downlinks.get(straggler, (None, None))
        s_up, s_up_rx = uploads[straggler]
        s_train = trains.get(r, {}).get(straggler)
        if s_down is not None:
            seg["broadcast_send"] = s_down["ts"] - first_bcast
            if s_down_rx is not None:
                seg["broadcast_wire"] = s_down_rx["ts"] - s_down["ts"]
        if s_train is not None:
            if s_down_rx is not None:
                seg["client_dispatch"] = s_train["ts"] - s_down_rx["ts"]
            seg["client_compute"] = s_train["dur"]
            seg["client_encode"] = s_up["ts"] - (s_train["ts"] + s_train["dur"])
        if s_up_rx is not None:
            seg["upload_wire"] = s_up_rx["ts"] - s_up["ts"]
            seg["server_decode"] = agg["ts"] - s_up_rx["ts"]
        ems, rfs = edge_merges.get(r), root_folds.get(r)
        if ems and rfs and s_up_rx is not None:
            # hierarchical two-hop split: the upload lands at an EDGE,
            # whose close exports the limb-set (edge_merge) the root
            # then merges (root_fold) before the finalize — name those
            # pieces and leave the uplink wire / sibling-edge waits as
            # the server_decode residual
            last_em = max(ems, key=lambda s: s["ts"] + s["dur"])
            seg["edge_merge"] = last_em["dur"]
            seg["root_fold"] = sum(s["dur"] for s in rfs)
            seg["server_decode"] = max(
                (agg["ts"] - s_up_rx["ts"])
                - seg["edge_merge"]
                - seg["root_fold"],
                0.0,
            )
        seg["aggregate"] = agg["dur"]
        named = sum(seg.values())
        seg["other"] = wall - named
        last = arrivals[straggler]
        reports.append(
            {
                "round": r,
                "wall_s": round(wall / 1e6, 6),
                "segments_s": {
                    k: round(v / 1e6, 6) for k, v in seg.items()
                },
                "coverage": round(named / wall, 4) if wall > 0 else None,
                "straggler_rank": straggler,
                "slack_s": {
                    str(rank): round((last - ts) / 1e6, 6)
                    for rank, ts in sorted(arrivals.items())
                },
                "cohort": sorted(arrivals),
            }
        )
    return reports


def trace_run(
    telemetry_dir: str, out_dir: Optional[str] = None
) -> Dict[str, Any]:
    """Stitch + analyze one run's shards: writes
    ``trace_merged.json`` (perfetto-loadable) and
    ``round_report.json`` into ``out_dir`` (default: the telemetry dir
    itself) and returns a summary. The ``fedml_tpu.cli trace``
    subcommand and tests/test_tracing.py's world test both call this."""
    out_dir = out_dir or telemetry_dir
    merged = stitch_shards(telemetry_dir)
    rounds = analyze_rounds(merged["traceEvents"])
    os.makedirs(out_dir, exist_ok=True)
    merged_path = os.path.join(out_dir, MERGED_TRACE_BASENAME)
    with open(merged_path + ".tmp", "w") as fh:
        json.dump(merged, fh)
    os.replace(merged_path + ".tmp", merged_path)
    report_path = os.path.join(out_dir, ROUND_REPORT_BASENAME)
    report = {
        "kind": "round_report",
        "telemetry_dir": os.path.abspath(telemetry_dir),
        "ranks": merged["otherData"]["ranks"],
        "skew_us": merged["otherData"]["skew_us"],
        "flows": flow_match_stats(merged["traceEvents"]),
        "rounds": rounds,
    }
    with open(report_path + ".tmp", "w") as fh:
        json.dump(report, fh, indent=2)
    os.replace(report_path + ".tmp", report_path)
    return {
        "merged_trace": merged_path,
        "round_report": report_path,
        "events": len(merged["traceEvents"]),
        "shards": merged["otherData"]["shards"],
        "ranks": merged["otherData"]["ranks"],
        "flows": report["flows"],
        "rounds_analyzed": len(rounds),
    }
