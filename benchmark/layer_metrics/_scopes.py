"""From the traced run's ``.xplane.pb`` to the time of each named part
of the round executable, and from its idle gaps to the program span
each fell in. Computed once a run and kept in ``ctx``.

**Scopes.** ``simulation/fedavg_api.build_round_fn`` wraps its three
parts in ``jax.named_scope`` (``fed.gather``, ``fed.local_train``,
``fed.aggregate``; ``fwd_bwd`` and ``opt`` nest inside local training).
A scope is a component of the ``op_name`` in an HLO instruction's
metadata, so it survives whatever number the compiler gives the
instruction. An ``XLA Ops`` event is resolved to its ``op_name`` from,
in this order and whichever first names any scope at all:

1. ``event_stat``: an op-name statistic (``OP_NAME_STATS``) of the
   event itself;
2. ``event_metadata``: the same of the event's metadata record, read
   from the file's bytes (``_xplane_wire.py``) -- where the v5e's
   traces have it, as ``tf_op``;
3. ``embedded_hlo``: the HLO module the profile embeds under
   ``/host:metadata``, joined by the executable the event ran in (the
   ``XLA Modules`` event around it) and the instruction's name.

once per distinct (executable, operation), not per event. A scope's
time is the **union** of the intervals of the events tagged with it --
a ``while`` encloses its body's operations, and a sum would count them
twice -- per chip, averaged over chips. An executable served from a
persistent cache written before the scopes existed carries none
(``jax_compilation_cache_include_metadata_in_key`` is False): then
every scope reads None, and ``why`` says so. Never a guess.

**Gaps.** Each idle gap of the first chip (between consecutive merged
operation intervals) goes to the *innermost* program span around its
midpoint, over ``PROGRAM_SPANS``; ``unnamed`` where there is none.
``reduce_trace._gaps`` does the same over the traffic file's two names.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from reduce_trace import DEVICE_PLANE, merge, short_name, union_length

LAYER_DIR = os.path.dirname(os.path.abspath(__file__))
# run.py keeps a traced run's files here until the readers have run
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(LAYER_DIR)), ".bench_trace")

SCOPES = ("fed.gather", "fed.local_train", "fed.aggregate", "fwd_bwd", "opt")
PROGRAM_SPANS = (
    "train.plan", "round", "round.prep", "round.dispatch", "round.wait", "eval",
    "flush.fetch", "flush.report", "round.ckpt", "train.drain", "gc",
)
_COMPONENT = re.compile(r"[^/():]+")
# the statistics that hold an HLO op_name (a TPU trace: ``tf_op`` on
# the event's metadata, "<op_name>:<op type>"). No other string is
# searched: a source path such as /opt/venv/... has components too
OP_NAME_STATS = ("tf_op", "op_name", "hlo_op_name")
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def scopes_in(op_name: str) -> Tuple[str, ...]:
    """The scope names among an ``op_name``'s components, outermost
    first (``jit(f)/fed.local_train/vmap()/while/body/opt/mul`` ->
    ``("fed.local_train", "opt")``)."""
    if not op_name:
        return ()
    return tuple(c for c in _COMPONENT.findall(op_name) if c in SCOPES)


def innermost_scope(op_name: str) -> Optional[str]:
    found = scopes_in(op_name)
    return found[-1] if found else None


class _Resolver:
    """(executable, event) -> the scopes of its ``op_name``, from the
    first source that names any; each distinct pair looked up once.
    An executable is the name of its ``XLA Modules`` event,
    ``jit_round_fn(<program id>)``: two programs may number their
    instructions alike."""

    def __init__(self, raw: Optional[bytes]) -> None:
        self.raw = raw
        self.source: Optional[str] = None
        self._meta = None  # plane -> {(program id, event name): [op names]}
        self._hlo = None   # executable -> {instruction: op_name}

    def _metadata(self, plane: str) -> Dict[Tuple[str, str], List[str]]:
        if self._meta is None:
            self._meta = {}
            if self.raw is not None:
                import _xplane_wire as wire

                for p in wire.planes(self.raw):
                    if not DEVICE_PLANE.match(wire.plane_name(p)):
                        continue
                    md = wire.plane_metadata(p)
                    self._meta[md["name"]] = {
                        (str(rec["stats"].get("program_id", "")), rec["name"]): [
                            v for k, v in rec["stats"].items()
                            if k in OP_NAME_STATS and isinstance(v, str)]
                        for rec in md["events"].values()}
        return self._meta.get(plane, {})

    def _modules(self) -> Dict[str, Dict[str, str]]:
        if self._hlo is None:
            self._hlo = {}
            if self.raw is not None:
                import _xplane_wire as wire

                self._hlo = dict(wire.embedded_hlo(self.raw))
        return self._hlo

    def candidates(self, source: str, plane: str, key, own: List[str]) -> List[str]:
        if source == "event_stat":
            return own
        if source == "event_metadata":
            m = _ID_SUFFIX.search(key[0])
            return self._metadata(plane).get((m.group(0)[1:-1] if m else "", key[1]), [])
        op_name = self._modules().get(key[0], {}).get(short_name(key[1]))
        return [op_name] if op_name else []

    def resolve(self, firsts: Dict[Tuple[str, str], Tuple[str, object]]):
        """``firsts``: {(module, event name): (plane, the first such
        event's own string statistics)}. Returns {(module, event
        name): scopes} by the first source under which any pair names
        a scope."""
        for source in ("event_stat", "event_metadata", "embedded_hlo"):
            out = {}
            for key, (plane, own) in firsts.items():
                best: Tuple[str, ...] = ()
                for text in self.candidates(source, plane, key, own):
                    found = scopes_in(text)
                    if len(found) > len(best):
                        best = found
                out[key] = best
            if any(out.values()):
                self.source = source
                return out
        return {key: () for key in firsts}


def reduce_scopes(data, raw: Optional[bytes] = None) -> dict:
    """``data``: a ``ProfileData`` (or planes of the same shape);
    ``raw``: the file's bytes, for the two sources ``ProfileData`` does
    not hand out. Returns ``{"source", "why", "scope_s", "gap_s",
    "gap_total_s", "untagged_s", "devices"}``."""
    devices, host = [], []
    # each event's executable, and the own statistics of the first
    # event of each distinct (executable, operation)
    firsts: Dict[Tuple[str, str], Tuple[str, List[str]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
                for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
            starts = [mod[0] for mod in modules]
            keyed = []
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s = int(ev.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                key = (modules[i][2] if i >= 0 and s < modules[i][1] else "", ev.name)
                if key not in firsts:
                    firsts[key] = (plane.name, [v for k, v in ev.stats
                                                if k in OP_NAME_STATS and isinstance(v, str)])
                keyed.append((key, s, s + int(ev.duration_ns)))
            if keyed:
                devices.append({"index": int(m.group(1)), "keyed": keyed})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PROGRAM_SPANS:
                        host.append((int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name))
    out = {"source": None, "why": None, "scope_s": {}, "gap_s": {}, "gap_total_s": 0.0,
           "untagged_s": 0.0, "devices": len(devices)}
    if not devices:
        out["why"] = "the trace has no device plane with an XLA Ops line"
        return out
    devices.sort(key=lambda d: d["index"])
    resolver = _Resolver(raw)
    scopes_of = resolver.resolve(firsts)
    out["source"] = resolver.source
    if resolver.source is None:
        out["why"] = (
            f"none of {len(firsts)} distinct operations names a scope in its statistics, its "
            "metadata or the embedded HLO: the executables were built (or served from a "
            "persistent cache written) before the scopes existed")

    n = len(devices)
    totals: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        by_scope: Dict[str, list] = collections.defaultdict(list)
        in_round, tagged = [], []
        for key, s, e in dev["keyed"]:
            found = scopes_of[key]
            for scope in found:
                by_scope[scope].append((s, e))
            if key[0].startswith("jit_round_fn"):
                (tagged if found else in_round).append((s, e))
        for scope, ivals in by_scope.items():
            totals[scope] += union_length(ivals) / 1e9 / n
        # what the round executable ran under no scope at all (on the
        # v5e the loops themselves carry no op name, their bodies do:
        # this is the loops' own overhead and the compiler's copies)
        out["untagged_s"] += (union_length(in_round + tagged) - union_length(tagged)) / 1e9 / n
    out["scope_s"] = dict(totals)
    out["gap_s"] = _gaps_by_span(devices[0], host)
    out["gap_total_s"] = sum(out["gap_s"].values())
    return out


def _gaps_by_span(dev: dict, host) -> Dict[str, float]:
    busy = merge((s, e) for _, s, e in dev["keyed"])
    spans = sorted(host)
    by_span: Dict[str, float] = collections.defaultdict(float)
    active: List[Tuple[int, int, str]] = []
    nxt = 0
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        mid = e0 + gap // 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > mid]
        # innermost: of the spans around the midpoint, the last to start
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2] if active else "unnamed"
        by_span[name] += gap / 1e9
    return dict(by_span)


def find_trace(cell_name: str) -> Optional[str]:
    hits = sorted(glob.glob(
        os.path.join(TRACE_ROOT, cell_name, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def summary(ctx) -> dict:
    """The run's scope and gap reduction, made on first use."""
    if "_scopes" not in ctx:
        path = find_trace(ctx["cell"].name)
        if path is None:
            out = {"source": None, "why": f"no trace under {TRACE_ROOT}/{ctx['cell'].name}",
                   "scope_s": {}, "gap_s": {}, "gap_total_s": 0.0, "untagged_s": 0.0}
        else:
            from jax.profiler import ProfileData

            with open(path, "rb") as f:
                raw = f.read()
            out = reduce_scopes(ProfileData.from_serialized_xspace(raw), raw)
        ctx["_scopes"] = out
        if out["why"]:
            print("scopes none: " + out["why"], file=sys.stderr, flush=True)
        else:
            print("scopes " + json.dumps({
                "source": out["source"], "untagged_s": round(out["untagged_s"], 6),
                **{k: round(v, 6) for k, v in sorted(out["scope_s"].items())}}),
                file=sys.stderr, flush=True)
        print("gaps " + json.dumps({k: round(v, 6) for k, v in sorted(
            out["gap_s"].items(), key=lambda kv: -kv[1])}), file=sys.stderr, flush=True)
    return ctx["_scopes"]


def scope_ms_per_round(ctx, scope: str):
    """Device milliseconds of one scope, per run of the round
    executable; None where the trace names no such scope."""
    seconds = summary(ctx)["scope_s"].get(scope, 0.0)
    runs = sum(m["count"] for name, m in ctx["trace"]["modules"].items()
               if "jit_round_fn" in name)
    if seconds <= 0 or not runs:
        return None
    return 1e3 * seconds / runs


def idle_unnamed_pct(ctx):
    s = summary(ctx)
    if s["gap_total_s"] <= 0:
        return None
    return 100.0 * s["gap_s"].get("unnamed", 0.0) / s["gap_total_s"]


# -- the program's own spans, from its flight recorder -------------------

def window_spans(events) -> Optional[List[dict]]:
    """The closed B/E spans between the last ``bench.window_start`` /
    ``bench.window_end`` pair as ``{"name", "tid", "t0", "t1"}``
    (microseconds); None without such a pair."""
    marks = [e for e in events if e["name"] in ("bench.window_start", "bench.window_end")]
    if len(marks) < 2 or marks[-2]["name"] != "bench.window_start" \
            or marks[-1]["name"] != "bench.window_end":
        return None
    lo, hi = marks[-2]["ts"], marks[-1]["ts"]
    open_: Dict[Tuple[int, str], list] = collections.defaultdict(list)
    out = []
    for e in events:
        if not lo <= e["ts"] <= hi or e["ph"] not in ("B", "E"):
            continue
        key = (e["tid"], e["name"])
        if e["ph"] == "B":
            open_[key].append(e["ts"])
        elif open_[key]:
            out.append({"name": e["name"], "tid": e["tid"], "t0": open_[key].pop(), "t1": e["ts"],
                        "args": e.get("args", {})})
    return out


def round_host_ms(events) -> Optional[List[float]]:
    """For each ``round`` span of the window, its length less the
    ``round.wait`` and ``flush.fetch`` inside it: the host's own work a
    round, milliseconds. None where the program has no ``round.dispatch``
    child (before this PR ``round`` wrapped the dispatch alone)."""
    spans = window_spans(events)
    if not spans or not any(s["name"] == "round.dispatch" for s in spans):
        return None
    waits = [s for s in spans if s["name"] in ("round.wait", "flush.fetch")]
    out = []
    for r in (s for s in spans if s["name"] == "round"):
        inside = sum(
            w["t1"] - w["t0"] for w in waits
            if w["tid"] == r["tid"] and r["t0"] <= w["t0"] and w["t1"] <= r["t1"])
        out.append((r["t1"] - r["t0"] - inside) / 1e3)
    return out or None


def round_host(ctx) -> Optional[List[float]]:
    """``round_host_ms`` of this run's window, made on first use."""
    if "_round_host" not in ctx:
        ctx["_round_host"] = round_host_ms(program_events())
    return ctx["_round_host"]


def program_events():
    from fedml_tpu.core.telemetry import Telemetry

    rec = Telemetry.get_instance().recorder
    return rec.tail(rec.capacity)
