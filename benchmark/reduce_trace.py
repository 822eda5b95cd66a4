"""From a profiler trace (``.xplane.pb``) to the numbers readers use.

    summary = reduce_trace.reduce_file(path, host_spans=["round", "eval"])

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds
(looked at by hand on the v5e, PR 24): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Modules`` carries one event per run
of an executable (named by its XLA module, ``jit_round_fn(<id>)``) and
whose line ``XLA Ops`` carries one event per HLO operation, control-flow
operations (``while``, ``conditional``, ``call``) enclosing their
bodies' events; and one plane ``/host:CPU`` whose lines are the host's
threads, ``jax.profiler.TraceAnnotation`` spans among their events. All
planes share one clock.

The summary:

- ``busy_s``          union of the device's operation intervals, seconds,
                      averaged over the chips traced
- ``span_s``          first to last device event, longest chip
- ``modules``         {executable: {"count", "total_s"}}, averaged over chips
- ``kernels``         {name: {"count", "total_s"}} for each of ``kernel_names``:
                      the operations whose name holds it (a Pallas kernel
                      keeps the ``name=`` it was given)
- ``top_ops``         [[operation, seconds], ...]: first the outermost
                      operations of the executables (``top:while.144`` is a
                      whole scan), then the leaf operations, by time
- ``top_gaps``        [[host span or "no span", seconds], ...] the longest
                      idle gaps of the first chip, named by the enclosing
                      host span
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTROL_FLOW = ("while", "conditional", "call")
_ID_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Possibly overlapping [start, end) as sorted disjoint intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Iterable[Interval]) -> int:
    """Total length covered by possibly overlapping [start, end)."""
    return sum(e - s for s, e in merge(intervals))


def short_name(name: str) -> str:
    """``%fusion.123 = f32[..] fusion(..)`` -> ``fusion.123``."""
    return name.split(" = ")[0].lstrip("%")


def op_kind(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", short_name(name))


def is_control_flow(name: str) -> bool:
    return op_kind(name) in CONTROL_FLOW


def module_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def _events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def reduce_profile(data, host_spans: Optional[List[str]] = None,
                   kernel_names: Optional[List[str]] = None) -> dict:
    host_spans = list(host_spans or [])
    kernel_names = list(kernel_names or [])
    devices = []
    host_events: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"index": int(m.group(1)), "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] = _events(line)
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:") and host_spans:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_spans:
                        host_events.append(
                            (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    if not devices:
        return {
            "devices": 0, "busy_s": 0.0, "span_s": 0.0, "modules": {}, "kernels": {},
            "top_ops": [], "top_gaps": [],
        }
    devices.sort(key=lambda d: d["index"])
    n = len(devices)
    busy, span = [], []
    modules: Dict[str, Dict[str, float]] = collections.defaultdict(lambda: {"count": 0.0, "total_s": 0.0})
    leaf_time: Dict[str, float] = collections.defaultdict(float)
    top_time: Dict[str, float] = collections.defaultdict(float)
    kernels: Dict[str, Dict[str, float]] = collections.defaultdict(lambda: {"count": 0.0, "total_s": 0.0})
    for dev in devices:
        ivals = [(s, e) for _, s, e in dev["ops"]] or [(s, e) for _, s, e in dev["modules"]]
        busy.append(union_length(ivals) / 1e9)
        every = ivals + [(s, e) for _, s, e in dev["modules"]]
        span.append((max(e for _, e in every) - min(s for s, _ in every)) / 1e9)
        for name, s, e in dev["modules"]:
            mod = modules[module_name(name)]
            mod["count"] += 1.0 / n
            mod["total_s"] += (e - s) / 1e9 / n
        outer_end = -1
        for name, s, e in sorted(dev["ops"], key=lambda ev: (ev[1], -ev[2])):
            if s >= outer_end:  # not enclosed by an earlier operation
                outer_end = e
                top_time[short_name(name)] += (e - s) / 1e9 / n
            if is_control_flow(name):
                continue
            leaf_time[short_name(name)] += (e - s) / 1e9 / n
    # a Pallas/Mosaic kernel keeps the name it was given in its
    # operation's name; the caller says which names to look for
    for dev in devices:
        for name, s, e in dev["ops"]:
            for kname in kernel_names:
                if kname in name:
                    kernels[kname]["count"] += 1.0 / n
                    kernels[kname]["total_s"] += (e - s) / 1e9 / n
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "span_s": max(span),
        "modules": {k: dict(v) for k, v in modules.items()},
        "kernels": {k: dict(v) for k, v in kernels.items()},
        "top_ops": _rank(top_time, "top:", 5) + _rank(leaf_time, "", 5),
        "top_gaps": _gaps(devices[0], host_events),
    }


def _rank(times: Dict[str, float], prefix: str, k: int) -> List[List]:
    return [[prefix + name, v] for name, v in sorted(times.items(), key=lambda kv: -kv[1])[:k]]


def _gaps(dev: dict, host_events) -> List[List]:
    ivals = merge([(s, e) for _, s, e in dev["ops"]] or [(s, e) for _, s, e in dev["modules"]])
    by_span: Dict[str, float] = collections.defaultdict(float)
    spans = sorted(host_events, key=lambda h: h[1])
    for (s0, e0), (s1, _) in zip(ivals, ivals[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        mid = e0 + gap // 2
        name = "no span"
        for hname, hs, he in spans:
            if hs <= mid < he:
                name = hname  # innermost wins: later spans start later
        by_span[name] += gap / 1e9
    return [[k, v] for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])[:10]]


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def reduce_file(path: str, host_spans: Optional[List[str]] = None,
                kernel_names: Optional[List[str]] = None) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), host_spans, kernel_names)


def reduce_dir(trace_dir: str, host_spans: Optional[List[str]] = None,
               kernel_names: Optional[List[str]] = None) -> dict:
    return reduce_file(find_xplane(trace_dir), host_spans, kernel_names)
