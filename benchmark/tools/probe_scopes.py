#!/usr/bin/env python3
"""Where do ``jax.named_scope`` names land in a TPU trace?

    python benchmark/tools/probe_scopes.py --workload <cell> --seed <n> [--rounds 1] [--keep-pb 1]
    python benchmark/tools/probe_scopes.py --strip chiprun_out/scopes_<cell>.xplane.pb.gz <fixture>.pb.gz

Builds the cell as ``run.py`` does, traces ONE ``train()`` call of
``--rounds`` rounds (an evaluation after each) and writes to
``chiprun_out/scope_probe_<cell>.json``: every plane with its own
statistics and its lines; for each distinct ``XLA Ops`` event (by
time) the event's own statistics as ``jax.profiler.ProfileData`` hands
them out and the statistics of its *event metadata*, which only the
file's bytes hold (``layer_metrics/_xplane_wire.py``); the HLO modules
the profile embeds, with how many instructions of each carry which
scope; and what ``layer_metrics/_scopes.py`` makes of it all. With
``--keep-pb 1`` the whole trace comes back gzipped, for a recorded
fixture. A tool for whoever writes or repairs a reader;
``probe_trace.py`` prints names and times only.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "layer_metrics"), CHECKOUT]


def _short(v, n=160):
    if isinstance(v, bytes):
        return f"<{len(v)} bytes>"
    if isinstance(v, str) and len(v) > n:
        return v[:n] + f"...<{len(v)} chars>"
    return v


def describe(path: str, top: int = 60) -> dict:
    from jax.profiler import ProfileData

    import _scopes
    import _xplane_wire as wire

    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    metadata = {m["name"]: m for m in map(wire.plane_metadata, wire.planes(raw))}
    out = {"bytes": len(raw), "planes": {}}
    for plane in data.planes:
        meta = metadata.get(plane.name, {"events": {}, "stat_names": {}})
        by_name = {rec["name"]: rec for rec in meta["events"].values()}
        lines = {}
        for line in plane.lines:
            total, count, first_stats = collections.Counter(), collections.Counter(), {}
            for ev in line.events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                if ev.name not in first_stats:
                    first_stats[ev.name] = {k: _short(v) for k, v in ev.stats}
            lines[line.name] = {
                "events": sum(count.values()),
                "top": [{
                    "name": _short(name, 240), "seconds": ns / 1e9, "count": count[name],
                    "event_stats": first_stats[name],
                    "metadata_stats": {
                        k: _short(v) for k, v in by_name.get(name, {}).get("stats", {}).items()},
                    "metadata_display_name": by_name.get(name, {}).get("display_name", ""),
                } for name, ns in total.most_common(top)],
            }
        out["planes"][plane.name] = {
            "plane_stats": {k: _short(v) for k, v in plane.stats},
            "stat_names": sorted(set(meta["stat_names"].values())),
            "event_metadata_records": len(meta["events"]),
            "lines": lines,
        }
    out["embedded_hlo"] = [{
        "module": module, "instructions": len(names),
        "by_scope": dict(collections.Counter(
            _scopes.innermost_scope(op_name) or "(none)" for op_name in names.values())),
        "sample": dict(list(names.items())[:8]),
    } for module, names in wire.embedded_hlo(raw)]
    summary = _scopes.reduce_scopes(data, raw)
    out["scopes"] = summary
    return out


# -- cutting a trace down to a fixture ------------------------------------

def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _put(num: int, wt: int, val) -> bytes:
    """One field back onto the wire (the inverse of ``wire.fields``)."""
    import struct

    key = _vint(num << 3 | wt)
    if wt == 0:
        return key + _vint(val)
    if wt == 2:
        val = bytes(val)
        return key + _vint(len(val)) + val
    return key + struct.pack("<Q" if wt == 1 else "<I", val)


def _keep(msg, rules) -> bytes:
    """``msg`` with only the fields ``rules`` names: {field: True} keeps
    it as it is, {field: callable} keeps what the callable returns
    (None drops it)."""
    import _xplane_wire as wire

    out = bytearray()
    for num, wt, val in wire.fields(msg):
        rule = rules.get(num)
        if rule is True:
            out += _put(num, wt, val)
        elif rule is not None:
            new = rule(val)
            if new is not None:
                out += _put(num, wt, new)
    return bytes(out)


def strip(raw: bytes, name_chars: int = 96) -> bytes:
    """The trace the scope reduction and ``reduce_trace`` need, and no
    more: each device plane's ``XLA Ops`` and ``XLA Modules`` lines
    (events without their own statistics, operation names cut after
    ``name_chars``), each operation's ``tf_op`` and ``program_id``, the
    program's spans on the host plane, and of the embedded HLO each
    instruction's name and op name."""
    import _scopes
    import _xplane_wire as wire

    def field(msg, want):
        for num, wt, val in wire.fields(msg):
            if num == want:
                return val
        return None

    def cut_hlo(stat):
        # XStat.bytes_value=6 -> HloProto.hlo_module=1 -> name=1, computations=3
        # -> instructions=2 -> name=1, metadata=7 -> op_name=2
        inst = {1: True, 7: lambda md: _keep(md, {2: True})}
        comp = {1: True, 2: lambda i: _keep(i, inst)}
        return _keep(stat, {1: True, 6: lambda proto: _keep(proto, {
            1: lambda mod: _keep(mod, {1: True, 3: lambda c: _keep(c, comp)})})})

    planes = []
    for plane in wire.planes(raw):
        pname = wire.plane_name(plane)
        md = wire.plane_metadata(plane)
        ids = {v: k for k, v in md["stat_names"].items()}
        if _scopes.DEVICE_PLANE.match(pname):
            stats = {ids[n] for n in ("tf_op", "program_id") if n in ids}
            lines = {"XLA Ops", "XLA Modules"}
            events = None
        elif pname == "/host:CPU":
            stats = {ids[n] for n in ("round", "generation") if n in ids}
            lines = None
            events = {eid for eid, rec in md["events"].items()
                      if rec["name"] in _scopes.PROGRAM_SPANS}
        elif pname == "/host:metadata":
            planes.append(_keep(plane, {1: True, 2: True, 5: True, 4: lambda entry: _keep(entry, {
                1: True, 2: lambda m: _keep(m, {1: True, 2: True, 5: cut_hlo})})}))
            continue
        else:
            continue
        used = set()

        def line(ln):
            if lines is not None and wire._text(field(ln, 2) or b"") not in lines:
                return None

            def event(evt):
                eid = field(evt, 1)
                if events is not None and eid not in events:
                    return None
                used.add(eid)
                keep_stat = lambda st: st if events is not None and field(st, 1) in stats else None  # noqa: E731
                return _keep(evt, {1: True, 2: True, 3: True, 5: True, 4: keep_stat})

            cut = _keep(ln, {1: True, 2: True, 3: True, 9: True, 10: True, 11: True, 4: event})
            return cut if field(cut, 4) is not None else None

        body = _keep(plane, {1: True, 2: True, 3: line})

        def metadata(entry):
            def record(m):
                if field(m, 1) not in used:
                    return None
                return _keep(m, {
                    1: True, 4: True,
                    2: lambda nm: bytes(nm)[:name_chars] if len(nm) > name_chars else nm,
                    5: lambda st: st if field(st, 1) in stats else None})

            cut = _keep(entry, {1: True, 2: record})
            return cut if field(cut, 2) is not None else None

        body += _keep(plane, {4: metadata, 5: lambda entry: entry if field(entry, 1) in stats else None})
        planes.append(body)
    return b"".join(_put(1, 2, p) for p in planes)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strip", nargs=2, metavar=("IN.pb.gz", "OUT.pb.gz"), help=(
        "cut a recorded trace down to a test fixture and exit (needs no chip)"))
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--keep-pb", type=int, default=0)
    ns = ap.parse_args()
    if ns.strip:
        with gzip.open(ns.strip[0]) as f:
            small = strip(f.read())
        with gzip.open(ns.strip[1], "wb", compresslevel=9) as g:
            g.write(small)
        print(len(small), "bytes,", os.path.getsize(ns.strip[1]), "gzipped")
        return 0
    if not ns.workload:
        ap.error("--workload is required")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    import jax

    import harness

    cell = harness.Cell(ns.workload)
    driver = cell.family_module().Driver(cell, ns.seed)
    driver.setup()
    driver._set_call(ns.rounds, 1)
    driver.api.train()  # the traced call's host programs, built outside the trace
    tdir = os.path.join(CHECKOUT, ".bench_trace", "scopes_" + cell.name)
    shutil.rmtree(tdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=options)
    t0 = time.perf_counter()
    driver.api.train()
    jax.block_until_ready(driver.api.global_params)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    pbs = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True))
    outdir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(outdir, exist_ok=True)
    summary = {"wall_s": wall, "rounds": ns.rounds,
               "device": [d.device_kind for d in jax.devices()]}
    if ns.keep_pb and pbs:
        with open(pbs[-1], "rb") as f, gzip.open(
                os.path.join(outdir, f"scopes_{cell.name}.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
    for p in pbs[-1:]:
        try:
            summary["trace"] = describe(p)
        except Exception as e:  # the trace itself still comes back
            import traceback

            summary["describe_failed"] = traceback.format_exc()
            print("describe failed:", e, file=sys.stderr)
    with open(os.path.join(outdir, f"scope_probe_{cell.name}.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print(json.dumps(summary.get("trace", {}).get("scopes"), default=str))
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
