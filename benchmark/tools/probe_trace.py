#!/usr/bin/env python3
"""Look at one trace by hand: which planes are devices, which lines
they carry, how executables, kernels and host spans are named.

    python benchmark/tools/probe_trace.py --workload <cell> --seed <n> [--calls 1]

Builds the cell as ``run.py`` does, traces ``--calls`` window calls and
writes a summary of every plane and line (event counts, the names that
took most time) to ``chiprun_out/trace_probe_<cell>.json``. A tool for
whoever writes or repairs a reader; the benchmark's runs do not use it.
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
sys.path[:0] = [BENCH_DIR, CHECKOUT]


def summarize(path: str, top: int = 40) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            first = last = None
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                first = s if first is None else min(first, s)
                last = e if last is None else max(last, e)
            lines[line.name] = {
                "events": n, "first_ns": first, "last_ns": last,
                "top": [[k, v / 1e9, count[k]] for k, v in total.most_common(top)],
            }
        out[plane.name] = lines
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--keep-pb", type=int, default=0)
    ns = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    import jax

    import harness

    cell = harness.Cell(ns.workload)
    driver = cell.family_module().Driver(cell, ns.seed)
    driver.setup()
    tdir = os.path.join(CHECKOUT, ".bench_trace", "probe_" + cell.name)
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    win = driver.window(ns.seconds)
    jax.profiler.stop_trace()
    print("window", {k: v for k, v in win.items() if k != "round_intervals_ms"}, file=sys.stderr)
    pbs = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    outdir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(outdir, exist_ok=True)
    summary = {"files": [[p, os.path.getsize(p)] for p in pbs], "wall_s": time.perf_counter() - t0}
    for p in pbs:
        summary[os.path.basename(p)] = summarize(p)
        if ns.keep_pb:
            with open(p, "rb") as f, gzip.open(
                    os.path.join(outdir, f"probe_{cell.name}.xplane.pb.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
    with open(os.path.join(outdir, f"trace_probe_{cell.name}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
