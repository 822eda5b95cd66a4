#!/usr/bin/env python3
"""Measure a cell's spreads the way the driver does: two sets of runs
with the same seeds, each run a new process, then traced runs.

    python benchmark/tools/measure_sets.py --workload <cell> \
        --seeds 1,2,3,4,5,6 --trace-seeds 7,8,9 [--seconds <run_seconds>]

This parent never imports JAX (a chip belongs to one process). For each
end-to-end metric it prints each set's spread -- the distance between
the first and third quartile (``statistics.quantiles(values, n=4)``) as
a share of the median -- the wider of the two, and how far the second
set's median lies from the first's. Every run's result line and the
tail of its errors go to ``chiprun_out/sets_<cell>.json``. Not part of
a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def run_once(cmd, cell, seed, seconds, trace):
    t0 = time.perf_counter()
    p = subprocess.run(
        cmd + ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
               "--trace", str(trace)],
        capture_output=True, text=True, cwd=CHECKOUT)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    row = {"seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.perf_counter() - t0,
           "stderr_tail": [ln for ln in p.stderr.splitlines() if "INFO:root" not in ln][-9:]}
    try:
        row["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        row["result"] = None
    return row


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--trace-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--sets", type=int, default=2)
    ns = ap.parse_args()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = ns.seconds if ns.seconds is not None else spec["run_seconds"]
    cmd = [sys.executable if w in ("python", "python3") else w for w in spec["command"]]
    out = {"workload": ns.workload, "seconds": seconds, "sets": [], "traced": []}
    for k in range(ns.sets):
        rows = [run_once(cmd, ns.workload, s, seconds, 0) for s in ns.seeds]
        out["sets"].append(rows)
        for r in rows:
            res = r["result"] or {}
            print("set", k, "seed", r["seed"], "rc", r["rc"], "correct", res.get("correct"),
                  {n: m["value"] for n, m in res.get("metrics", {}).items()},
                  res.get("compared"), flush=True)
    for s in ns.trace_seeds:
        r = run_once(cmd, ns.workload, s, seconds, 1)
        out["traced"].append(r)
        res = r["result"] or {}
        print("traced seed", s, "rc", r["rc"], "wall_s", round(r["wall_s"], 1), json.dumps(res), flush=True)
    summary = {}
    good = [[r["result"] for r in rows if r["result"]] for rows in out["sets"]]
    names = sorted({n for rows in good for res in rows for n in res["metrics"]})
    for n in names:
        per_set = [[res["metrics"][n]["value"] for res in rows] for rows in good]
        # the first run of the first set compiles: its set-up is recorded apart
        if n == "setup_s" and per_set and len(per_set[0]) > 3:
            per_set[0] = per_set[0][1:]
        meds = [statistics.median(v) for v in per_set if v]
        summary[n] = {
            "medians": meds,
            "spreads": [spread(v) for v in per_set if len(v) >= 2],
            "second_vs_first": (meds[1] - meds[0]) / meds[0] if len(meds) > 1 else None,
        }
        if summary[n]["spreads"]:
            summary[n]["widest_spread"] = max(summary[n]["spreads"])
    out["summary"] = summary
    print("summary", json.dumps(summary, indent=1), flush=True)
    outdir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"sets_{ns.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    bad = [r for rows in out["sets"] + [out["traced"]] for r in rows
           if r["rc"] != 0 or not (r["result"] or {}).get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
