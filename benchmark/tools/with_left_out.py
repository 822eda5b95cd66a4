#!/usr/bin/env python3
"""``BENCHMARK.json`` with the cells under ``benchmark/left_out/`` added.

    python benchmark/tools/with_left_out.py <directory>

writes the committed file plus every left-out cell's entries -- what the
PR that brings such a cell back will commit -- to
``<directory>/BENCHMARK.json``, its paths made absolute so that the
directory serves as a ``root`` beside this checkout's files. The
benchmark's tests build their specs with ``full_spec``; on the chip it
lets a left-out cell be run without touching the committed file.
"""
from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def full_spec(absolute: bool = False) -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    left_dir = os.path.join(BENCH_DIR, "left_out")
    for name in sorted(os.listdir(left_dir)):
        with open(os.path.join(left_dir, name)) as f:
            left = json.load(f)
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            spec[group] += left[group]
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in left["joins"]:
                m["workloads"] += [w["name"] for w in left["workloads"]]
    if absolute:
        spec["paths"] = [os.path.join(CHECKOUT, p) for p in spec["paths"]]
        for c in spec["configs"]:
            c["file"] = os.path.join(CHECKOUT, c["file"])
    return spec


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    with open(os.path.join(sys.argv[1], "BENCHMARK.json"), "w") as f:
        json.dump(full_spec(absolute=True), f, indent=1)
