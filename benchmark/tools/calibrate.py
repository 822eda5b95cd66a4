#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, many seeds a process.

    python benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \
        [--set model.init_gn_scale=1.0] \
        [--witness-set program_args.dtype=float32 program_args.matmul_precision=highest]

For every seed: build the cell as ``run.py`` does, drive the check's steps through the program, free it, follow
the same steps with the plain reference, and print the gap of each
number compared. For the control seeds also the control (the reference
in fp8, ``controls.py``) against the reference; for the fault seeds the
reference with half of every batch left out. ``--set`` changes keys of
the configuration for the program and the reference alike (another
regime); ``--witness-set`` builds the program a second time with those
keys changed for it alone (a higher precision) and reads it against the
same reference: where the program as configured departs from the
reference and the witness does not, the departure is rounding. Only the
check's drive is read, so the warm-up call is cut to one round. Writes
``chiprun_out/calibrate_<cell>[_<tag>].json``. Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, CHECKOUT]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def _pair(s):
    key, value = s.split("=", 1)
    return key, value


def _with(cell, pairs):
    """A copy of the cell whose configuration has ``a.b=value`` set."""
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    for dotted, value in pairs:
        node, keys = cell.config, dotted.split(".")
        for k in keys[:-1]:
            node = node[k]
        try:
            node[keys[-1]] = json.loads(value)
        except ValueError:
            node[keys[-1]] = value
    return cell


def _observe(cell, seed):
    driver = cell.family_module().Driver(cell, seed)
    driver.setup()
    got = driver.observed
    driver.release()
    return driver, got


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--set", type=_pair, nargs="+", default=[], dest="sets")
    ap.add_argument("--witness-set", type=_pair, nargs="+", default=[])
    ap.add_argument("--tag", default="")
    ap.add_argument("--leaves", type=int, default=0)
    ap.add_argument("--root", default=None)
    ns = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    import controls
    import harness

    cell = _with(harness.Cell(ns.workload, root=ns.root), ns.sets)
    cell.traffic = dict(cell.traffic, rounds_per_call=1, epochs_per_call=1)
    rows = []
    for seed in ns.seeds:
        t0 = time.perf_counter()
        driver, got = _observe(cell, seed)
        t1 = time.perf_counter()
        want = driver.reference_numbers()
        t2 = time.perf_counter()
        row = {"seed": seed, "set": dict(ns.sets), "program": driver.gaps(got, want),
               "setup_s": t1 - t0, "reference_s": t2 - t1}
        if ns.witness_set:
            row["witness_set"] = dict(ns.witness_set)
            try:
                _, seen = _observe(_with(cell, ns.witness_set), seed)
                row["witness"] = driver.gaps(seen, want)
            except Exception as e:  # a witness that does not fit says so and the row stays
                seen, row["witness_error"] = None, f"{type(e).__name__}: {e}"[:2000]
            t2 = time.perf_counter()
        if seed in ns.control_seeds:
            row["control_fp8"] = driver.gaps(
                driver.reference_numbers(quant=controls.FP8), want)
            row["control_s"] = time.perf_counter() - t2
        if seed in ns.fault_seeds:
            row["fault_half_batch"] = driver.gaps(driver.reference_numbers(row_keep=2), want)
        # each loss beside the reference's, for a gap that swings
        plain = [k for k, v in want.items() if k in got and (
            isinstance(v, float) or (isinstance(v, list) and all(isinstance(x, float) for x in v)))]
        row["read"] = {k: {"program": got[k], "reference": want[k]} for k in plain}
        if ns.witness_set and seen is not None:
            for k in plain:
                row["read"][k]["witness"] = seen[k]
        if hasattr(driver, "compiles_at_check"):
            # executables the check's drive built: the program compared
            # is the warmed one only where this is 0
            row["compiles_in_check_drive"] = driver.compiles_after_check - driver.compiles_at_check
        if ns.leaves:
            # per-leaf norms, for whoever looks into a gap that swings
            row["leaves"] = {
                k: {"program": [float(v) for v in got[k]], "reference": [float(v) for v in want[k]]}
                for k in want if k.endswith("_norms")}
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "leaves"}), flush=True)
        del driver, got, want
    out = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = "_" + ns.tag if ns.tag else ""
    with open(os.path.join(out, f"calibrate_{cell.name}{tag}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
