#!/usr/bin/env python3
"""Read the numbers a ``fedavg_lm`` cell's limits are set from.

    python benchmark/tools/calibrate_lm.py --workload <cell> --seeds 1,2

``calibrate.py`` for the language-model family, whose reference costs
minutes a drive on the chip. The program's own gaps are read from the
cell's runs (``compared`` in each result line); this tool reads the
other side, and builds none of the program's executables: for every
seed the control (the reference in fp8, ``controls.py``) and each
planted fault (half of every batch left out, the window ignored, the
top-k renormalisation dropped, YaRN left off) against the reference,
over the check's own three rounds (``Driver.reference_numbers``): the
training losses, the first update's norms and the norms of the change
after all three, and the evaluation losses where the configuration
limits ``eval_gap``.
``--plants`` picks among them: a plant is a compile and three rounds
of the reference, minutes on the chip. Writes
``chiprun_out/calibrate_lm_<cell>.json``.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, CHECKOUT]

PLANTS = {
    "control_fp8": lambda controls: {"quant": controls.FP8},
    "fault_half_batch": lambda controls: {"row_keep": 2},
    "fault_no_window": lambda controls: {"fault": "no_window"},
    "fault_no_renorm": lambda controls: {"fault": "no_renorm"},
    "fault_no_yarn": lambda controls: {"fault": "no_yarn"},
}


def main() -> int:
    ints = lambda s: [int(x) for x in s.split(",") if x]
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--plants", default=",".join(PLANTS))
    ap.add_argument("--root", default=None)
    ns = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    import controls
    import harness

    cell = harness.Cell(ns.workload, root=ns.root)
    rows = []
    for seed in ns.seeds:
        driver = cell.family_module().Driver(cell, seed)
        driver.load_data()
        t0 = time.perf_counter()
        want = driver.reference_numbers()
        row = {"seed": seed, "reference": {"loss": want["loss"], "eval_test": want["eval_test"]},
               "reference_s": time.perf_counter() - t0}
        for name in ns.plants.split(","):
            t0 = time.perf_counter()
            got = driver.reference_numbers(**PLANTS[name](controls))
            row[name] = driver.gaps(got, want)
            row[name].update(loss=got["loss"], seconds=time.perf_counter() - t0)
            print(json.dumps({"seed": seed, name: row[name]}), flush=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
        del driver, want
    out = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"calibrate_lm_{cell.name}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
