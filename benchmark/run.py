#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic, builds the program through
its normal entry, warms up only that cell's shapes (set-up), measures
for ``--seconds``, then frees the program and checks what the timed
path produced against the plain reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` when traced, and ``compared``
(each number compared, beside its limit) last. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import json
import math
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

import harness  # noqa: E402
from harness import BenchError  # noqa: E402

TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else 1e30


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float) -> dict:
    """Set-up, window, release, comparison: everything but the look for
    a chip. Returns the result object."""
    import jax

    peaks = harness.peaks_for(devices[0].device_kind)
    harness.compile_count()  # starts the count of executables built
    driver = cell.family_module().Driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's spans, not every Python call
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles = harness.compile_count()
    try:
        win = driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = harness.compile_count() - compiles
    device = harness.device_facts(devices)
    facts = driver.facts()
    driver.release()

    metrics = {}
    result = {"correct": False, "attempted": int(win["units"]), "failed": int(win["failed"])}
    if not trace:
        values = {"setup_s": setup_s, **driver.end_to_end(win)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        import reduce_trace

        summary = reduce_trace.reduce_dir(
            trace_dir, host_spans=cell.traffic.get("host_spans", []),
            kernel_names=cell.traffic.get("kernel_names", []))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = float(win["wall_s"])
        ctx = {
            "cell": cell, "window": win, "trace": summary, "facts": facts,
            "peaks": peaks, "device": device, "flops": cell.flops_module(),
            "setup_s": setup_s,
        }
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": summary["top_ops"][:10],
            "idle_gaps": summary["top_gaps"][:10],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device

    compared = harness.Compared()
    t_ref = time.perf_counter()
    driver.compare(compared)
    print("spans " + json.dumps({
        **{k: round(v, 3) for k, v in facts["spans"].items()},
        "setup_s": round(setup_s, 3), "window_s": round(win["wall_s"], 3),
        "executables_built_in_window": compiles,
        "call_s": [round(c, 3) for c in win.get("call_s", [])],
        "reference_s": round(time.perf_counter() - t_ref, 3)}), file=sys.stderr, flush=True)
    if win["failed"]:
        compared.add("failed_units", float(win["failed"]), 0.0)
    result["correct"] = compared.correct
    for line in compared.lines():
        print(line, file=sys.stderr, flush=True)
    result["compared"] = {k: [_finite(v), lim] for k, (v, lim) in compared.as_dict().items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=None, help=(
        "the directory whose BENCHMARK.json names the cell (this checkout's, "
        "unless a tool such as tools/with_left_out.py wrote another)"))
    ns = ap.parse_args(argv)

    try:
        import fedml_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e}) -- no result", file=sys.stderr)
        return 2
    # the program takes its compile cache from this variable and then
    # sets no other directory in code; a fixed path inside the checkout
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["FEDML_TPU_NO_NATIVE"] = "1"
    try:
        cell = harness.Cell(ns.workload, root=ns.root)
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise BenchError(
                f"needs a TPU, JAX found platform {devices[0].platform!r} -- no result")
        if len(devices) < cell.chips:
            raise BenchError(
                f"cell {cell.name} asks for {cell.chips} chips, JAX found {len(devices)} -- no result")
        devices = devices[: cell.chips]
        result = run_cell(cell, ns.seed, ns.seconds, bool(ns.trace), devices, _T_START)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
