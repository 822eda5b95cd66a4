"""CPU rehearsals of a whole run at tiny size, one per family.

``run.run_cell`` is everything but the look for a chip: set-up, the
window, release, the reduction to metrics and the comparison with the
plain reference. The chip's cells differ from these only in the data
files ``BENCHMARK.json`` names.
"""

import json
import math
import os
import subprocess
import sys

import pytest

import harness
from conftest import CHECKOUT, run_cell

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _check_line(cell, res, names):
    line = json.loads(json.dumps(res))  # what main() prints
    assert list(line)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS
    assert list(line)[-1] == "compared"  # the numbers compared come last
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["metrics"]) == sorted(names)
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer") for m in cell.spec[g]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert math.isfinite(m["value"]) and m["value"] >= 0
    for value, limit in line["compared"].values():
        assert value <= limit
    return line


@pytest.mark.parametrize("cell_name", ["tiny_c4", "tiny_e3"])
def test_end_to_end_line(cell_name, tiny_root, narrow_resnet, monkeypatch):
    cell, res = run_cell(cell_name, tiny_root, monkeypatch)
    line = _check_line(cell, res, [m["name"] for m in cell.end_to_end])
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "breakdown" not in line


@pytest.mark.parametrize("cell_name", ["tiny_c4", "tiny_e3"])
def test_traced_line(cell_name, tiny_root, narrow_resnet, monkeypatch):
    """A traced run reports the per-layer metrics whose readers find
    something to read: on a CPU the trace holds no TPU plane, so the
    readers of device time return nothing and are left out -- never 0."""
    cell, res = run_cell(cell_name, tiny_root, monkeypatch, trace=True)
    declared = [m["name"] for m in cell.per_layer]
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) <= set(declared)
    from_trace = {
        "round_fn_device_ms", "eval_device_ms", "lm_step_device_ms", "flash_fwd_roofline",
        "device_idle_pct.fedavg", "device_idle_pct.lm",
        "peak_hbm_pct.fedavg", "peak_hbm_pct.lm",  # no memory_stats() on a CPU either
    }
    assert set(declared) - set(line["metrics"]) <= from_trace
    assert set(line["metrics"]) & {"mfu_pct.fedavg", "mfu_pct.lm"}
    assert {"device_ops", "idle_gaps"} == set(line["breakdown"])
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError, match="not in benchmark/peaks.json"):
        harness.peaks_for("TPU v9 imaginary")


def test_no_chip_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "benchmark", "run.py"), "--workload",
         "fedavg_r18_c32", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=CHECKOUT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` the command exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        os.path.join(CHECKOUT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fedavg_r18_c32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not in this checkout" in p.stderr
