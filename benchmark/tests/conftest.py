"""The benchmark's own tests run on the CPU at tiny sizes.

They rehearse the harness (control flow, the result's shape, the
comparison that decides ``correct``); a time, a rate or a utilization
comes only from a chip run. The platform is pinned before JAX is
imported, as ``tests/conftest.py`` does for the program's tests.
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["FEDML_TPU_NO_NATIVE"] = "1"

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS_DIR)
CHECKOUT = os.path.dirname(BENCH_DIR)
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
from with_left_out import full_spec  # noqa: E402  (the committed file plus the left-out cells)

TINY_DIR = os.path.join(TESTS_DIR, "tiny")
# what a CPU stands in for where a reader divides by a peak
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def tiny_spec() -> dict:
    """``full_spec()`` with two tiny cells added the way a later PR
    adds one: new files in a directory of their own and new entries,
    nothing that is there edited."""
    spec = full_spec(absolute=True)
    spec["paths"] = [TINY_DIR] + spec["paths"]
    like = {"tiny_c4": "fedavg_r18_c32", "tiny_e3": "gpt2_b8_t1024"}
    for name, cfg in (("tiny_fedavg", "tiny_c4"), ("tiny_lm", "tiny_e3")):
        spec["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "CPU rehearsal",
            "file": os.path.join(TINY_DIR, "configs", name + ".json")})
        spec["workloads"].append({
            "name": cfg, "config": name, "traffic": cfg, "chips": 1, "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            for tiny, full in like.items():
                if full in m.get("workloads", []):
                    m["workloads"].append(tiny)
    return spec


def run_cell(cell_name, root, monkeypatch, seed=2 ** 31 + 7, seconds=1.0, trace=False):
    """Everything of a run but the look for a chip, on one CPU device."""
    import time

    import harness
    import run

    monkeypatch.setattr(harness, "peaks_for", lambda kind: dict(FAKE_PEAKS))
    cell = harness.Cell(cell_name, root=root)
    return cell, run.run_cell(cell, seed, seconds, trace, jax.devices()[:1], time.perf_counter())


@pytest.fixture
def full_root(tmp_path):
    """A checkout root whose ``BENCHMARK.json`` holds the left-out
    cells too, over the committed files."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(full_spec(absolute=True), f)
    return str(tmp_path)


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding the tiny ``BENCHMARK.json``."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(tiny_spec(), f)
    return str(tmp_path)


@pytest.fixture(autouse=True)
def _reset_program_singletons():
    """The program's tracking/telemetry singletons are process-wide."""
    prev = jax.config.jax_threefry_partitionable
    yield
    from fedml_tpu.core import devtime
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.core.tracking import ProfilerEvent, RunLogger

    Telemetry.reset()
    devtime.reset()
    ProfilerEvent.reset()
    RunLogger.reset()
    if jax.config.jax_threefry_partitionable != prev:
        jax.config.update("jax_threefry_partitionable", prev)


@pytest.fixture
def narrow_resnet(monkeypatch):
    """Hand the program the tiny configuration's ResNet: the test steers
    the program here, the program has no option for it."""
    from fedml_tpu import models
    from fedml_tpu.models.resnet import ResNet

    with open(os.path.join(TINY_DIR, "configs", "tiny_fedavg.json")) as f:
        model = json.load(f)["model"]

    def tiny(output_dim):
        return ResNet(
            stage_sizes=tuple(model["stage_sizes"]),
            stage_channels=tuple(model["stage_channels"]),
            output_dim=output_dim, stem_kernel=model["stem_kernel"], stem_pool=False)

    monkeypatch.setattr(models, "resnet18_gn", tiny)
