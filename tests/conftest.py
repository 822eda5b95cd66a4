"""Test harness: force an 8-device virtual CPU mesh.

The tests run on the CPU (the chip is reached through chip_smoke.py
and benchmark/run.py, never through pytest); per the reference's own pattern of
running every scenario single-host (SURVEY.md §4 "multi-node without a
cluster"), all sharding tests run on
``--xla_force_host_platform_device_count=8`` CPU devices. The platform
is pinned through jax.config as well as the environment, so a test
process can never reach for an accelerator.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_observability_singletons():
    """The tracking/telemetry singletons are process-wide; without a
    reset, one test's args (or counters, heartbeats, watchdog) leak
    into every later test in the worker."""
    prev_threefry = jax.config.jax_threefry_partitionable
    yield
    from fedml_tpu.core import devtime
    from fedml_tpu.core.chaos import reset_chaos
    from fedml_tpu.core.telemetry import Telemetry
    from fedml_tpu.core.tracking import ProfilerEvent, RunLogger

    Telemetry.reset()
    devtime.reset()
    ProfilerEvent.reset()
    RunLogger.reset()
    # the chaos plane (schedule + durable-IO seam) is process-global
    reset_chaos()
    # building a fed (data, fsdp) mesh flips jax_threefry_partitionable
    # process-wide (sharding-invariant random draws); restore it so a
    # mesh test can never shift another test's seeded stream
    if jax.config.jax_threefry_partitionable != prev_threefry:
        jax.config.update("jax_threefry_partitionable", prev_threefry)


@pytest.fixture
def compile_cache_reset():
    """core/compile_cache.py is process-scoped on purpose; tests that
    enable it put its bookkeeping back (jax.config's cache dir is
    cleared too so later tests never write into a deleted tmpdir)."""
    yield
    from fedml_tpu.core import compile_cache

    if compile_cache._enabled_dir is not None:
        from jax.experimental.compilation_cache import compilation_cache as jcc

        jax.config.update("jax_compilation_cache_dir", None)
        # jax pins its persistent-cache object to the first directory
        # it initialized with; drop it so the next test's enable takes
        # a fresh tmpdir (production never switches — one directory
        # per process by design)
        jcc.reset_cache()
    compile_cache._enabled_dir = None
    compile_cache._warned_conflict = False
    # what enabling sets beside the directory goes back to JAX's own
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    jax.config.update("jax_traceback_in_locations_limit", 10)
    compile_cache._counts.update(hits=0, misses=0)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


def make_args(**kw):
    """Small helper to build Arguments without YAML."""
    from fedml_tpu.arguments import Arguments

    a = Arguments()
    for k, v in kw.items():
        setattr(a, k, v)
    a._validate()
    return a


@pytest.fixture
def args_factory():
    return make_args
