"""Each cell's main executable, compiled at full size for a v5e that is
described and not attached (``jax.experimental.topologies``): what the
chip's compiler refuses -- a kernel Mosaic rejects, a program that does
not fit 16 GB -- fails here at no chip time. Nothing runs, so nothing
here is a time or a rate; ``memory_analysis()`` is printed for PERF.md.

The program is built on the CPU through its normal entry at the cell's
real sizes, the executable's arguments are caught at its first call and
the call abandoned, and the same jitted function is lowered for the TPU
from their shapes. The topology is described inside a fixture, never at
import, and every such test lives in this one file (one worker loads
libtpu; see the on-chip-measurement guide).
"""

import json

import jax
import pytest

import harness
from conftest import CHECKOUT

pytestmark = pytest.mark.slow

HBM_BYTES = 16e9


class _Caught(Exception):
    pass


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _catch_first_call(owner, attr, drive):
    """The positional and keyword arguments of ``owner.<attr>``'s first
    call under ``drive()``, the call itself abandoned."""
    real, seen = getattr(owner, attr), {}

    def catcher(*args, **kwargs):
        seen["args"], seen["kwargs"] = args, kwargs
        raise _Caught

    setattr(owner, attr, catcher)
    try:
        with pytest.raises(_Caught):
            drive()
    finally:
        setattr(owner, attr, real)
    return real, seen["args"], seen["kwargs"]


def _compile_for(one_chip, jitted, args, kwargs):
    def abstract(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        return a

    args, kwargs = jax.tree.map(abstract, (args, kwargs))
    compiled = jitted.trace(*args, **kwargs).lower(lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    sizes = {
        k: int(getattr(mem, k + "_size_in_bytes"))
        for k in ("argument", "output", "alias", "temp", "generated_code")}
    sizes["total"] = sizes["argument"] + sizes["output"] - sizes["alias"] + sizes["temp"]
    return compiled, sizes


def _built_driver(cell_name, root=CHECKOUT):
    cell = harness.Cell(cell_name, root=root)
    driver = cell.family_module().Driver(cell, 1)
    return cell, driver


@pytest.mark.parametrize("cell_name", ["fedavg_r18_c32", "fedavg_r18_c32_eval1"])
def test_fedavg_round_executable(cell_name, one_chip, no_compile_cache, monkeypatch):
    from fedml_tpu.simulation import fedavg_api

    cell, driver = _built_driver(cell_name)
    caught, real_train = {}, fedavg_api.FedAvgAPI.train

    class Caught(fedavg_api.FedAvgAPI):
        def train(self):
            caught["api"] = self
            raise _Caught

    monkeypatch.setattr(fedavg_api, "FedAvgAPI", Caught)
    with pytest.raises(_Caught):
        driver.setup()  # the program at the cell's sizes, stopped at its first train()
    api = caught["api"]
    api.args.comm_round, api.args.frequency_of_the_test = 1, 1
    jitted, args, kwargs = _catch_first_call(api, "_round_fn", lambda: real_train(api))
    packed = args[2]
    assert packed.x.shape == (100, 15, 64, 32, 32, 3) and str(packed.x.dtype) == "bfloat16"
    assert kwargs["valid"].shape == (32,)  # the cohort's bucket: 32 lanes, none padded
    _, sizes = _compile_for(one_chip, jitted, args, kwargs)
    print("AOT", cell_name, "jit_round_fn", json.dumps(sizes))
    assert sizes["total"] < HBM_BYTES


def test_gpt2_epoch_executable(one_chip, no_compile_cache, full_root, monkeypatch):
    """The cell PR 24 left out (``left_out/gpt2_b8_t1024.json``), so
    that it still compiles for the chip when a PR brings it back."""
    from fedml_tpu import distributed

    cell, driver = _built_driver("gpt2_b8_t1024", root=full_root)
    caught, real_run = {}, distributed.DistributedTrainer.run

    class Caught(distributed.DistributedTrainer):
        def run(self):
            caught["trainer"] = self
            raise _Caught

    monkeypatch.setattr(distributed, "DistributedTrainer", Caught)
    with pytest.raises(_Caught):
        driver.setup()
    trainer = caught["trainer"]
    trainer.args.epochs = 1
    jitted, args, kwargs = _catch_first_call(trainer, "_epoch", lambda: real_run(trainer))
    assert args[2].x.shape == (16, 8, 1024)
    assert args[0]["Dense_0"]["kernel"].shape == (768, 50257)  # GPT-2's own vocabulary
    compiled, sizes = _compile_for(one_chip, jitted, args, kwargs)
    print("AOT gpt2_b8_t1024 jit_epoch", json.dumps(sizes))
    assert sizes["total"] < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernel went through Mosaic
