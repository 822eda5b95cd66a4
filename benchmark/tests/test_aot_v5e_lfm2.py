"""The LFM2 cell's round and evaluation executables, compiled at full
size for a v5e that is described and not attached: does Mosaic take the
flash kernels (the forward and both backward ones) at 32/8 heads of 64
and T = 4,096, does the chip's compiler take the lane-after-lane round
(``lax.map`` over two lanes, a step loop of dynamic length around
rematerialised blocks, the ragged expert product unbatched), and does one
live lane of a 507.8M-parameter model fit 16 GB -- read here before any
chip minute is spent. Nothing runs, so nothing here is a time or a rate;
``memory_analysis()`` is printed for PERF.md.

The helpers are ``test_aot_v5e.py``'s. Run these files in one process
(``-p no:xdist``, or one ``pytest`` call a file): only one process at a
time may load libtpu.
"""

import json

import pytest

from test_aot_v5e import (  # noqa: F401  (fixtures)
    HBM_BYTES, _Caught, _built_driver, _catch_first_call, _compile_for, no_compile_cache,
    one_chip, topo,
)

pytestmark = pytest.mark.slow

CELL = "fedavg_lfm2_t4096"
# what the chip's allocator offers (``bytes_limit`` in ``memory_stats()``,
# read on the v5e: PERF.md section 7 j). ``memory_analysis()`` counts the
# lane loop's carried weight-sized buffers more than once and overstates
# this executable by GBs, so it is held against the chip's limit and not
# against ``HBM_BYTES``' round 16e9.
CHIP_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def stopped_api():
    """The program at the cell's sizes, built through the family's
    set-up on the CPU and stopped at its first ``train()``."""
    from fedml_tpu.simulation import fedavg_api

    cell, driver = _built_driver(CELL)
    caught, real = {}, fedavg_api.FedAvgAPI

    class Caught(real):
        def train(self):
            caught["api"] = self
            raise _Caught

    fedavg_api.FedAvgAPI = Caught
    try:
        with pytest.raises(_Caught):
            driver.setup()
    finally:
        fedavg_api.FedAvgAPI = real
    return cell, caught["api"], real.train


def test_round_executable_fits(stopped_api, one_chip, no_compile_cache):
    cell, api, real_train = stopped_api
    assert api._round_exec_name() == "simulation.round_fn_ragged"
    api.args.comm_round, api.args.frequency_of_the_test = 1, 1
    jitted, args, kwargs = _catch_first_call(api, "_round_fn", lambda: real_train(api))
    packed = args[2]
    assert packed.x.shape == (8, 7, 2, 4096) and str(packed.x.dtype) == "int32"
    assert kwargs["valid"].shape == (2,)  # the cohort's bucket: 2 lanes, none padded
    assert args[0]["layer_4"]["moe"]["gate_proj"].shape == (8, 2048, 1792)
    assert args[0]["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (2048, 7168)
    assert args[0]["embed"]["embedding"].shape == (16384, 2048) and "lm_head" not in args[0]
    compiled, sizes = _compile_for(one_chip, jitted, args, kwargs)
    print("AOT", CELL, "jit_round_fn", json.dumps(sizes))
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv", "flash_attention_bwd_dq"):
        assert kernel in text, kernel
    assert "ragged" in text  # the grouped product is the chip's ragged dot
    assert sizes["total"] < CHIP_BYTES_LIMIT
    # a deployment's fill: well over the contract's quarter of the chip
    assert sizes["total"] > 0.5 * HBM_BYTES


def test_eval_executable_fits(stopped_api, one_chip, no_compile_cache):
    cell, api, _ = stopped_api
    packed = api.dataset.packed_train
    _, sizes = _compile_for(one_chip, api._eval_all, (api.global_params, packed), {})
    print("AOT", CELL, "jit_eval_all", json.dumps(sizes))
    assert sizes["total"] < HBM_BYTES
