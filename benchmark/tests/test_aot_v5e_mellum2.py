"""The Mellum2 cell's round and evaluation executables, compiled at full
size for a v5e that is described and not attached: does Mosaic take the
windowed grouped-KV flash kernel at 32/4 heads of 128 and T = 4,096,
does the chip's compiler take the ragged expert product one lane after
another, and do two lanes of a 340M-parameter model fit 16 GB -- read
here before any chip minute is spent. Nothing runs, so nothing here is a
time or a rate; ``memory_analysis()`` is printed for PERF.md.

``test_aot_v5e.py`` holds the other cells' compiles and may not be
edited by the PR that added this cell; the helpers are imported from it.
Both files describe the topology inside a fixture. Run them in one
process (``-p no:xdist``, or one ``pytest`` call a file): only one
process at a time may load libtpu.
"""

import json

import pytest

from test_aot_v5e import (  # noqa: F401  (fixtures)
    HBM_BYTES, _Caught, _built_driver, _catch_first_call, _compile_for, no_compile_cache,
    one_chip, topo,
)

pytestmark = pytest.mark.slow

CELL = "fedavg_mellum2_c2_t4096"


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
    api.args.comm_round, api.args.frequency_of_the_test = 1, 1
    jitted, args, kwargs = _catch_first_call(api, "_round_fn", lambda: real_train(api))
    packed = args[2]
    assert packed.x.shape == (8, 4, 4, 4096) and str(packed.x.dtype) == "int32"
    assert kwargs["valid"].shape == (2,)  # the cohort's bucket: 2 lanes, none padded
    assert args[0]["layer_3"]["moe"]["gate_proj"].shape == (8, 2304, 896)
    assert args[0]["lm_head"]["kernel"].shape == (2304, 12288)
    compiled, sizes = _compile_for(one_chip, jitted, args, kwargs)
    print("AOT", CELL, "jit_round_fn", json.dumps(sizes))
    text = compiled.as_text()
    assert "flash_attention_window_fwd" in text and "flash_attention_fwd" in text
    assert "ragged" in text  # the grouped product is the chip's ragged dot
    assert sizes["total"] < HBM_BYTES
    # a deployment's fill: well over the contract's quarter of the chip
    assert sizes["total"] > 0.5 * HBM_BYTES


def test_eval_executable_fits(stopped_api, one_chip, no_compile_cache):
    cell, api, _ = stopped_api
    packed = api.dataset.packed_train
    _, sizes = _compile_for(one_chip, api._eval_all, (api.global_params, packed), {})
    print("AOT", CELL, "jit_eval_all", json.dumps(sizes))
    assert sizes["total"] < HBM_BYTES
