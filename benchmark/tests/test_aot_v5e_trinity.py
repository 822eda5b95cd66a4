"""The Trinity-Mini cell's round and evaluation executables, compiled at
full size for a v5e that is described and not attached: does Mosaic
take both flash kernels (the windowed ones at a window of 2,048 and the
full ones, forward and both backward) at 32/4 heads of 128 and T =
8,192 inside the lane-after-lane round, and what does
``memory_analysis()`` read -- without a chip attached. Nothing
runs, so nothing here is a time or a rate; the sizes are printed for
PERF.md.

``memory_analysis()`` of a described-v5e compile overstates the lane
loop by ~5 GB (PERF.md section 7 j), so the round is held against
nothing here: if the compile refuses the round for memory that is the
host compile's count, the refusal is printed, and the chip decides the
cell's size.

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

CELL = "fedavg_trinity_mini_t8192"
FLASH = ("flash_attention_fwd", "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
         "flash_attention_window_fwd", "flash_attention_window_bwd_dkv", "flash_attention_window_bwd_dq")


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


def test_round_executable(stopped_api, one_chip, no_compile_cache):
    cell, api, real_train = stopped_api
    assert api._round_exec_name() == "simulation.round_fn_ragged"
    api.args.comm_round, api.args.frequency_of_the_test = 1, 1
    jitted, args, kwargs = _catch_first_call(api, "_round_fn", lambda: real_train(api))
    packed, params = args[2], args[0]
    assert packed.x.shape == (8, 7, 1, 8192) and str(packed.x.dtype) == "int32"
    assert kwargs["valid"].shape == (2,)  # the cohort's bucket: 2 lanes, none padded
    assert params["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (2048, 6144)
    assert params["layer_1"]["attn"]["gate_proj"]["kernel"].shape == (2048, 4096)
    assert params["layer_1"]["moe"]["up_proj"].shape == (8, 2048, 1024)
    assert params["layer_1"]["moe"]["shared"]["gate_proj"]["kernel"].shape == (2048, 1024)
    assert {"attn_post_norm", "ffn_post_norm"} <= set(params["layer_2"])
    assert params["lm_head"]["kernel"].shape == (2048, 25024)
    try:
        compiled, sizes = _compile_for(one_chip, jitted, args, kwargs)
    except Exception as e:  # the described chip's allocator: the host compile's count, not the chip's
        if "memory" not in str(e).lower() and "RESOURCE_EXHAUSTED" not in str(e):
            raise
        print("AOT", CELL, "jit_round_fn REFUSED", str(e)[:2000])
        pytest.skip("the described-v5e compile refuses the round for memory: the chip decides")
    print("AOT", CELL, "jit_round_fn", json.dumps(sizes))
    text = compiled.as_text()
    for kernel in FLASH:
        assert kernel in text, kernel
    assert "blk.attn.gate" in text and "blk.post_norm" in text
    # a deployment's fill: well over the contract's quarter of the chip
    assert sizes["total"] > 0.5 * HBM_BYTES


def test_eval_executable(stopped_api, one_chip, no_compile_cache):
    cell, api, _ = stopped_api
    train, _ = api._eval_splits()
    compiled, sizes = _compile_for(one_chip, api._eval_all, (api.global_params, train), {})
    print("AOT", CELL, "jit_eval_all", json.dumps(sizes))
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_window_fwd" in text
    assert sizes["total"] < HBM_BYTES
