"""The comparison that decides ``correct`` has to fail what is wrong.

Each test skips the harness's look for a chip and drives the rest of a
run (``run.run_cell``) with the timed path broken underneath:

- a step that returns its state unchanged;
- half of every batch left out, the mean taken over the rest;
- for the LM, whose epoch executable the program compiles twice, the
  state left unchanged by every call but the trainer's first (a fault
  of the steady-state path alone), and an executable built inside the
  window.

(No cell of these families exchanges anything between chips, and a
training cell produces no token or answer to alter.) The control -- the
plain reference put in the program's place, computed in fp8, the
precision below the configurations' bfloat16 -- has to come out as not
correct too, by the same limits.
"""

import jax
import jax.numpy as jnp
import pytest

import controls
import harness
from conftest import run_cell


def _failed(res):
    return sorted(k for k, (value, limit) in res["compared"].items() if not value <= limit)


# -- faults planted in the program's executables -----------------------
def _break_fedavg(monkeypatch, wrap):
    from fedml_tpu.simulation import fedavg_api

    class Broken(fedavg_api.FedAvgAPI):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._round_fn = wrap(self._round_fn)

    monkeypatch.setattr(fedavg_api, "FedAvgAPI", Broken)


def _break_lm(monkeypatch, wrap):
    from fedml_tpu import distributed

    class Broken(distributed.DistributedTrainer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._epoch = wrap(self._epoch)

    monkeypatch.setattr(distributed, "DistributedTrainer", Broken)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def fedavg_state_unchanged(real):
    def round_fn(params, server_state, *rest, **kw):
        out = real(_copy(params), _copy(server_state), *rest, **kw)
        return (params, server_state) + tuple(out[2:])

    return round_fn


def fedavg_half_batch(real):
    def round_fn(params, server_state, packed, *rest, **kw):
        keep = (jnp.arange(packed.mask.shape[-1]) % 2 == 0).astype(packed.mask.dtype)
        return real(params, server_state, packed.replace(mask=packed.mask * keep), *rest, **kw)

    return round_fn


def lm_state_unchanged(real):
    def epoch(params, opt_state, batches, rng):
        _, _, sums = real(_copy(params), _copy(opt_state), batches, rng)
        return params, opt_state, sums

    return epoch


def lm_state_unchanged_after_first_call(real):
    """Sound on the trainer's first call (the first of the program's two
    epoch executables), broken on every later one: what the window runs."""
    calls = []

    def epoch(params, opt_state, batches, rng):
        calls.append(1)
        if len(calls) == 1:
            return real(params, opt_state, batches, rng)
        return lm_state_unchanged(real)(params, opt_state, batches, rng)

    return epoch


def lm_compiles_in_window(real):
    """From the trainer's fourth call on -- the window's first -- every
    call builds a new executable beside the real one."""
    calls = []

    def epoch(params, opt_state, batches, rng):
        calls.append(1)
        if len(calls) > 3:
            jax.jit(lambda x: x + len(calls))(jnp.zeros(()))
        return real(params, opt_state, batches, rng)

    return epoch


def lm_half_batch(real):
    def epoch(params, opt_state, batches, rng):
        keep = (jnp.arange(batches.mask.shape[-1]) % 2 == 0).astype(batches.mask.dtype)
        return real(params, opt_state, batches.replace(mask=batches.mask * keep), rng)

    return epoch


FAULTS = {
    "tiny_c4-state_unchanged": ("tiny_c4", _break_fedavg, fedavg_state_unchanged),
    "tiny_c4-half_batch": ("tiny_c4", _break_fedavg, fedavg_half_batch),
    "tiny_e3-state_unchanged": ("tiny_e3", _break_lm, lm_state_unchanged),
    "tiny_e3-half_batch": ("tiny_e3", _break_lm, lm_half_batch),
    "tiny_e3-state_unchanged_after_first_call": (
        "tiny_e3", _break_lm, lm_state_unchanged_after_first_call),
    "tiny_e3-compiles_in_window": ("tiny_e3", _break_lm, lm_compiles_in_window),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, tiny_root, narrow_resnet, monkeypatch):
    cell_name, breaker, wrap = FAULTS[fault]
    breaker(monkeypatch, wrap)
    _, res = run_cell(cell_name, tiny_root, monkeypatch, seed=11, seconds=0.5)
    assert res["correct"] is False
    failed = _failed(res)
    assert failed, res["compared"]
    if "state_unchanged" in fault:
        # a state left unchanged reads 1 by the training bullet's measure
        assert res["compared"]["change_norm_gap"][0] == pytest.approx(1.0, abs=1e-6)
    if fault.endswith("compiles_in_window"):
        assert failed == ["compiles_since_check"]


# -- the control: the reference in fp8 in the program's place ----------
@pytest.mark.parametrize("cell_name", ["tiny_c4", "tiny_e3"])
def test_control_is_not_correct(cell_name, tiny_root, narrow_resnet, monkeypatch):
    cell = harness.Cell(cell_name, root=tiny_root)
    driver = cell.family_module().Driver(cell, 12)
    driver.setup()
    driver.release()
    want = driver.reference_numbers()
    limits = cell.config["limits"]
    sound = driver.gaps(driver.observed, want)
    assert all(sound[k] <= limits[k] for k in limits), sound
    control = driver.gaps(driver.reference_numbers(quant=controls.FP8), want)
    assert any(control[k] > limits[k] for k in limits), control
    half = driver.gaps(driver.reference_numbers(row_keep=2), want)
    assert any(half[k] > limits[k] for k in limits), half
