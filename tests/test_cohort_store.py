"""The round executable gathers its cohort from a client-major sample
store (ISSUE 27; ``core/sample_store.py``).

CPU, tiny sizes: a round through the store is the round through the
image-shaped ``take`` bit for bit, the store is made once per dataset
and outside every round, and the layout check pins or warns. What the
chip's compiler makes of the two gathers is the slow test's at the end
(a described v5e, nothing runs) and the benchmark's to time.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core import sample_store
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI, fedavg_api
from fedml_tpu.simulation.fedavg_api import FedOptAPI, build_round_fn
from tests.conftest import make_args

IMAGES = dict(dataset="mnist", synthetic_train_size=160, synthetic_test_size=40)
FLAT = dict(dataset="synthetic", synthetic_train_size=96, synthetic_test_size=32)


def _world(cls=FedAvgAPI, **kw):
    base = dict(
        model="lr", client_num_in_total=6, client_num_per_round=3,
        partition_method="hetero", comm_round=2, epochs=1, batch_size=8,
        learning_rate=0.1, frequency_of_the_test=1,
    )
    base.update(kw)
    args = fedml_tpu.init(make_args(**base))
    ds = load(args)
    return cls(args, None, ds, models.create(args, ds.class_num))


def _staged(api):
    return [e for e in api.telemetry.recorder.tail(10_000) if e["name"] == "store.staged"]


# -- the same round, bit for bit -----------------------------------------

WORLDS = pytest.mark.parametrize("cls, data, model, valid", [
    (FedAvgAPI, IMAGES, "cnn", None),
    (FedAvgAPI, IMAGES, "lr", (1.0, 1.0, 1.0, 0.0)),
    (FedOptAPI, IMAGES, "lr", (1.0, 1.0, 1.0, 0.0)),
    (FedAvgAPI, FLAT, "lr", (1.0, 1.0, 1.0, 0.0)),
], ids=["images", "images_padded_lanes", "images_padded_lanes_fedopt", "flat"])


@WORLDS
def test_round_through_store_equals_round_through_take(cls, data, model, valid):
    api = _world(cls, model=model, server_optimizer="adam", **data)
    packed = api.dataset.packed_train
    # the round as it was: the dataset's own arrays, nothing reshaped
    plain = jax.jit(build_round_fn(
        api._local_train, api._aggregate, api._preprocess, sample_shape=None))
    call = (jnp.asarray(api.dataset.packed_num_samples),
            jnp.asarray([4, 1, 5, 1], jnp.int32), jax.random.PRNGKey(7))
    kwargs = {} if valid is None else {"valid": jnp.asarray(valid)}
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731  (_round_fn donates)
    want = plain(copy(api.global_params), copy(api.server_state), packed, *call, **kwargs)
    got = api._round_fn(
        copy(api.global_params), copy(api.server_state), api._sample_store(), *call, **kwargs)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert bool(jax.tree.leaves(want[1])) == (cls is FedOptAPI)  # a server state to compare
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@WORLDS
def test_ragged_round_through_store_equals_static_round_through_take(
        cls, data, model, valid, monkeypatch):
    """The same worlds with their lane-step counted heavy (ISSUE 31):
    lanes one after another, each to its last real batch, through the
    store, against the plain static round above. Every lane's trained
    parameters bit for bit; what is summed over lanes (the global
    parameters, the server's moments, the metrics) within float32
    rounding of the sum's order."""
    monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)
    api = _world(type("Stacked", (cls,), {"_keep_stacked": True}),
                 model=model, server_optimizer="adam", **data)
    assert api._ragged
    plain = jax.jit(build_round_fn(
        api._local_train, api._aggregate, api._preprocess, sample_shape=None,
        keep_stacked=True))
    call = (jnp.asarray(api.dataset.packed_num_samples),
            jnp.asarray([4, 1, 5, 1], jnp.int32), jax.random.PRNGKey(7))
    kwargs = {} if valid is None else {"valid": jnp.asarray(valid)}
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731  (_round_fn donates)
    want = plain(copy(api.global_params), copy(api.server_state), api.dataset.packed_train,
                 *call, **kwargs)
    got = api._round_fn(
        copy(api.global_params), copy(api.server_state), api._sample_store(), *call, **kwargs)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got[3]), jax.tree.leaves(want[3])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    run, packed_to = float(got[2].pop("steps_run")), float(want[2].pop("steps_run"))
    assert run <= packed_to and (valid is None or run < packed_to)  # a padded lane runs no step
    for a, b in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want[:3])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


def test_identity_branch_is_the_datasets_own_arrays():
    api = _world(**FLAT)
    assert sample_store.sample_shape(api.dataset.packed_train) is None
    assert api._sample_store() is api.dataset.packed_train
    (e,) = _staged(api)
    assert e["args"]["copied"] is False
    assert e["args"]["shape"] == list(api.dataset.packed_train.x.shape)


def test_staged_branch_shares_labels_and_masks():
    api = _world(**IMAGES)
    packed, store = api.dataset.packed_train, api._sample_store()
    assert sample_store.sample_shape(packed) == (28, 28, 1)
    assert store.x.shape == packed.x.shape[:3] + (784,)
    assert store.y is packed.y and store.mask is packed.mask
    assert np.array_equal(np.asarray(store.x), np.asarray(packed.x).reshape(store.x.shape))
    assert not packed.x.is_deleted()  # evaluation still reads it


# -- made once, outside every round --------------------------------------

class _KeepStacked(FedAvgAPI):
    _keep_stacked = True  # the synchronous loop


@pytest.mark.parametrize("cls", [FedAvgAPI, _KeepStacked], ids=["pipeline", "sync_loop"])
def test_two_trains_stage_once_and_a_new_dataset_stages_again(cls):
    api = _world(cls, **IMAGES)
    assert api._store_stagings == 0  # nothing before the first train()
    api.train()
    api.train()
    assert api.pipeline_stats["store_stagings"] == 1
    (e,) = _staged(api)
    x = api.dataset.packed_train.x
    assert e["args"] == {
        "bytes": x.nbytes, "shape": list(x.shape[:3]) + [784],
        "major_to_minor": [0, 1, 2, 3], "copied": True}
    packed = api.dataset.packed_train
    api.dataset = dataclasses.replace(api.dataset, packed_train=packed.replace(x=packed.x + 0))
    api.train()
    assert api.pipeline_stats["store_stagings"] == 2
    assert len(_staged(api)) == 2
    # each staging lies before its call's first round, never inside one
    events = api.telemetry.recorder.tail(10_000)
    rounds = [(b["ts"], e["ts"]) for b, e in zip(
        [e for e in events if e["name"] == "round" and e["ph"] == "B"],
        [e for e in events if e["name"] == "round" and e["ph"] == "E"])]
    assert len(rounds) == 6
    for s in _staged(api):
        assert not any(b <= s["ts"] <= e for b, e in rounds)
    assert _staged(api)[0]["ts"] < rounds[0][0] and rounds[3][1] < _staged(api)[1]["ts"] < rounds[4][0]


def test_sequential_mode_holds_no_store():
    api = _world(sim_mode="sequential", **IMAGES)
    api.train()
    assert api.pipeline_stats["store_stagings"] == 0 and not _staged(api)


def test_hierarchical_takes_the_store_the_same_way():
    from fedml_tpu.simulation.hierarchical_fl import HierarchicalFLAPI

    api = _world(HierarchicalFLAPI, group_num=2, group_comm_round=1, **IMAGES)
    api.train()
    assert api._store_stagings == 1 and _staged(api)[0]["args"]["copied"] is True


# -- what the executable holds -------------------------------------------

def test_lowered_round_gathers_from_a_rank4_operand():
    api = _world(**IMAGES)
    store = api._sample_store()
    n, nb, bs = store.mask.shape
    text = api._round_fn.lower(
        api.global_params, api.server_state, store,
        jnp.asarray(api.dataset.packed_num_samples), jnp.zeros((4,), jnp.int32),
        jax.random.PRNGKey(0), valid=jnp.ones((4,)),
    ).as_text()
    gathers = [line for line in text.splitlines() if "stablehlo.gather" in line]
    assert any(f"tensor<{n}x{nb}x{bs}x784xf32>" in line for line in gathers)
    assert f"{n}x{nb}x{bs}x28x28x1x" not in text  # no value of the 6-D store's shape
    assert f"tensor<4x{nb}x{bs}x28x28x1xf32>" in text  # local training sees its images


# -- the store checks what it got ----------------------------------------

class _Format:
    """What ``Array.format`` hands out, as far as the check reads it."""

    def __init__(self, order):
        self.layout = None if order is None else self
        self.major_to_minor = order


class _Arr:
    def __init__(self, order):
        self.format = _Format(order)


def test_reader_takes_the_order_from_the_arrays_format():
    assert sample_store._major_to_minor(_Arr((1, 2, 3, 0))) == (1, 2, 3, 0)
    assert sample_store._major_to_minor(_Arr(None)) is None
    assert sample_store._major_to_minor(jnp.zeros((2, 3, 4, 5))) == (0, 1, 2, 3)


@pytest.mark.parametrize("case", ["pin_takes", "pin_does_not_take", "pin_refused", "unknown"])
def test_layout_check_pins_or_warns(case, monkeypatch, caplog):
    api = _world(**IMAGES)
    packed = api.dataset.packed_train
    real_read, real_flatten = sample_store._major_to_minor, sample_store._flatten
    pins = []

    def flatten(x, shape, pin):
        pins.append(pin)
        if pin and case == "pin_refused":
            raise ValueError("no such layout here")
        return real_flatten(x, shape, pin)

    def read(x):
        if case == "unknown":
            return None
        if case == "pin_takes" and pins[-1]:
            return real_read(x)
        return (1, 2, 3, 0)  # the client axis minor-most: 784 pads more than N does

    monkeypatch.setattr(sample_store, "_flatten", flatten)
    monkeypatch.setattr(sample_store, "_major_to_minor", read)
    with caplog.at_level(logging.WARNING):
        store, facts = sample_store.stage(packed)
    assert np.array_equal(np.asarray(store.x), np.asarray(packed.x).reshape(store.x.shape))
    warned = [r.getMessage() for r in caplog.records if "sample store" in r.getMessage()]
    if case == "pin_takes":
        assert pins == [False, True] and not warned
        assert facts["major_to_minor"] == [0, 1, 2, 3]
        assert real_read(store.x) == (0, 1, 2, 3) and store.x.committed
    elif case == "unknown":
        assert pins == [False] and not warned and facts["major_to_minor"] == "unknown"
    else:
        assert pins == ([False, True] if case == "pin_does_not_take" else [False, True, False])
        assert facts["major_to_minor"] == [1, 2, 3, 0]
        assert any(str(list(store.x.shape)) in m and "client axis" in m for m in warned)
        assert (case == "pin_refused") == any("no pinned layout" in m for m in warned)


def test_a_pinned_store_goes_through_the_round(monkeypatch):
    """jit takes the explicitly laid-out (committed) argument as it is."""
    api = _world(**IMAGES)
    want = api._sample_store()
    reads = iter([(1, 2, 3, 0)])
    real_read = sample_store._major_to_minor
    monkeypatch.setattr(
        sample_store, "_major_to_minor", lambda x: next(reads, None) or real_read(x))
    pinned, facts = sample_store.stage(api.dataset.packed_train)
    assert facts["major_to_minor"] == [0, 1, 2, 3] and pinned.x.committed
    call = (jnp.asarray(api.dataset.packed_num_samples),
            jnp.asarray([0, 2, 3, 3], jnp.int32), jax.random.PRNGKey(1))
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
    a = api._round_fn(copy(api.global_params), (), pinned, *call)
    b = api._round_fn(copy(api.global_params), (), want, *call)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_flat_samples_are_checked_but_never_copied(monkeypatch, caplog):
    api = _world(**FLAT)
    monkeypatch.setattr(sample_store, "_major_to_minor", lambda x: (1, 2, 3, 0))
    with caplog.at_level(logging.WARNING):
        store, facts = sample_store.stage(api.dataset.packed_train)
    assert store is api.dataset.packed_train and facts["copied"] is False
    assert any("client axis" in r.getMessage() for r in caplog.records)


# -- on a mesh: the same spec on the same leading axes --------------------

def test_store_keeps_the_federations_mesh_placement(eight_devices):
    from jax.sharding import NamedSharding

    from fedml_tpu.parallel.mesh import build_mesh, federation_spec, shard_federation

    api = _world(client_num_in_total=8, client_num_per_round=4, **IMAGES)
    mesh = build_mesh(mesh_shape={"clients": 4, "data": 2})
    packed, _ = shard_federation(
        api.dataset.packed_train, api.dataset.packed_num_samples, mesh)
    store, facts = sample_store.stage(packed)
    assert store.x.sharding.is_equivalent_to(
        NamedSharding(mesh, federation_spec(mesh)), store.x.ndim)
    n, nb, bs = packed.mask.shape
    assert {s.data.shape for s in store.x.addressable_shards} == {(n // 4, nb, bs // 2, 784)}
    assert np.array_equal(np.asarray(store.x), np.asarray(packed.x).reshape(store.x.shape))


# -- the chip's compiler, without the chip --------------------------------

class _Caught(Exception):
    pass


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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


@pytest.mark.slow
def test_staged_round_compiled_for_a_v5e_is_client_major(one_chip, no_compile_cache):
    """The benchmark cell's sizes (ResNet-18(GN), bf16, 100 clients x 15
    batches of 64 CIFAR-shaped images, 32 a round), compiled for a
    described v5e: the store parameter lies client-major, no loop
    carries the gather, and no value of one client's images has the
    client axis for its lanes (the parent's ``bf16[1,15,64,32,32,3]
    {0,4,5,3,2,1}``, a 755 MB temporary for 5.9 MB of data)."""
    import re

    api = _world(
        dataset="cifar10", data_cache_dir="", model="resnet18", dtype="bfloat16",
        matmul_precision="default", client_num_in_total=100, client_num_per_round=32,
        batch_size=64, comm_round=1, partition_method="hetero", partition_alpha=0.5,
        synthetic_train_size=50_000, synthetic_test_size=2_000, learning_rate=0.03,
        shuffle=False, random_seed=0)
    x = api.dataset.packed_train.x
    assert x.shape == (100, 15, 64, 32, 32, 3) and x.dtype == jnp.bfloat16
    seen = {}

    def catcher(*args, **kwargs):
        seen["args"], seen["kwargs"] = args, kwargs
        raise _Caught

    jitted, api._round_fn = api._round_fn, catcher
    with pytest.raises(_Caught):
        api.train()
    assert seen["args"][2].x.shape == (100, 15, 64, 3072)

    def abstract(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        return a

    args, kwargs = jax.tree.map(abstract, (seen["args"], seen["kwargs"]))
    text = jitted.trace(*args, **kwargs).lower(lowering_platforms=("tpu",)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    (store,) = re.findall(r"bf16\[100,15,64,3072\]\{([\d,]*)[:}][^\n]* parameter\(", entry)
    assert store == "3,2,1,0"  # XLA writes minor to major: the client axis most-major
    for line in text.splitlines():
        if " while(" in line:
            assert "fed.gather" not in line
    assert "100,15,64,32,32,3]" not in text
    # a six-dimensional value whose minor-most (first-listed) dimension
    # is its leading axis has the clients or the cohort for its lanes
    assert not re.findall(r"bf16\[\d+,15,64,32,32,3\]\{0,", text)


def _sweep_script():
    """``scripts/grouped_matmul_sweep.py``, which reads each LM cell's
    chunk (rows, hidden, expert width, stacks) from its configuration."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts", "grouped_matmul_sweep.py")
    spec = importlib.util.spec_from_file_location("grouped_matmul_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", ["mellum2", "lfm2", "twotower"])
def test_grouped_matmul_kernels_compile_for_a_v5e_at_the_cells_widths(one_chip, no_compile_cache, cell):
    """``ops/grouped_matmul.py``'s forward and backward at each LM cell's
    chunk, compiled for a described v5e, Mosaic included: the tiling
    rule's blocks fit the scoped VMEM (a tiling that does not is refused
    here, not on the chip) and every product is a Mosaic call."""
    from fedml_tpu.ops import grouped_matmul as gm

    m, c, i, n_stacks = _sweep_script().cell_chunk(cell)
    assert gm.takes(m, c, i)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    stacks = [spec((8, c, i))] * (n_stacks - 1) + [spec((8, i, c))]
    sizes, xs = spec((8,), jnp.int32), spec((m, c))
    fwd = jax.jit(gm._kernel_forward).trace(xs, sizes, *stacks).lower(lowering_platforms=("tpu",)).compile()
    bwd = jax.jit(gm._kernel_backward).trace(xs, sizes, xs, *stacks).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in fwd.as_text() and "ragged" not in bwd.as_text()
