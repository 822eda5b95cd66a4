"""An evaluation reads samples, not slots (ISSUE 33;
``simulation/fedavg_api.dense_eval_split``, ``FedAvgAPI._eval_splits``).

CPU, tiny sizes: the four sums over the dense split are the sums over
the per-client packing, every real sample is there once, a federation
with no padding to lose gets its own arrays back (and the executable it
had), the split is made once per dataset and outside every round, and
every loop evaluates through the one accessor. What the shorter scan is
worth on the chip is the benchmark's to time (``eval_device_ms``).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core.types import Batches
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI
from fedml_tpu.simulation.fedavg_api import dense_eval_split, real_slots
from tests.conftest import make_args

IMAGES = dict(dataset="mnist", synthetic_train_size=160, synthetic_test_size=40)
TAGS = dict(dataset="stackoverflow_lr", synthetic_train_size=160, synthetic_test_size=40,
            synthetic_feature_dim=24)


def _world(cls=FedAvgAPI, **kw):
    base = dict(
        model="lr", client_num_in_total=5, client_num_per_round=3,
        partition_method="hetero", comm_round=2, epochs=1, batch_size=4,
        learning_rate=0.1, frequency_of_the_test=1,
    )
    base.update(kw)
    args = fedml_tpu.init(make_args(**base))
    ds = load(args)
    return cls(args, None, ds, models.create(args, ds.class_num))


def _staged(api):
    return [e for e in api.telemetry.recorder.tail(10_000) if e["name"] == "eval.staged"]


def _real(mask) -> int:
    return int(np.asarray(mask).sum())


def _dense_nb(packed: Batches) -> int:
    lanes, _, bs = packed.mask.shape
    return max(1, math.ceil(_real(packed.mask) / (lanes * bs)))


class _KeepStacked(FedAvgAPI):
    _keep_stacked = True  # the synchronous loop


# -- the same four sums ---------------------------------------------------

@pytest.mark.parametrize("data, extras", [(IMAGES, ()), (TAGS, ("tp", "fp", "fn"))],
                         ids=["classification", "tag_prediction"])
@pytest.mark.parametrize("split", ["train", "test"])
def test_sums_over_the_dense_split_are_the_packed_splits(data, extras, split):
    api = _world(**data)
    packed = getattr(api.dataset, f"packed_{split}")
    dense = api._eval_splits()[split == "test"]
    if split == "train":  # unequal clients: the packing pads, the dense split is shorter
        assert dense.num_batches == _dense_nb(packed) < packed.num_batches
    want = api._eval_all(api.global_params, packed)
    got = api._eval_all(api.global_params, dense)
    assert set(got) == {"loss_sum", "correct", "count", *extras}
    for k in ("count", "correct", *extras):
        assert float(got[k]) == float(want[k]), k
    assert float(want["count"]) > 0 and float(want["loss_sum"]) > 0
    assert float(got["loss_sum"]) == pytest.approx(float(want["loss_sum"]), rel=1e-6)


# -- every real sample once, the rest masked -----------------------------

@pytest.mark.parametrize("clients, batch_size, alpha", [(5, 4, 0.5), (6, 8, 0.1), (8, 2, 0.2)])
def test_every_real_sample_lands_once_and_the_tail_stays_masked(clients, batch_size, alpha):
    api = _world(client_num_in_total=clients, batch_size=batch_size, partition_alpha=alpha, **IMAGES)
    packed = api.dataset.packed_train
    lanes, nb, bs = packed.mask.shape
    # labels made unique, the image marked with its label
    ids = jnp.arange(lanes * nb * bs, dtype=jnp.int32).reshape(lanes, nb, bs)
    packed = packed.replace(y=ids, x=packed.x.at[..., 0, 0, 0].set(ids.astype(packed.x.dtype)))
    n_real = _real(packed.mask)
    assert [int(n) for n in real_slots(packed.mask, api.dataset.packed_test.mask)] == [
        n_real, _real(api.dataset.packed_test.mask)]
    dense, facts = dense_eval_split(packed, n_real)
    want_nb = _dense_nb(packed)
    assert dense.mask.shape == (lanes, want_nb, bs) and want_nb < nb
    assert dense.x.shape == (lanes, want_nb, bs) + packed.x.shape[3:]
    assert dense.x.dtype == packed.x.dtype and dense.y.dtype == packed.y.dtype
    assert facts == {"nb": nb, "nb_dense": want_nb, "real": n_real,
                     "slots": lanes * want_nb * bs,
                     "bytes": dense.x.nbytes + dense.y.nbytes + dense.mask.nbytes}
    mask = np.asarray(dense.mask).reshape(-1)
    assert mask.sum() == n_real and set(np.unique(mask)) <= {0.0, 1.0}
    # the real slots lead, what is left of the last batches is masked
    assert mask[:n_real].all() and not mask[n_real:].any()
    got_y = np.asarray(dense.y).reshape(-1)[:n_real]
    want_y = np.asarray(packed.y)[np.asarray(packed.mask) > 0]
    assert sorted(got_y) == sorted(want_y) and len(set(got_y)) == n_real
    # each label still sits with its own image
    got_x = np.asarray(dense.x).reshape((-1,) + packed.x.shape[3:])[:n_real]
    assert np.array_equal(got_x[:, 0, 0, 0].astype(np.int32), got_y)
    by_id = np.asarray(packed.x).reshape((-1,) + packed.x.shape[3:])
    assert np.array_equal(got_x, by_id[got_y])


# -- nothing to lose: the same arrays, the same executable ---------------

def _tokens(lanes, nb, bs, per_lane, t=8) -> Batches:
    """A ``[lanes, nb, bs]`` split of token sequences, lane ``i`` holding
    ``per_lane[i]`` of them from its head."""
    slot = np.arange(nb * bs).reshape(1, nb, bs)
    mask = (slot < np.asarray(per_lane).reshape(-1, 1, 1)).astype(np.float32)
    x = np.arange(lanes * nb * bs * t, dtype=np.int32).reshape(lanes, nb, bs, t) % 64
    return Batches(x=jnp.asarray(x), y=jnp.asarray((x + 1) % 64), mask=jnp.asarray(mask))


LM_SHAPES = {
    # 8 silos x 16 sequences at batch 4, 16 held out (the Mellum2 cell)
    "mellum2_train": ((8, 4, 4), [16] * 8), "mellum2_held_out": ((8, 1, 4), [2] * 8),
    # 100 sequences split 13,13,13,13,12,12,12,12 at batch 2, 16 held out (the LFM2 cell)
    "lfm2_train": ((8, 7, 2), [13] * 4 + [12] * 4), "lfm2_held_out": ((8, 1, 2), [2] * 8),
}


@pytest.mark.parametrize("name", LM_SHAPES)
def test_the_lm_cells_shapes_get_their_own_arrays_back(name):
    shape, per_lane = LM_SHAPES[name]
    packed = _tokens(*shape, per_lane)
    dense, facts = dense_eval_split(packed, sum(per_lane))
    assert dense is packed
    assert facts["nb"] == facts["nb_dense"] == shape[1] and facts["bytes"] == 0
    assert facts["slots"] == math.prod(shape) and facts["real"] == sum(per_lane)


def test_a_lm_federation_evaluates_the_arrays_and_the_executable_it_had():
    """The Mellum2 cell's packing at a toy width, through the API:
    sequences fill ``[8, 4, 4]``, the held-out ones ``[8, 1, 4]``."""
    from tests.test_moe_decoder import _fed_args

    args = _fed_args(client_num_in_total=8, batch_size=4,
                     synthetic_train_size=128, synthetic_test_size=16)
    ds = load(args)
    assert ds.packed_train.mask.shape == (8, 4, 4) and ds.packed_test.mask.shape == (8, 1, 4)
    api = FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    train, test = api._eval_splits()
    assert train is ds.packed_train and test is ds.packed_test
    assert api._eval_real_share == (128 + 16) / (128 + 32)
    (e,) = _staged(api)
    assert e["args"]["bytes"] == 0 and e["args"]["train_nb_dense"] == e["args"]["train_nb"] == 4


@pytest.mark.parametrize("split", ["train", "test"])
def test_a_full_federation_evaluates_the_arrays_and_the_executable_it_had(split):
    # 5 clients x 24 samples at batch 4: every slot holds a sample
    api = _world(partition_method="homo", dataset="mnist",
                 synthetic_train_size=120, synthetic_test_size=40)
    packed = getattr(api.dataset, f"packed_{split}")
    assert _real(packed.mask) == packed.mask.size
    dense = api._eval_splits()[split == "test"]
    assert dense is packed and dense.x is packed.x
    assert api._eval_real_share == 1.0
    assert (api._eval_all.lower(api.global_params, dense).as_text()
            == api._eval_all.lower(api.global_params, packed).as_text())


def test_the_dense_evaluation_is_the_same_scan_over_fewer_batches():
    """One executable shape a step: the lowered text over the dense
    split is the packed split's with the trip count changed and nothing
    else (the vmapped width and the batch are the parent's)."""
    api = _world(**IMAGES)
    packed, dense = api.dataset.packed_train, api._eval_splits()[0]
    lanes, nb, bs = packed.mask.shape
    old = api._eval_all.lower(api.global_params, packed).as_text()
    new = api._eval_all.lower(api.global_params, dense).as_text()
    assert f"tensor<{lanes}x{nb}x{bs}x" in old and f"tensor<{lanes}x{nb}x{bs}x" not in new
    assert f"tensor<{lanes}x{dense.num_batches}x{bs}x28x28x1xf32>" in new
    assert "module @jit_eval_all " in new
    assert len(old.splitlines()) == len(new.splitlines())


# -- made once, outside every round, for every loop ----------------------

@pytest.mark.parametrize("cls", [FedAvgAPI, _KeepStacked], ids=["pipeline", "sync_loop"])
def test_two_trains_stage_once_and_a_new_dataset_stages_again(cls):
    api = _world(cls, **IMAGES)
    assert api._eval_held is None and not _staged(api)  # nothing before the first train()
    api.train()
    held = api._eval_splits()
    api.train()
    assert len(_staged(api)) == 1 and api._eval_splits() is held
    packed = api.dataset.packed_train
    e = _staged(api)[0]["args"]
    assert e["train_nb"] == packed.num_batches and e["train_nb_dense"] == held[0].num_batches
    assert e["test_nb"] == e["test_nb_dense"] == api.dataset.packed_test.num_batches
    assert e["train_real"] == _real(packed.mask)
    assert e["bytes"] == sum(a.nbytes for a in jax.tree.leaves(held[0]))
    assert held[1] is api.dataset.packed_test
    # the benchmark's move: other images over the program's packing
    api.dataset = dataclasses.replace(api.dataset, packed_train=packed.replace(x=packed.x + 0))
    api.train()
    api.train()
    assert len(_staged(api)) == 2 and api._eval_splits()[0] is not held[0]
    # each staging lies before its call's first round, never inside one
    events = api.telemetry.recorder.tail(10_000)
    rounds = [(b["ts"], e["ts"]) for b, e in zip(
        [e for e in events if e["name"] == "round" and e["ph"] == "B"],
        [e for e in events if e["name"] == "round" and e["ph"] == "E"])]
    assert len(rounds) == 8
    first, second = (s["ts"] for s in _staged(api))
    assert first < rounds[0][0] and rounds[3][1] < second < rounds[4][0]


@pytest.mark.parametrize("other", ["sync_loop", "sequential"])
def test_every_loop_reports_the_pipelines_evaluation(other):
    pipeline = _world(**IMAGES)
    pipeline.train()
    if other == "sync_loop":
        api, tol = _world(_KeepStacked, **IMAGES), 0.0
    else:  # its rounds add the clients' updates in another order
        api, tol = _world(sim_mode="sequential", **IMAGES), 1e-5
    api.train()
    assert len(api.history) == len(pipeline.history) == 2
    for got, want in zip(api.history, pipeline.history):
        for k in ("train_loss", "test_loss", "train_acc", "test_acc"):
            assert got[k] == pytest.approx(want[k], rel=tol, abs=0.0), k
    assert api._eval_splits()[0].num_batches == pipeline._eval_splits()[0].num_batches
    assert len(_staged(api)) == 2  # the process's one ring: the pipeline's world, then this one


@pytest.mark.parametrize("algorithm", ["hierarchical", "dsgd"])
def test_the_other_loops_evaluate_through_the_accessor(algorithm):
    if algorithm == "hierarchical":
        from fedml_tpu.simulation.hierarchical_fl import HierarchicalFLAPI as cls
        kw = dict(group_num=2, group_comm_round=1)
    else:
        from fedml_tpu.simulation.decentralized import DecentralizedDSGDAPI as cls
        kw = dict(client_num_per_round=5)
    api = _world(cls, **kw, **IMAGES)
    seen = []
    real = api._eval_all
    api._eval_all = lambda params, split: seen.append(split) or real(params, split)
    api.train()
    train, test = api._eval_splits()
    assert train.num_batches < api.dataset.packed_train.num_batches
    assert len(seen) == 4 and all(a is b for a, b in zip(seen, (train, test) * 2))
    assert len(_staged(api)) == 1 and np.isfinite(api.history[-1]["train_loss"])


# -- the counter, and no sync of its own ---------------------------------

@pytest.mark.parametrize("rounds, freq, syncs", [(2, 1, 1.0), (10, 5, 0.3)])
def test_real_share_is_samples_over_slots_and_no_sync_is_added(rounds, freq, syncs):
    api = _world(comm_round=rounds, frequency_of_the_test=freq, **IMAGES)
    api.train()
    ds, (train, test) = api.dataset, api._eval_splits()
    real = _real(ds.packed_train.mask) + _real(ds.packed_test.mask)
    assert real == 200
    share = api.pipeline_stats["eval_real_share"]
    assert share == real / (train.mask.size + test.mask.size)
    packed_share = real / (ds.packed_train.mask.size + ds.packed_test.mask.size)
    assert packed_share < share <= 1.0
    assert api.telemetry.snapshot()["gauges"]["pipeline_eval_real_share"] == share
    assert _staged(api)[0]["args"]["real_share"] == share
    # one fetch per evaluation round, as before: the counts were read at set-up
    assert api.pipeline_stats["host_syncs_per_round"] == syncs


# -- on a mesh ------------------------------------------------------------

def test_a_mesh_placed_federation_keeps_its_placement(eight_devices):
    from fedml_tpu.simulation.simulator import SimulatorMesh

    args = make_args(
        model="lr", client_num_in_total=6, client_num_per_round=4,
        partition_method="hetero", partition_alpha=0.1, comm_round=2, epochs=1, batch_size=8,
        learning_rate=0.1, frequency_of_the_test=1, mesh_shape={"data": 4, "fsdp": 2},
        **IMAGES)
    args = fedml_tpu.init(args)
    ds = load(args)
    sim = SimulatorMesh(args, None, ds, models.create(args, ds.class_num))
    sim.run()
    api = sim.fl_trainer
    packed, (train, test) = api.dataset.packed_train, api._eval_splits()
    assert packed.mask.shape[0] == 8  # six clients padded to the mesh's cohort axis
    assert train.num_batches == _dense_nb(packed) < packed.num_batches
    for dense, source in zip(jax.tree.leaves(train), jax.tree.leaves(packed)):
        assert dense.sharding.is_equivalent_to(source.sharding, dense.ndim)
    assert test is api.dataset.packed_test
    want = api._eval_all(api.global_params, packed)
    got = api._eval_all(api.global_params, train)
    assert float(got["count"]) == float(want["count"]) == 160.0
    assert float(got["correct"]) == float(want["correct"])
    assert float(got["loss_sum"]) == pytest.approx(float(want["loss_sum"]), rel=1e-6)
    assert api.history[-1]["train_loss"] == pytest.approx(
        float(want["loss_sum"]) / 160.0, rel=1e-6)
