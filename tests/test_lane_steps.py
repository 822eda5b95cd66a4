"""Batches that hold no sample are not stepped (ISSUE 31).

``core/local_trainer.py``: handed ``steps``, the step loop ends there
and gives the full scan's bits. ``simulation/fedavg_api.build_round_fn``
with ``ragged``: a ``lax.map`` over the lanes, each to its own last real
batch, which the API asks for where clients leave batches empty and a
lane's step is heavy (``_HEAVY_LANE_STEP``); everything else keeps the
static scan. The two counters ``steps_run`` / ``steps_packed`` say how
often that engages. CPU, tiny sizes: results and counts. What the chip
makes of it is the benchmark's to time (PERF.md §6, PR 31).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core import sample_store
from fedml_tpu.core.aggregation import weighted_average
from fedml_tpu.core.frame import DefaultClientTrainer
from fedml_tpu.core.local_trainer import (
    _shuffle_batches,
    last_real_step,
    make_local_train_fn,
)
from fedml_tpu.core.partition import non_iid_partition_with_dirichlet_distribution
from fedml_tpu.core.types import Batches
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI
from fedml_tpu.simulation import fedavg_api
from fedml_tpu.simulation.fedavg_api import (
    build_round_fn,
    deterministic_client_sampling,
)
from tests.conftest import make_args

NB, BS, F, CLASSES = 15, 8, 5, 3


# -- a model that counts, and the parent's loop written out ---------------

def _apply_counted(p, x):
    # the counter reads 0 on a padding batch (packing pads with zeros)
    return x @ p["w"] + p["b"], {"seen": (x != 0).any(axis=-1).sum().astype(jnp.float32)}


def _loss(logits, y, m):
    lp = jax.nn.log_softmax(logits)
    per = -jnp.take_along_axis(lp, y[:, None], axis=1)[:, 0]
    count = m.sum()
    loss = (per * m).sum() / jnp.maximum(count, 1.0)
    return loss, {
        "loss": loss, "count": count,
        "correct": ((logits.argmax(-1) == y) * m).sum(),
    }


def _parent_local_train(optimizer, epochs, shuffle):
    """``local_train`` as it was before there was a bound: one scan over
    all ``num_batches``, the metrics stacked and summed after."""

    def local_train(params, batches, rng):
        def train_step(carry, batch):
            p, s = carry
            x, y, m = batch

            def batch_loss(p):
                logits, counters = _apply_counted(p, x)
                loss, metrics = _loss(logits.astype(jnp.float32), y, m)
                return loss, {**metrics, "counters": counters}

            (_, metrics), grads = jax.value_and_grad(batch_loss, has_aux=True)(p)
            updates, s_new = optimizer.update(grads, s, p)
            p_new = optax.apply_updates(p, updates)
            nonempty = m.sum() > 0
            p = jax.tree.map(lambda a, b: jnp.where(nonempty, a, b), p_new, p)
            s = jax.tree.map(lambda a, b: jnp.where(nonempty, a, b), s_new, s)
            return (p, s), metrics

        def epoch(carry, ep_rng):
            b = _shuffle_batches(batches, ep_rng) if shuffle else batches
            carry, metrics = jax.lax.scan(train_step, carry, (b.x, b.y, b.mask))
            return carry, {
                "loss_sum": (metrics["loss"] * metrics["count"]).sum().astype(jnp.float32),
                "correct": metrics["correct"].sum().astype(jnp.float32),
                "count": metrics["count"].sum().astype(jnp.float32),
                "seen": metrics["counters"]["seen"].sum().astype(jnp.float32),
            }

        (params, _), per_epoch = jax.lax.scan(
            epoch, (params, optimizer.init(params)), jax.random.split(rng, epochs))
        return params, jax.tree.map(lambda x: x[-1], per_epoch)

    return local_train


def _client(real_batches, last_fill=3):
    """One client packed to NB batches of BS: samples in the batches
    ``real_batches`` names (the last of them partly filled), zeros
    elsewhere."""
    rs = np.random.RandomState(0)
    mask = np.zeros((NB, BS), np.float32)
    for b in real_batches:
        mask[b] = 1.0
    mask[real_batches[-1], last_fill:] = 0.0
    x = rs.randn(NB, BS, F).astype(np.float32) * mask[..., None]
    y = rs.randint(0, CLASSES, (NB, BS))
    params = {"w": jnp.asarray(rs.randn(F, CLASSES).astype(np.float32)), "b": jnp.zeros(CLASSES)}
    return params, Batches(x=jnp.asarray(x), y=jnp.asarray(y), mask=jnp.asarray(mask))


OPTIMIZERS = {"sgd": lambda: optax.sgd(0.1), "adam": lambda: optax.adam(0.01)}


@pytest.mark.parametrize("real_batches", [(0, 1, 2), (0, 2)], ids=["tail", "hole"])
@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
@pytest.mark.parametrize("epochs", [1, 2])
@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_a_bound_gives_the_full_scans_bits(shuffle, epochs, opt, real_batches):
    """3 real batches of 15 (or 2 with an empty one between them, which
    is still stepped and reverted): parameters, metrics and the model's
    counter are the parent's full-length scan's, bit for bit."""
    params, client = _client(real_batches)
    steps = last_real_step(client.mask)
    assert int(steps) == real_batches[-1] + 1
    rng = jax.random.PRNGKey(5)
    want_p, want_m = jax.jit(_parent_local_train(OPTIMIZERS[opt](), epochs, shuffle))(
        params, client, rng)
    local_train = make_local_train_fn(
        _apply_counted, _loss, OPTIMIZERS[opt](), epochs, shuffle=shuffle)
    got_p, got_m = jax.jit(local_train)(params, client, rng, None, steps)
    for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(want_p)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for k in want_m:
        assert np.array_equal(np.asarray(got_m[k]), np.asarray(want_m[k])), k
    assert set(got_m) == set(want_m) and float(want_m["loss_sum"]) != 0.0
    # without a bound: the static scan, the parent's to the bit as well
    full_p, full_m = jax.jit(local_train)(params, client, rng)
    for a, b in zip(jax.tree.leaves((full_p, full_m)), jax.tree.leaves((want_p, want_m))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_last_real_step_reads_holes_and_empty_lanes():
    mask = np.zeros((4, 6, 2), np.float32)
    mask[0, :3] = 1
    mask[1, 0] = mask[1, 4, 0] = 1
    mask[3] = 1
    assert np.asarray(last_real_step(jnp.asarray(mask))).tolist() == [3, 5, 0, 6]


# -- the round: one lane after another, each to its own length ------------

def _federation(lengths, seed=0):
    """A packed federation of ``len(lengths)`` clients, client ``c``
    holding ``lengths[c]`` real batches of NB (its last one partly
    filled), and the round's collaborators over the counting model."""
    rs = np.random.RandomState(seed)
    n = len(lengths)
    mask = np.zeros((n, NB, BS), np.float32)
    for c, k in enumerate(lengths):
        mask[c, :k] = 1.0
        if k:
            mask[c, k - 1, 1 + c % (BS - 1):] = 0.0
    x = rs.randn(n, NB, BS, F).astype(np.float32) * mask[..., None]
    y = rs.randint(0, CLASSES, (n, NB, BS))
    packed = Batches(x=jnp.asarray(x), y=jnp.asarray(y), mask=jnp.asarray(mask))
    params = {"w": jnp.asarray(rs.randn(F, CLASSES).astype(np.float32)), "b": jnp.zeros(CLASSES)}
    return params, packed, jnp.asarray(mask.sum(axis=(1, 2)))


def _aggregate(global_params, server_state, stacked, weights, cohort, rng):
    return weighted_average(stacked, weights), server_state


def _round(ragged, **kw):
    local_train = make_local_train_fn(_apply_counted, _loss, optax.sgd(0.1), 1, shuffle=True)
    return jax.jit(build_round_fn(local_train, _aggregate, ragged=ragged, **kw))


LENGTHS_40 = [3, 15, 8, 9, 4, 12, 7, 8, 10, 3, 5, 9, 13, 6, 8, 11, 4, 9, 7, 14,
              8, 3, 10, 6, 9, 12, 5, 8, 7, 9, 11, 4, 8, 6, 10, 9, 3, 7, 13, 8]


def test_ragged_round_is_the_static_round_lane_for_lane():
    """32 ragged lanes one after another against one vmap of 32 over
    the static scan: stacked parameters lane for lane bit for bit (on
    this CPU and this linear model; across vmap widths the chip
    promises rounding only), the global parameters and the summed
    metrics within 1e-6 relative (a sum over lanes rounds by its order)."""
    params, packed, ns = _federation(LENGTHS_40)
    idx = jnp.asarray(np.random.RandomState(1).choice(40, 32, replace=False), jnp.int32)
    call = (params, (), packed, ns, idx, jax.random.PRNGKey(9))
    static = _round(False, keep_stacked=True)(*call)
    ragged = _round(True, keep_stacked=True)(*call)
    for a, b in zip(jax.tree.leaves(ragged[3]), jax.tree.leaves(static[3])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ragged[0]), jax.tree.leaves(static[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    for k in ("loss_sum", "correct", "count", "seen"):
        np.testing.assert_allclose(float(ragged[2][k]), float(static[2][k]), rtol=1e-6)
    lengths = np.asarray(LENGTHS_40)[np.asarray(idx)]
    assert float(static[2]["steps_run"]) == float(static[2]["steps_packed"]) == 32 * NB
    assert float(ragged[2]["steps_run"]) == lengths.sum()
    assert float(ragged[2]["steps_packed"]) == 32 * NB


def test_steps_run_counts_real_steps_and_padded_lanes_run_nothing():
    """A hand-built length vector with padded lanes scattered in it: a
    padded lane runs 0 steps and comes back as the global parameters."""
    params, packed, ns = _federation(LENGTHS_40)
    idx = np.arange(3, 35, dtype=np.int32)
    valid = np.ones(32, np.float32)
    valid[[2, 5, 6, 11, 13, 17, 19, 23, 24, 29]] = 0.0
    idx[valid == 0] = 1  # as pad_cohort_idx pads: a real client's index again, here the longest's
    lengths = np.asarray(LENGTHS_40)[idx]
    out = _round(True, keep_stacked=True)(
        params, (), packed, ns, jnp.asarray(idx), jax.random.PRNGKey(2),
        valid=jnp.asarray(valid))
    assert float(out[2]["steps_run"]) == (lengths * valid).sum() < lengths.sum()
    assert float(out[2]["steps_packed"]) == 32 * NB
    for leaf, g in zip(jax.tree.leaves(out[3]), jax.tree.leaves(params)):
        for lane in np.flatnonzero(valid == 0):
            assert np.array_equal(np.asarray(leaf[lane]), np.asarray(g))
    assert float(out[2]["count"]) == float(np.asarray(packed.mask)[idx[valid == 1]].sum())
    # a cohort of padded lanes only runs no step at all
    none = _round(True)(
        params, (), packed, ns, jnp.asarray(idx), jax.random.PRNGKey(2),
        valid=jnp.zeros(32, jnp.float32))
    assert float(none[2]["steps_run"]) == 0.0 == float(none[2]["count"])


def test_keep_stacked_returns_lanes_in_idx_order():
    """Each lane of the fourth output is its own client trained alone
    with the stream of its place in idx."""
    params, packed, ns = _federation(LENGTHS_40)
    idx = np.random.RandomState(4).choice(40, 32, replace=False).astype(np.int32)
    rng = jax.random.PRNGKey(11)
    out = _round(True, keep_stacked=True)(params, (), packed, ns, jnp.asarray(idx), rng)
    local_train = jax.jit(
        make_local_train_fn(_apply_counted, _loss, optax.sgd(0.1), 1, shuffle=True))
    rngs = jax.random.split(rng, 32)
    for lane in (0, 7, 8, 19, 31):
        alone, _ = local_train(params, jax.tree.map(lambda a: a[idx[lane]], packed), rngs[lane])
        for a, b in zip(jax.tree.leaves(out[3]), jax.tree.leaves(alone)):
            np.testing.assert_allclose(np.asarray(a[lane]), np.asarray(b), rtol=1e-6, atol=1e-7)
    # and no two lanes swapped: lanes differ, so a wrong order would show
    w = np.asarray(out[3]["w"])
    assert len({w[i].tobytes() for i in range(32)}) == 32


# -- through the API: the facts read off the dataset and the model --------

def _world(cls=FedAvgAPI, client_trainer=None, **kw):
    base = dict(
        dataset="synthetic", synthetic_train_size=1600, synthetic_test_size=64,
        model="lr", client_num_in_total=40, client_num_per_round=32,
        partition_method="hetero", partition_alpha=0.5, comm_round=3, epochs=1,
        batch_size=8, learning_rate=0.1, frequency_of_the_test=1,
    )
    base.update(kw)
    args = fedml_tpu.init(make_args(**base))
    ds = load(args)
    model = models.create(args, ds.class_num)
    trainer = client_trainer(model, args) if client_trainer is not None else None
    return cls(args, None, ds, model, client_trainer=trainer)


FULL = dict(dataset="mnist", synthetic_train_size=1280, partition_method="homo")
WEIGHTS = {"heavy": True, "light": False}


@pytest.fixture
def every_step_heavy(monkeypatch):
    """The tests' models are a logistic regression's size: count its
    lane-step heavy, as a ResNet-18's is (test_the_rule... below)."""
    monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)


def _lowered(api, bucket):
    packed = api._sample_store()
    return api._round_fn.lower(
        api.global_params, api.server_state, packed,
        jnp.asarray(api.dataset.packed_num_samples),
        jnp.zeros((bucket,), jnp.int32), jax.random.PRNGKey(0),
        valid=jnp.ones((bucket,), jnp.float32),
    ).as_text()


def _static_text(api, bucket):
    fn = jax.jit(build_round_fn(
        api._local_train, api._aggregate, api._preprocess,
        sample_shape=sample_store.sample_shape(api.dataset.packed_train),
    ), donate_argnums=(0, 1))
    return fn.lower(
        api.global_params, api.server_state, api._sample_store(),
        jnp.asarray(api.dataset.packed_num_samples),
        jnp.zeros((bucket,), jnp.int32), jax.random.PRNGKey(0),
        valid=jnp.ones((bucket,), jnp.float32),
    ).as_text()


def test_the_rule_reads_weights_and_batch_size():
    """A ResNet-18 step of 64 images, or of 8, is heavy; a 420k-parameter
    CNN's of 32 and a logistic regression's are not (PERF.md §6's
    readings); and the floor is what the API reads off its own model."""
    heavy = fedavg_api._HEAVY_LANE_STEP
    assert 421_642 * 32 < heavy <= 11_173_962 * 8 < 11_173_962 * 64
    api = _world()
    assert api._lane_step_floor() == (60 * 10 + 10) * 8 < heavy
    assert api._has_empty_batches() and not api._ragged


def test_full_federation_lowers_with_a_static_trip_count(every_step_heavy):
    """Every client fills its batches (homo, 40 x 32 samples, batch 8):
    the round is the static scan's text -- no loop bound that is a
    value, no sort."""
    api = _world(shuffle=False, **FULL)
    assert not api._has_empty_batches() and not api._ragged
    text = _lowered(api, 32)
    assert text == _static_text(api, 32)
    assert "stablehlo.sort" not in text
    assert api._round_exec_name() == "simulation.round_fn"


def test_light_ragged_federation_lowers_the_static_scan_too():
    api = _world(shuffle=False)
    assert api._has_empty_batches() and not api._ragged
    assert _lowered(api, 32) == _static_text(api, 32)


def test_heavy_ragged_federation_lowers_a_bound_and_never_a_sort(every_step_heavy):
    api = _world(shuffle=False)  # (the reshuffle sorts)
    assert api._has_empty_batches() and api._ragged
    text = _lowered(api, 32)
    assert text != _static_text(api, 32)
    assert "stablehlo.sort" not in text  # lanes keep their place in idx
    assert api._round_exec_name() == "simulation.round_fn_ragged"


class _ThreeArgumentTrainer(DefaultClientTrainer):
    """A custom trainer's train fn takes (params, batches, rng) and
    nothing else."""

    def make_train_fn(self, args):
        stock = super().make_train_fn(args)

        def train(params, batches, rng):
            return stock(params, batches, rng)

        return train


def test_custom_trainer_round_is_the_parents_bit_for_bit(every_step_heavy):
    api = _world(client_trainer=_ThreeArgumentTrainer, shuffle=False)
    assert api._has_empty_batches() and not api._ragged
    text = _lowered(api, 32)
    assert text == _static_text(api, 32) and "stablehlo.sort" not in text
    # and it runs: the engine hands it three arguments
    stock = _world(shuffle=False)
    api.global_params = jax.tree.map(jnp.copy, stock.global_params)
    api.train()
    stock.train()
    for a, b in zip(jax.tree.leaves(api.global_params), jax.tree.leaves(stock.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _cohort_lengths(api, rounds):
    lengths = np.ceil(np.asarray(api.dataset.packed_num_samples) / 8)
    return [lengths[deterministic_client_sampling(r, 40, 32)] for r in range(rounds)]


@pytest.mark.parametrize("heavy", WEIGHTS.values(), ids=WEIGHTS)
def test_one_trace_a_bucket_and_the_share_in_the_records(heavy, monkeypatch):
    if heavy:
        monkeypatch.setattr(fedavg_api, "_HEAVY_LANE_STEP", 0)
    api = _world()
    api.train()
    api.train()
    assert api._round_trace_count == 1
    nb = api.dataset.packed_train.num_batches
    want = [c.sum() if heavy else 32 * nb for c in _cohort_lengths(api, 3)]
    for rec, steps in zip(api.history[-3:], want):
        assert rec["steps_run"] == steps and rec["steps_packed"] == 32 * nb
    share = api.pipeline_stats["lane_steps_run_share"]
    assert share == pytest.approx(sum(want) / (3 * 32 * nb), abs=1e-6)
    assert (share < 1.0) == heavy
    assert api.pipeline_stats["host_syncs_per_round"] == 1.0
    gauges = api.telemetry.snapshot()["gauges"]
    assert gauges["pipeline_lane_steps_run_share"] == pytest.approx(share)


def test_share_counts_every_round_of_a_call_with_no_sync_of_its_own(every_step_heavy):
    """Evaluation on 2 rounds in 6: the other rounds' counts ride to the
    host with the next evaluation round's record."""
    api = _world(comm_round=6, frequency_of_the_test=5)
    api.train()
    nb = api.dataset.packed_train.num_batches
    want = sum(c.sum() for c in _cohort_lengths(api, 6))
    assert [h["round"] for h in api.history] == [0, 5]
    assert api.pipeline_stats["lane_steps_run_share"] == pytest.approx(
        want / (6 * 32 * nb), abs=1e-6)
    assert api.pipeline_stats["host_syncs_per_round"] == pytest.approx(2 / 6, abs=1e-3)


def test_full_federation_reads_a_share_of_one(every_step_heavy):
    api = _world(**FULL)
    api.train()
    assert api.pipeline_stats["lane_steps_run_share"] == 1.0


def test_ragged_cohort_agrees_with_the_sequential_reference(every_step_heavy):
    """tests/test_fedavg_oracle.py's second oracle on a ragged cohort
    of 32: the python loop over clients, each through the static scan,
    against the round that ends its loops early."""
    results = {}
    for mode in ("vectorized", "sequential"):
        api = _world(sim_mode=mode, comm_round=2, shuffle=False)
        assert api._ragged
        api.train()
        results[mode] = api.global_params
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        results["vectorized"], results["sequential"])


# -- the benchmark's cohort: the slot table of ISSUE 31 -------------------

def test_resnet_cells_slot_table():
    """The ResNet cells' partition (Dirichlet(0.5) over 100 clients,
    partition seed 0) and the cohorts of rounds 0..9, 32 of 100: the
    share of 32 x 15 lane-steps a round that lanes run each to its own
    length (ISSUE 31's table at W = 1: what `lane_steps_run_share` must
    read on the chip), and what one count for all would have left."""
    labels = np.random.RandomState(0).randint(0, 10, 50000)
    part = non_iid_partition_with_dirichlet_distribution(labels, 100, 10, 0.5, seed=0)
    lengths = np.ceil(np.asarray([len(part[c]) for c in range(100)]) / 64)
    assert lengths.max() == 15
    cohorts = [lengths[deterministic_client_sampling(r, 100, 32)] for r in range(10)]
    assert sum(c.sum() for c in cohorts) / (10 * 32 * 15) == pytest.approx(0.55875)
    assert sum(32 * c.max() for c in cohorts) / (10 * 32 * 15) == pytest.approx(0.933, abs=1e-3)
